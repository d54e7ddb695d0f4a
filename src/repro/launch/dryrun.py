import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, with ZERO device allocation (ShapeDtypeStruct
inputs only):
  * the sharding config is coherent (GSPMD partitions the whole step),
  * memory fits (memory_analysis peak bytes/device vs the 16 GB v5e budget),
  * and extracts cost_analysis FLOPs/bytes + the collective op inventory
    (operand bytes parsed from the HLO text) for §Roofline.

NOTE (§Roofline methodology): cost_analysis counts lax.scan bodies ONCE
(probed empirically), so the per-cell JSON records both the raw compile
numbers and the scan trip counts; benchmarks/roofline.py scales per-layer
unit lowerings by trip count for the roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch ID] [--shape NAME]
      [--mesh single|multi|both] [--out results/dryrun] [--skip-existing]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs, shape_by_name, STANDARD_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch import inputs as inputs_mod
from repro.distributed.sharding import mesh_context, partition_specs
from repro.models.transformer import LanguageModel
from repro.train.state import TrainState
from repro.train.step import make_train_step, resolve_grad_accum
from jax.sharding import NamedSharding, PartitionSpec as P

HBM_BYTES = 16 * 1024**3       # v5e per-chip budget

# Collective parsing lives in the shared static-audit layer since ISSUE 6
# (repro.audit.hlo — one regex, one dtype map for the dry-run inventory,
# the dist_worker audits and the collective-budget pass alike); re-exported
# here for the roofline/multipod benchmarks.
from repro.audit.hlo import parse_collectives  # noqa: E402,F401


def scan_trip_counts(model: LanguageModel):
    return {f"seg{i}": seg.count for i, seg in enumerate(model.plan)}


def build_step(acfg, shape, mesh, scan_layers: bool = True):
    """Returns (fn, example_args, in_shardings, model, donate, info) for
    one cell; ``info`` is a dict of cell metadata (the train cell's
    packed-arena bucket count, DESIGN.md §7, plus the dmd.scope and the
    number of coefficient solves one jump costs under it, DESIGN.md §9 —
    None for serving cells)."""
    info = {"arena_buckets": None, "dmd_scope": None, "jump_solves": None}
    mc = acfg.model
    model = LanguageModel(mc, chunk_k=min(1024, shape.seq_len),
                          remat=acfg.parallel.remat, scan_layers=scan_layers,
                          pad_heads_to=mesh.shape["model"])
    batch, batch_specs = inputs_mod.input_specs(acfg, shape, mesh)

    if shape.kind == "train":
        params = model.init(abstract=True)
        from repro.optim import make_optimizer
        opt = make_optimizer(acfg.optimizer)
        opt_state = jax.eval_shape(opt.init, params)
        from repro.core.accelerator import DMDAccelerator
        acc = DMDAccelerator(acfg.dmd, mesh=mesh,
                             stack_dims=model.param_stack_dims())
        bufs = acc.init(params)    # abstract-aware: ShapeDtypeStruct leaves
        grams = acc.init_grams(bufs)
        # controller state (None unless dmd.controller.enabled): tiny
        # (n_groups,) leaves, abstract like everything else here
        ctrl = acc.init_controller(abstract=True)
        state = TrainState(params, opt_state,
                           jax.ShapeDtypeStruct((), jnp.int32), bufs, grams,
                           ctrl)
        # arena=: bucket-table specs for the packed block-major ring buffers
        # (abstract like everything else here — DESIGN.md §7)
        st_specs = inputs_mod.state_specs(state, mesh,
                                          plans=acc.plans_for(params),
                                          arena=acc.arena_for(params))
        step = make_train_step(model, acfg, mesh=mesh,
                               global_batch=shape.global_batch, acc=acc)
        table = acc.arena_for(params)
        info["arena_buckets"] = len(table)
        info["dmd_scope"] = acc.scope
        # bucket scope: one dmd_coefficients system per bucket, not per
        # leaf — this is the batched-solve row count a full jump traces
        info["jump_solves"] = sum(
            b.gram_lead(acc.scope) for b in table.values())
        # third arg = the step index (the per-group DMD slot vector is
        # derived from it in-trace — train/step.py)
        args = (state, batch, jax.ShapeDtypeStruct((), jnp.int32))
        shardings = (inputs_mod.shardings_of(st_specs, mesh),
                     inputs_mod.shardings_of(batch_specs, mesh),
                     NamedSharding(mesh, P()))
        return step, args, shardings, model, (0,), info  # donate TrainState

    # serving cells
    params = model.init(abstract=True)
    p_specs = partition_specs(params, mesh)
    if shape.kind == "prefill":
        caches = model.init_cache(shape.global_batch, shape.seq_len,
                                  abstract=True)
        c_specs = inputs_mod.cache_partition_specs(caches, mesh)

        def prefill_step(params, batch, caches):
            return model.prefill(params, batch, caches)

        args = (params, batch, caches)
        shardings = (inputs_mod.shardings_of(p_specs, mesh),
                     inputs_mod.shardings_of(batch_specs, mesh),
                     inputs_mod.shardings_of(c_specs, mesh))
        return prefill_step, args, shardings, model, (2,), info  # donate caches

    # decode: one new token against a cache of seq_len
    caches = model.init_cache(shape.global_batch, shape.seq_len,
                              abstract=True)
    c_specs = inputs_mod.cache_partition_specs(caches, mesh)

    def serve_step(params, batch, caches):
        logits, new_caches = model.decode_step(params, batch, caches)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, new_caches

    args = (params, batch, caches)
    shardings = (inputs_mod.shardings_of(p_specs, mesh),
                 inputs_mod.shardings_of(batch_specs, mesh),
                 inputs_mod.shardings_of(c_specs, mesh))
    return serve_step, args, shardings, model, (2,), info   # donate caches


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             skip_existing: bool = False) -> dict:
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_kind}.json"
    if skip_existing and out_path.exists():
        rec = json.loads(out_path.read_text())
        if rec.get("status") == "ok":
            print(f"[skip] {arch} {shape_name} {mesh_kind}")
            return rec

    acfg = get_config(arch)
    shape = shape_by_name(shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind}
    if shape_name not in acfg.shapes:
        rec["status"] = "skipped"
        rec["note"] = acfg.skip_notes
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[SKIP by design] {arch} {shape_name}: {acfg.skip_notes}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        with mesh_context(mesh):
            fn, args, shardings, model, donate, info = build_step(
                acfg, shape, mesh)
            lowered = jax.jit(fn, in_shardings=shardings,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            ma = compiled.memory_analysis()
            mstat = lambda name: int(getattr(ma, name, 0) or 0)
            ca = compiled.cost_analysis() or {}
            if isinstance(ca, (list, tuple)):    # older jaxlibs: one dict
                ca = ca[0] if ca else {}         # per executable
            hlo = compiled.as_text()
            coll, coll_counts = parse_collectives(hlo)

            n_dev = mesh.devices.size
            rec.update({
                "status": "ok",
                "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1),
                "n_devices": n_dev,
                # getattr-guarded: CPU jaxlibs lack some CompiledMemoryStats
                # fields (peak_memory_in_bytes is TPU-only on 0.4.x)
                "memory": {
                    "argument_bytes": mstat("argument_size_in_bytes"),
                    "output_bytes": mstat("output_size_in_bytes"),
                    "temp_bytes": mstat("temp_size_in_bytes"),
                    "peak_bytes": mstat("peak_memory_in_bytes"),
                    "alias_bytes": mstat("alias_size_in_bytes"),
                },
                "fits_hbm": bool(
                    (mstat("argument_size_in_bytes")
                     - mstat("alias_size_in_bytes"))
                    + mstat("peak_memory_in_bytes") < HBM_BYTES * 1.0),
                "cost": {"flops": ca.get("flops"),
                         "bytes_accessed": ca.get("bytes accessed")},
                "collective_bytes_local": coll,
                "collective_counts": coll_counts,
                "scan_trip_counts": scan_trip_counts(model),
                "grad_accum": resolve_grad_accum(acfg, mesh,
                                                 shape.global_batch)
                if shape.kind == "train" else None,
                # packed-arena audit (DESIGN.md §7): how many bucket
                # launches the DMD data passes cost per recorded step
                "dmd_arena_buckets": info["arena_buckets"],
                # bucket-scope audit (DESIGN.md §9): which Koopman scope
                # the cell trains under and how many coefficient solves
                # (batched eig callback rows) one full jump costs
                "dmd_scope": info["dmd_scope"],
                "dmd_jump_solves": info["jump_solves"],
            })
            print(f"[ok] {arch} {shape_name} {mesh_kind}: "
                  f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
                  f"args/dev {mstat('argument_size_in_bytes')/2**30:.2f}GiB "
                  f"peak/dev {mstat('peak_memory_in_bytes')/2**30:.2f}GiB "
                  f"colls {sum(coll_counts.values())}")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=20)
        print(f"[FAIL] {arch} {shape_name} {mesh_kind}: "
              f"{type(e).__name__}: {str(e)[:400]}")
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else [s.name for s in STANDARD_SHAPES]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                rec = run_cell(arch, shape_name, mesh_kind, out_dir,
                               args.skip_existing)
                st = rec["status"]
                n_ok += st == "ok"
                n_fail += st == "error"
                n_skip += st == "skipped"
    print(f"\nDRY-RUN SUMMARY: ok={n_ok} fail={n_fail} "
          f"skipped-by-design={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
