#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the reference against
itself computed as a control (one precision below the configuration's) and
with planted faults, on several seeds, at the cell's own sizes.

    python3 chipbench/controls.py --workload <cell> --seeds 1 2 3

For each seed it prints every compared number of the control and of each
fault, read against the full-precision reference exactly as a run reads the
program. The benchmark's own runs never run this; ``PERF.md`` records what
it printed and the limits set from it.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def fp8(x):
    import jax.numpy as jnp
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def bf16_np(x):
    import ml_dtypes
    import numpy as np
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def half_rows(b):
    """The fault "half of the batch left out, the mean taken over the
    rest": keep the first half of every row-major input."""
    import jax
    return jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], b)


def steady_train(cell, cfg, mod, seeds_: list) -> list:
    import jax
    import jax.numpy as jnp

    from bench import seeds
    from bench.steady_train import compare, reference_readings

    traffic = cell["traffic_params"]
    dmd_on = bool(traffic["dmd"])
    acfg, model, mesh = mod.build(cfg, traffic, jax.devices()[:1])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shape = mod.traffic_shape(cfg, traffic)
    step0 = int(traffic["start_step"])
    lower = {jnp.dtype(jnp.bfloat16): jnp.float8_e4m3fn}
    rows = []
    for seed in seeds_:
        kw = dict(mod=mod, cfg=cfg, shapes=shapes,
                  k_w=seeds.key_for(seed, 0), k_data=seeds.key_for(seed, 1),
                  shape=shape, ring=int(traffic["ring"]), step0=step0,
                  gram=dmd_on)
        ref = reference_readings(**kw)
        variants = {
            "control_fp8": reference_readings(
                cast=fp8, store=lambda dt: lower.get(jnp.dtype(dt), dt),
                **kw),
            "fault_half_batch": reference_readings(batch_filter=half_rows,
                                                   **kw)}
        for name, got in variants.items():
            checks = compare(got, ref, cfg["limits"])
            rows.append({"seed": seed, "variant": name,
                         **{c.name: c.value for c in checks}})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def time_to_target(cell, cfg, mod, seeds_: list) -> list:
    import jax
    import numpy as np

    from bench import seeds, weights
    from bench.time_to_target import _flat, load_data, reference_checks

    acfg, model = mod.build(cfg, cell["traffic_params"])
    data = load_data(cfg)
    batch = {"x": data["x_train"], "y": data["y_train"]}
    x = np.asarray(batch["x"], np.float64)
    y = np.asarray(batch["y"], np.float64)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    variants = {"control_bf16": (bf16_np, x, y),
                "fault_half_batch": (mod._identity, half_rows(x),
                                     half_rows(y))}
    rows = []
    for seed in seeds_:
        p0 = jax.device_get(weights.make(seeds.key_for(seed, 0), shapes,
                                         mod.weight_rule))
        for name, (cast, cx, cy) in variants.items():
            losses, g1, layers = mod.adam_steps(
                mod.to_lists(p0), cx, cy, cfg["optimizer"], 3, cast=cast)
            grad1 = [float(np.linalg.norm(t)) for t in _flat(g1)]
            checks = reference_checks(mod, cfg, batch, p0, losses, grad1,
                                      _tree(layers))
            rows.append({"seed": seed, "variant": name,
                         **{c.name: c.value for c in checks}})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def _tree(layers: list) -> dict:
    return {f"l{i}": {"w": w, "b": b} for i, (w, b) in enumerate(layers)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench import harness
    harness.import_program()
    cell = harness.find_cell(args.workload)
    cfg, mod = harness.find_config(cell["config"])
    run = {"steady_train": steady_train,
           "time_to_target": time_to_target}[cell["driver"]]
    run(cell, cfg, mod, args.seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
