"""Subprocess worker for distributed tests (run with
XLA_FLAGS=--xla_force_host_platform_device_count=N).

Modes (argv[1]):
  train <ndev> <ckpt_dir?>   3 sharded train steps; prints loss + checksum
  gram                        sharded DMD gram == numpy
  gradsync                    int8 cross-pod psum correctness
  elastic_save <dir>          train 2 steps on (2,2) mesh, checkpoint
  elastic_restore <dir>       restore on (4,) x model=2... different mesh,
                              run 1 more step, print checksum
  gram_save <dir> keep|zero|hetero
                              train through full DMD window(s) on (2,2),
                              checkpoint (zero: strip dmd_gram — the
                              pre-streaming format; hetero: TWO schedule
                              groups with different m, saved at a step
                              where both windows are complete)
  gram_restore <dir> [hetero] restore on the REMAPPED (4,2) mesh; check every
                              running Gram == gram_matrix oracle; GRAMS_OK
  sharded_kernels             pallas_shard_map route vs dot_general oracle
                              across window wraps (fsdp/tp-sharded + stacked
                              leaves, forced interpret-mode Pallas), plus the
                              update_grams HLO all-gather audit
  ctrl_save <dir> jump|mid    controller-enabled run on (2,2), SIGTERM
                              raised on the exact jump step (5) or
                              mid-window (7) -> preempt-save; prints the
                              CTRL line (counters / s_eff / relax_eff /
                              slot vector at the saved step)
  ctrl_restore <dir> <step>   restore on the REMAPPED (4,2) mesh; print the
                              same CTRL line (bit-exact vs ctrl_save's),
                              assert the cooldown/window phase re-derives
                              from the restored step, run to step 14 and
                              check the remaining gated jumps fire; CTRL_OK
  resident_save <dir>         ARENA-RESIDENT fit (adam, arena_native on) on
                              (2,2) for 6 steps with a sharded bucket;
                              prints the params checksum
  resident_restore <dir>      restore on the REMAPPED (4,2) mesh: the
                              leaf-wise checkpoint re-places per-leaf
                              against the new mesh, re-residentizes into
                              the new mesh's buckets, and one more fit
                              step runs on the resident state; RESIDENT_OK
  flash_shard_map             a reduced float32 whisper-base's loss and
                              grads on a (2,2) mesh, head-TP: the forced
                              kernel route (the flash kernel per shard
                              under shard_map, interpret mode) against the
                              jnp route; FLASH_ERR
"""
import os
import sys

n_dev = os.environ.get("TEST_NDEV", "8")
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.configs.base import DMDConfig, OptimizerConfig, TrainConfig
from repro.data.tokens import batch_for_step
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.models.transformer import LanguageModel
from repro.train import Trainer
from repro.train.state import TrainState


def small_acfg(hetero=False, controller=False):
    from repro.configs.base import DMDControllerConfig
    acfg = get_config("tinyllama-1.1b")
    mc = reduced(acfg.model, n_layers=2, d_model=32, d_ff=64, vocab_size=128,
                 n_heads=4, n_kv_heads=2, head_dim=8)
    groups = ()
    if hetero:
        # Two schedule groups with DIFFERENT windows: norm scales (both the
        # unstacked final_norm and the scan-stacked ln1/ln2) get m=3,
        # everything else the default m=4. Both windows complete at step 13
        # (default jumps at 5, 9, 13; norms at 4, 7, 10, 13).
        from repro.core.schedule import DMDGroupRule
        groups = (DMDGroupRule(name="norms", path_regex="norm|/ln", m=3),)
    return dataclasses.replace(
        acfg, model=mc,
        dmd=DMDConfig(enabled=True, m=4, s=8, tol=1e-4, warmup_steps=2,
                      cooldown_steps=0, groups=groups,
                      controller=DMDControllerConfig(enabled=controller)),
        optimizer=OptimizerConfig(name="adam", lr=1e-3, schedule="constant"),
        parallel=dataclasses.replace(acfg.parallel, grad_accum=2,
                                     remat="none"),
        train=TrainConfig(global_batch=8, seq_len=16))


def checksum(tree):
    return float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                     for l in jax.tree_util.tree_leaves(tree)))


def run_train(mesh_shape, axis_names, steps=6):
    acfg = small_acfg()
    mesh = make_mesh(mesh_shape, axis_names)
    model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
    with mesh_context(mesh):
        trainer = Trainer(model, acfg, mesh=mesh)
        state = trainer.init_state()
        losses = []
        for step in range(steps):
            batch = batch_for_step(0, step, 8, 16, acfg.model.vocab_size)
            state, m = trainer.train_step(state, batch,
                                          jnp.asarray(step, jnp.int32))
            groups = trainer.acc.apply_groups(step)
            if groups:
                relax = jnp.asarray(trainer.acc.relax_vector(step))
                state, _ = trainer.dmd_step(state, relax, groups=groups)
            losses.append(float(m["loss"]))
        return losses, checksum(state.params)


# The largest-all-gather scan is the shared static-audit primitive since
# ISSUE 6 (repro.audit.hlo — one regex, one dtype map for both shard_map
# workers here AND the collective-budget pass the CLI runs).
from repro.audit.hlo import max_allgather_bytes  # noqa: E402


def run_sharded_kernels():
    """pallas_shard_map route == dot_general oracle on an 8-device mesh.

    Leaves cover the shapes the flat kernels could never serve under GSPMD:
    a 2-D fsdp+tp-sharded matrix, a tp-sharded vector, a bf16 fsdp+tp leaf
    (gram_upcast=False semantics: fp32 accumulation happens in-kernel), and
    a stacked (scan-over-layers) leaf. The Pallas bodies run through the
    interpreter (forced backend) inside shard_map. Also audits the lowered
    update_grams HLO: the whole point of the route is that NO buffer-sized
    all-gather appears (DESIGN.md §3.4).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import dmd as dmd_mod, leafplan
    from repro.core import snapshots as snap
    from repro.kernels import ops, sharded

    mesh = make_mesh((2, 4), ("data", "model"))
    m = 5
    cfg = DMDConfig(m=m, s=8, tol=1e-4, anchor="first", warmup_steps=0,
                    cooldown_steps=0)
    rng = np.random.default_rng(0)

    def mk(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    params = {
        "wqkv": mk((64, 32)),                    # ("data", "model"): fsdp+tp
        "A_log": mk((32,)),                      # ("model",): tp vector
        "w_gate": mk((64, 32), jnp.bfloat16),    # bf16 fsdp+tp leaf
        "seg0": {"attn": {"wqkv": mk((6, 64, 32))}},   # stacked
    }
    stack_dims = {"wqkv": 0, "A_log": 0, "w_gate": 0,
                  "seg0": {"attn": {"wqkv": 1}}}
    plans = leafplan.build_plans(params, cfg, mesh, stack_dims)
    flat_plans = leafplan.plan_entries(plans)
    assert all(p.route == "pallas_shard_map" for p in flat_plans), \
        [(p.path, p.route, p.sharded) for p in flat_plans]
    assert {p.path: p.psum_axes() for p in flat_plans} == {
        "/wqkv": ("data", "model"), "/A_log": ("model",),
        "/w_gate": ("data", "model"),
        "/seg0/attn/wqkv": ("data", "model")}

    place = lambda t, specs: jax.tree_util.tree_map(
        lambda l, s: jax.device_put(l, NamedSharding(mesh, s)), t, specs)
    params = place(params, jax.tree_util.tree_map(
        lambda pl: pl.param_spec, plans, is_leaf=leafplan.is_plan_leaf))

    ops.set_backend("pallas")                    # interpret-mode Pallas bodies
    try:
        with jax.set_mesh(mesh):
            bufs = snap.init_buffers(params, cfg, plans)
            grams = snap.init_grams(bufs, cfg, plans)

            def upd(g, b, p, slot):
                b = snap.record(b, p, slot, plans)
                return b, snap.update_grams(g, b, p, slot, cfg, plans)
            upd_jit = jax.jit(upd)

            for window in range(2):              # across a full cyclic wrap
                for slot in range(m):
                    params = jax.tree_util.tree_map(
                        lambda p: (p + (0.03 * jnp.asarray(
                            rng.normal(size=p.shape), jnp.float32)
                        ).astype(p.dtype)), params)
                    bufs, grams = upd_jit(grams, bufs, params, slot)
                # window-complete: streaming == oracle (DESIGN.md §2)
                err = 0.0
                for key, pl in ((("wqkv",), plans["wqkv"]),
                                (("A_log",), plans["A_log"]),
                                (("w_gate",), plans["w_gate"]),
                                (("seg0", "attn", "wqkv"),
                                 plans["seg0"]["attn"]["wqkv"])):
                    b = bufs; g = grams
                    for k in key:
                        b, g = b[k], g[k]
                    oracle = dmd_mod.gram_matrix(
                        b, anchor=cfg.anchor, stack_dims=pl.stack_dims,
                        upcast=cfg.gram_upcast)
                    scale = max(float(jnp.max(jnp.abs(oracle))), 1.0)
                    tol = 3e-2 if b.dtype == jnp.bfloat16 else 1e-5
                    e = float(jnp.max(jnp.abs(g - oracle))) / scale
                    assert e < tol, (key, window, e)
                    err = max(err, e)
            print("STREAM_ERR", f"{err:.2e}")

            # gram_upcast=False + bf16 snapshot storage: the kernel's fused
            # in-VMEM upcast must match the bf16-accumulation oracle
            import dataclasses as _dc
            cfg_bf = _dc.replace(cfg, snapshot_dtype="bfloat16",
                                 gram_upcast=False)
            plans_bf = leafplan.build_plans(params, cfg_bf, mesh, stack_dims)
            bufs_bf = snap.init_buffers(params, cfg_bf, plans_bf)
            grams_bf = snap.init_grams(bufs_bf, cfg_bf, plans_bf)
            upd_bf = jax.jit(lambda g, b, p, slot: (
                lambda nb: (nb, snap.update_grams(g, nb, p, slot, cfg_bf,
                                                  plans_bf)))(
                snap.record(b, p, slot, plans_bf)))
            pp = params
            for slot in range(m):
                pp = jax.tree_util.tree_map(
                    lambda p: (p + (0.03 * jnp.asarray(
                        rng.normal(size=p.shape), jnp.float32)
                    ).astype(p.dtype)), pp)
                bufs_bf, grams_bf = upd_bf(grams_bf, bufs_bf, pp, slot)
            b = bufs_bf["seg0"]["attn"]["wqkv"]
            assert b.dtype == jnp.bfloat16
            oracle = dmd_mod.gram_matrix(b, anchor=cfg_bf.anchor,
                                         stack_dims=1, upcast=False)
            scale = max(float(jnp.max(jnp.abs(oracle))), 1.0)
            e_bf = float(jnp.max(jnp.abs(
                grams_bf["seg0"]["attn"]["wqkv"] - oracle))) / scale
            assert e_bf < 3e-2, e_bf
            print("BF16_STREAM_ERR", f"{e_bf:.2e}")

            # combine from the shard_map route == the dot_general oracle
            errc = 0.0
            for key, pl in ((("wqkv",), plans["wqkv"]),
                            (("seg0", "attn", "wqkv"),
                             plans["seg0"]["attn"]["wqkv"])):
                b = bufs
                for k in key:
                    b = b[k]
                cshape = pl.stack_shape + (m,)
                c = jnp.asarray(rng.normal(size=cshape), jnp.float32)
                w = jax.jit(lambda b, c, pl=pl: sharded.combine(b, c, pl))(
                    b, c)
                w_ref = dmd_mod.combine_snapshots(
                    b, c, stack_dims=pl.stack_dims)
                errc = max(errc, float(jnp.max(jnp.abs(w - w_ref)))
                           / max(float(jnp.max(jnp.abs(w_ref))), 1.0))
            assert errc < 1e-5, errc
            print("COMBINE_ERR", f"{errc:.2e}")

            # HLO audit: no all-gather of a buffer-sized operand anywhere in
            # the lowered update_grams (the psum'd row pass is all-reduce
            # O(stack*m), never a gather of the O(m*n) buffer)
            hlo = upd_jit.lower(grams, bufs, params, 2).compile().as_text()
            max_ag = max_allgather_bytes(hlo)
            smallest_buf = min(
                4 * b.size for b in jax.tree_util.tree_leaves(bufs))
            assert max_ag < smallest_buf, (max_ag, smallest_buf)
            print("AG_MAX_BYTES", max_ag, "SMALLEST_BUF", smallest_buf)
    finally:
        ops.set_backend(None)
    print("SHARDED_KERNELS_OK")


def run_arena_sharded():
    """Sharded arena buckets (core/arena.py, DESIGN.md §7) on an 8-device
    mesh: leaves sharded over the SAME contracted-dim axes bucket together,
    the bucket's (m, N) ring buffer is lane-sharded, the segmented kernels
    run per shard under shard_map with one O(n_sys*m)/O(n_sys*m^2) psum,
    and the whole route matches the per-leaf (arena=False) oracle. Also
    audits the lowered record+update HLO for buffer-sized all-gathers
    (there must be none — lane sharding keeps every pass local)."""
    import dataclasses as _dc
    from jax.sharding import NamedSharding
    from repro.core import DMDAccelerator, arena as arena_mod, leafplan

    mesh = make_mesh((2, 4), ("data", "model"))
    m = 5
    cfg = DMDConfig(m=m, s=8, tol=1e-3, anchor="first", warmup_steps=0,
                    cooldown_steps=0)
    rng = np.random.default_rng(0)

    def mk(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    params = {
        "wqkv": mk((64, 32)),                    # ("data", "model"): fsdp+tp
        "A_log": mk((32,)),                      # ("model",): tp vector
        "w_gate": mk((64, 32)),                  # same axes as wqkv
        "seg0": {"attn": {"wqkv": mk((6, 64, 32))}},   # stacked, sharded
        "bias": mk((40,)),                       # replicated -> local bucket
    }
    stack_dims = {"wqkv": 0, "A_log": 0, "w_gate": 0, "bias": 0,
                  "seg0": {"attn": {"wqkv": 1}}}

    with jax.set_mesh(mesh):
        acc = DMDAccelerator(cfg, mesh=mesh, stack_dims=stack_dims)
        plans = acc.plans_for(params)
        table = acc.arena_for(params)
        keys = sorted(table)
        # fsdp+tp leaves share one lane-sharded bucket; the tp vector and
        # the replicated vector land in their own sharding classes
        lane_axes = {k: table[k].lane_axes for k in keys}
        assert ("data", "model") in lane_axes.values(), lane_axes
        assert ("model",) in lane_axes.values(), lane_axes
        assert () in lane_axes.values(), lane_axes
        dm_key = next(k for k, v in lane_axes.items() if v == ("data",
                                                               "model"))
        assert {s.path for s in table[dm_key].segments} >= {
            "/wqkv", "/w_gate", "/seg0/attn/wqkv"}, table[dm_key].segments

        place = lambda t, specs: jax.tree_util.tree_map(
            lambda l, s: jax.device_put(l, NamedSharding(mesh, s)), t, specs)
        params = place(params, jax.tree_util.tree_map(
            lambda pl: pl.param_spec, plans, is_leaf=leafplan.is_plan_leaf))

        def run(acc_):
            bufs = acc_.init(params)
            grams = acc_.init_grams(bufs)
            rec = jax.jit(lambda b, g, p, t: acc_.record(b, p, t, g))
            p = params
            rr = np.random.default_rng(1)
            for t in range(m):
                p = jax.tree_util.tree_map(
                    lambda x: x + (0.03 * jnp.asarray(
                        rr.normal(size=x.shape), jnp.float32)
                    ).astype(x.dtype), p)
                bufs, grams = rec(bufs, grams, p,
                                  jnp.asarray(acc_.slots(t)))
            newp, _ = acc_.apply(p, bufs, grams=grams, step=m - 1)
            return bufs, grams, newp, rec

        bufs, grams, newp, rec = run(acc)
        assert arena_mod.is_arena_state(bufs)
        acc_o = DMDAccelerator(_dc.replace(cfg, arena=False), mesh=mesh,
                               stack_dims=stack_dims)
        bufs_o, grams_o, newp_o, _ = run(acc_o)

        from repro.train.state import TrainState
        lw = acc.state_leafwise(TrainState(
            params, None, jnp.zeros((), jnp.int32), bufs, grams))
        err_b = err_g = err_p = 0.0
        flat_lw = jax.tree_util.tree_flatten_with_path(
            lw.dmd_buffers, is_leaf=lambda x: x is None)[0]
        flat_o = {jax.tree_util.keystr(kp): l
                  for kp, l in jax.tree_util.tree_flatten_with_path(
                      bufs_o, is_leaf=lambda x: x is None)[0]}
        for kp, l in flat_lw:
            o = flat_o[jax.tree_util.keystr(kp)]
            err_b = max(err_b, float(jnp.max(jnp.abs(l - o))))
        for x, y in zip(jax.tree_util.tree_leaves(lw.dmd_gram),
                        jax.tree_util.tree_leaves(grams_o)):
            err_g = max(err_g, float(jnp.max(jnp.abs(x - y)))
                        / max(float(jnp.max(jnp.abs(y))), 1.0))
        for x, y in zip(jax.tree_util.tree_leaves(newp),
                        jax.tree_util.tree_leaves(newp_o)):
            err_p = max(err_p, float(jnp.max(jnp.abs(x - y)))
                        / max(float(jnp.max(jnp.abs(y))), 1.0))
        print("ARENA_BUF_ERR", f"{err_b:.2e}")
        print("ARENA_GRAM_ERR", f"{err_g:.2e}")
        print("ARENA_JUMP_ERR", f"{err_p:.2e}")
        assert err_b == 0.0                     # recording is a pure copy
        assert err_g < 1e-5
        assert err_p < 1e-3                     # eigensolve noise floor

        # HLO audit: the packed record+update emits no buffer-sized
        # all-gather (lane sharding keeps the data passes local)
        hlo = jax.jit(lambda b, g, p, t: acc.record(b, p, t, g)).lower(
            bufs, grams, params,
            jnp.asarray(acc.slots(2))).compile().as_text()
        max_ag = max_allgather_bytes(hlo)
        smallest = min(4 * b.size
                       for b in jax.tree_util.tree_leaves(bufs["__arena__"]))
        assert max_ag < smallest, (max_ag, smallest)
        print("ARENA_AG_MAX_BYTES", max_ag, "SMALLEST_BUF", smallest)

        # Bucket scope on LANE-SHARDED buckets (DESIGN.md §9): the same
        # trajectory under scope="bucket" — each lane-sharded bucket's
        # (1, m, m) Gram must equal the leaf-scope Gram stack summed over
        # systems (the segment-sum identity, with the shard-local partial
        # rows psum'd over the SAME lane axes), and the jump stays finite.
        # The record+update HLO keeps the no-buffer-sized-all-gather ban.
        acc_bk = DMDAccelerator(_dc.replace(cfg, scope="bucket"), mesh=mesh,
                                stack_dims=stack_dims)
        bufs_bk, grams_bk, newp_bk, _ = run(acc_bk)
        err_bg = 0.0
        for key in sorted(table):
            b_ = table[key]
            gb = grams_bk["__arena__"][key]
            gl = grams["__arena__"][key]
            if b_.bucket_scoped("bucket"):
                assert gb.shape == (1, m, m), (key, gb.shape)
                ref = jnp.sum(gl, axis=0, keepdims=True)
            else:                       # sys-sharded carve-out: per-system
                assert gb.shape == gl.shape, (key, gb.shape)
                ref = gl
            err_bg = max(err_bg, float(jnp.max(jnp.abs(gb - ref)))
                         / max(float(jnp.max(jnp.abs(ref))), 1.0))
        for x in jax.tree_util.tree_leaves(newp_bk):
            assert bool(jnp.isfinite(x).all())
        hlo_bk = jax.jit(
            lambda b, g, p, t: acc_bk.record(b, p, t, g)).lower(
            bufs_bk, grams_bk, params,
            jnp.asarray(acc_bk.slots(2))).compile().as_text()
        max_ag_bk = max_allgather_bytes(hlo_bk)
        assert max_ag_bk < smallest, (max_ag_bk, smallest)
        print("ARENA_BUCKET_GRAM_ERR", f"{err_bg:.2e}")
        print("ARENA_BUCKET_AG_MAX_BYTES", max_ag_bk,
              "SMALLEST_BUF", smallest)
    print("ARENA_SHARDED_OK")


def _ctrl_line(state, acc):
    """Canonical render of the controller + schedule phase at a step:
    printed by ctrl_save and ctrl_restore, compared VERBATIM by the test —
    counters, s_eff/relax_eff (full fp32 precision), and the per-group slot
    vector re-derived from the step (cooldown/window phase)."""
    c = state.controller
    step = int(state.step)
    slots = acc.slots(step)
    fields = [
        "step=" + str(step),
        "acc=" + ",".join(map(str, np.asarray(c.accepts))),
        "scl=" + ",".join(map(str, np.asarray(c.scaled))),
        "rej=" + ",".join(map(str, np.asarray(c.rejects))),
        "stk=" + ",".join(map(str, np.asarray(c.streak))),
        "s=" + ",".join(f"{v:.9e}" for v in np.asarray(c.s_eff)),
        "rx=" + ",".join(f"{v:.9e}" for v in np.asarray(c.relax_eff)),
        "ema=" + ",".join(f"{v:.9e}" for v in np.asarray(c.gain_ema)),
        "slots=" + ",".join(map(str, slots)),
    ]
    return "CTRL " + " ".join(fields)


def run_controller_preempt(mode, argv):
    """SIGTERM fault injection with the controller on, across a mesh remap
    (ISSUE 4 satellite): save on (2,2) — preempted on the exact jump step
    or mid-window — then restore on (4,2) and verify counters, s_eff, and
    the cooldown phase resume bit-exactly, and the remaining gated jumps
    still fire. Schedule (m=4, warmup=2, cooldown=0): jumps at 5, 9, 13."""
    import signal
    ckpt = argv[0]
    eval_batch = batch_for_step(0, 10 ** 6, 8, 16, 128)   # step-independent
    if mode == "ctrl_save":
        variant = argv[1]
        preempt_at = 5 if variant == "jump" else 7
        acfg = small_acfg(controller=True)
        mesh = make_mesh((2, 2), ("data", "model"))
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            batches = (batch_for_step(0, s, 8, 16, acfg.model.vocab_size)
                       for s in range(100))

            def bomb(step, metrics):
                if step == preempt_at:
                    signal.raise_signal(signal.SIGTERM)
            state = trainer.fit(batches, steps=14, on_metrics=bomb,
                                eval_batch=eval_batch)
            assert int(state.step) == preempt_at + 1
            print(_ctrl_line(state, trainer.acc))
        print("SAVED", preempt_at + 1)
    else:
        expected_step = int(argv[1])
        acfg = small_acfg(controller=True)
        mesh = make_mesh((4, 2), ("data", "model"))   # REMAPPED topology
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            state = trainer.restore()
            assert state is not None and int(state.step) == expected_step
            print(_ctrl_line(state, trainer.acc))
            # the cooldown/window phase is pure step arithmetic: pin it
            g = trainer.acc.groups[0]
            assert trainer.acc.slots(expected_step)[0] == g.slot(
                expected_step)
            # finish the run: the remaining jump steps must gate + count
            jumps_before = sum(
                bool(trainer.acc.apply_groups(t))
                for t in range(expected_step))
            jumps_total = sum(bool(trainer.acc.apply_groups(t))
                              for t in range(14))
            batches = (batch_for_step(0, s, 8, 16, acfg.model.vocab_size)
                       for s in range(expected_step, 100))
            final = trainer.fit(batches, steps=14, state=state,
                                eval_batch=eval_batch)
            c = final.controller
            assert int(np.asarray(c.accepts).sum()
                       + np.asarray(c.scaled).sum()
                       + np.asarray(c.rejects).sum()) == jumps_total, \
                (jumps_before, jumps_total)
            assert np.isfinite(checksum(final.params))
        print("CTRL_OK", jumps_total)


def run_flash_shard_map():
    from repro.kernels import ops
    acfg = get_config("whisper-base")
    mc = dataclasses.replace(
        reduced(acfg.model, encoder_seq_len=160, max_seq_len=128),
        dtype="float32")
    model = LanguageModel(mc, head_tp=True, chunk_k=64)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0,
                                          mc.vocab_size),
             "frames": jax.random.normal(jax.random.PRNGKey(2),
                                         (4, 160, mc.d_model))}
    mesh = make_mesh((2, 2), ("data", "model"))

    def run():           # a new function each time: JAX caches by function
        f = jax.value_and_grad(lambda p: model.loss(p, batch)[0])
        with mesh_context(mesh):
            jaxpr = str(jax.make_jaxpr(f)(params))
            return jax.jit(f)(params), jaxpr.count("shard_map")

    (l_jnp, g_jnp), n_jnp = run()
    ops.set_backend("pallas")
    (l_ker, g_ker), n_ker = run()
    assert (n_jnp, n_ker) == (0, 9), (n_jnp, n_ker)
    err = max([abs(float(l_ker) - float(l_jnp)) / abs(float(l_jnp))] + [
        float(np.abs(np.asarray(a) - np.asarray(b)).max()
              / np.abs(np.asarray(b)).max())
        for a, b in zip(jax.tree_util.tree_leaves(g_ker),
                        jax.tree_util.tree_leaves(g_jnp))])
    print("FLASH_ERR", err)


def main():
    mode = sys.argv[1]
    if mode == "train":
        shape = sys.argv[2]
        if shape == "2x4":
            losses, cs = run_train((2, 4), ("data", "model"))
        elif shape == "1x1":
            losses, cs = run_train((1, 1), ("data", "model"))
        elif shape == "2x2x2":
            losses, cs = run_train((2, 2, 2), ("pod", "data", "model"))
        print("LOSSES", " ".join(f"{l:.6f}" for l in losses))
        print("CHECKSUM", f"{cs:.4f}")
    elif mode == "gram":
        from repro.core.dmd import gram_matrix
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        S = rng.normal(size=(6, 64, 32)).astype(np.float32)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharded = jax.device_put(
            S, NamedSharding(mesh, P(None, "data", "model")))
        with jax.set_mesh(mesh):
            g = jax.jit(lambda s: gram_matrix(s, anchor="first"))(sharded)
        flat = S.reshape(6, -1)
        flat = flat - flat[:1]
        ref = flat @ flat.T
        err = float(np.abs(np.asarray(g) - ref).max() / np.abs(ref).max())
        print("GRAM_ERR", f"{err:.2e}")
        assert err < 1e-5
    elif mode == "gradsync":
        from repro.distributed.gradsync import int8_psum_grads
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}
        with jax.set_mesh(mesh):
            synced = jax.jit(lambda t: int8_psum_grads(t, mesh))(g)
        # replicated input: mean over pods == input (up to int8 quantization)
        err = float(jnp.max(jnp.abs(synced["w"] - g["w"])))
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
        print("GRADSYNC_ERR", f"{err:.4f}", "TOL", f"{scale:.4f}")
        assert err <= scale * 1.01 + 1e-6
    elif mode == "elastic_save":
        ckpt = sys.argv[2]
        acfg = small_acfg()
        mesh = make_mesh((2, 2), ("data", "model"))
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            batches = (batch_for_step(0, s, 8, 16, acfg.model.vocab_size)
                       for s in range(100))
            state = trainer.fit(batches, steps=2)
            trainer.save(state, 2)
        print("SAVED", checksum(state.params))
    elif mode == "gram_save":
        ckpt, variant = sys.argv[2], sys.argv[3]
        hetero = variant == "hetero"
        acfg = small_acfg(hetero)          # m=4 (+ norms m=3), warmup=2
        mesh = make_mesh((2, 2), ("data", "model"))
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            batches = (batch_for_step(0, s, 8, 16, acfg.model.vocab_size)
                       for s in range(100))
            # single group: steps 0..5 record slots 0..3, jump at step 5 —
            # the window completes, so the streaming Gram == oracle.
            # hetero: run through step 13, where BOTH groups' windows
            # complete (m=4 jumps at 5,9,13; m=3 at 4,7,10,13).
            steps = 14 if hetero else 6
            state = trainer.fit(batches, steps=steps)
            assert state.dmd_gram is not None
            if variant == "zero":
                state = state._replace(dmd_gram=None)   # pre-streaming format
            trainer.save(state, steps)
        print("SAVED", checksum(state.params))
    elif mode == "gram_restore":
        ckpt = sys.argv[2]
        hetero = len(sys.argv) > 3 and sys.argv[3] == "hetero"
        from repro.core import dmd as dmd_mod
        from repro.core.leafplan import is_plan_leaf
        acfg = small_acfg(hetero)
        mesh = make_mesh((4, 2), ("data", "model"))   # REMAPPED topology
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            state = trainer.restore()
            assert state is not None
            assert int(state.step) == (14 if hetero else 6)
            # the run carries packed arenas (DESIGN.md §7); audit the
            # equivalent per-leaf view
            state = trainer.acc.state_leafwise(state)
            plans = trainer.acc.plans_for(state.params)
            n_checked = 0
            n_small = 0

            def chk(plan, buf, g):
                nonlocal n_checked, n_small
                if plan is None or buf is None:
                    return None
                assert g is not None
                # heterogeneous windows restore heterogeneous shapes
                assert buf.shape[0] == plan.m and g.shape[-1] == plan.m
                n_small += plan.m != acfg.dmd.m
                oracle = dmd_mod.gram_matrix(buf, anchor=acfg.dmd.anchor,
                                             stack_dims=plan.stack_dims)
                np.testing.assert_allclose(np.asarray(g), np.asarray(oracle),
                                           rtol=1e-4, atol=1e-4)
                n_checked += 1
                return None
            jax.tree_util.tree_map(chk, plans, state.dmd_buffers,
                                   state.dmd_gram, is_leaf=is_plan_leaf)
            assert n_checked > 0
            if hetero:
                assert n_small > 0          # the m=3 group really exists
        print("GRAMS_OK", n_checked)
    elif mode == "resident_save":
        from repro.core import arena as arena_mod
        from repro.train.step import resident_enabled, state_resident
        ckpt = sys.argv[2]
        acfg = small_acfg()                       # adam: resident-capable
        mesh = make_mesh((2, 2), ("data", "model"))
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            assert resident_enabled(trainer.acc, acfg)
            batches = (batch_for_step(0, s, 8, 16, acfg.model.vocab_size)
                       for s in range(100))
            state = trainer.fit(batches, steps=6)
            # fit de-residentizes at return; the bucket table it trained
            # on contains at least one SHARDED bucket
            assert not arena_mod.is_arena_state(state.params)
            table = trainer.acc.arena_for(state.params)
            assert any(b.lane_axes or b.sys_axes for b in table.values()), \
                {k: (b.lane_axes, b.sys_axes) for k, b in table.items()}
            # the resident layout really was live: re-residentize and pin
            # bucket count + bit-exact round trip through the wrapper
            res = state_resident(trainer.acc, acfg, state)
            assert arena_mod.is_arena_state(res.params)
            trainer.save(state, 6)
        print("SAVED", f"{checksum(state.params):.6f}")
    elif mode == "resident_restore":
        from repro.core import arena as arena_mod
        from repro.train.step import state_resident
        ckpt = sys.argv[2]
        acfg = small_acfg()
        mesh = make_mesh((4, 2), ("data", "model"))   # REMAPPED topology
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            state = trainer.restore()
            assert state is not None and int(state.step) == 6
            print("RESTORED", f"{checksum(state.params):.6f}")
            # the new mesh's bucket table also carries a sharded bucket,
            # and the restored per-leaf state re-residentizes into it
            table = trainer.acc.arena_for(state.params)
            assert any(b.lane_axes or b.sys_axes for b in table.values())
            res = state_resident(trainer.acc, acfg, state)
            assert arena_mod.is_arena_state(res.params)
            assert arena_mod.is_arena_state(res.opt_state.m)
            back = trainer.acc.state_leafwise(res)
            assert abs(checksum(back.params)
                       - checksum(state.params)) < 1e-3
            # one more fit step runs ON the resident layout
            batches = (batch_for_step(0, s, 8, 16, acfg.model.vocab_size)
                       for s in range(6, 100))
            final = trainer.fit(batches, steps=7, state=state)
            assert int(final.step) == 7
            assert np.isfinite(checksum(final.params))
        print("RESIDENT_OK", f"{checksum(final.params):.6f}")
    elif mode in ("ctrl_save", "ctrl_restore"):
        run_controller_preempt(mode, sys.argv[2:])
    elif mode == "sharded_kernels":
        run_sharded_kernels()
    elif mode == "flash_shard_map":
        run_flash_shard_map()
    elif mode == "arena_sharded":
        run_arena_sharded()
    elif mode == "elastic_restore":
        ckpt = sys.argv[2]
        acfg = small_acfg()
        mesh = make_mesh((4, 2), ("data", "model"))   # DIFFERENT topology
        model = LanguageModel(acfg.model, head_tp=True, chunk_k=16)
        with mesh_context(mesh):
            trainer = Trainer(model, acfg, mesh=mesh, checkpoint_dir=ckpt)
            state = trainer.restore()
            assert state is not None and int(state.step) == 2
            batch = batch_for_step(0, 2, 8, 16, acfg.model.vocab_size)
            state, m = trainer.train_step(state, batch,
                                          jnp.asarray(2, jnp.int32))
            assert np.isfinite(float(m["loss"]))
        print("RESTORED", checksum(state.params), f"{float(m['loss']):.6f}")
    else:
        raise SystemExit(f"unknown mode {mode}")


if __name__ == "__main__":
    main()
