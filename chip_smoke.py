#!/usr/bin/env python3
"""Chip smoke test: DMD-accelerated training on a TPU through the launcher.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # a 2x2 (data x model) mesh, compared
                                       # with one device of the same four

One chip runs two phases in this one process:

1. whisper-base at its published depth and widths (6+6 layers, d_model 512,
   8x64 heads, d_ff 2048, vocab 51865, 1500 encoder frames; decoder length
   448), weights and batches from a seed, built by the training launcher's
   own ``repro.launch.train.build`` and trained by ``Trainer.fit`` with the
   config's DMD settings: m=14, s=55, fp32 snapshots, the resident arena
   route and the streaming Gram. The validation gate is switched on so that
   every jump is evaluated (accepted, scaled back or rejected) on the
   held-out split. The run takes enough steps for two DMD windows to close.
2. the paper's pollutant MLP (6-40-200-1000-2670) with its own DMD config,
   ``mode="eig"`` — the jump's eigendecomposition is a host callback — for
   one jump.

It exits nonzero, printing no result, when JAX finds no TPU or when any check
fails: the loss is finite and falls; a window closed and its jump was
evaluated; the train step runs the Pallas kernels (no interpret
mode, no reference fallback); the streaming Gram carried in the state
matches a float64 numpy Gram of the recorded snapshot buffer; the eig phase
jumped once. The last line of standard output is one JSON object.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "whisper-base"
SEQ = 448            # whisper's decoder context
GLOBAL_BATCH = 2     # the train step compiled for one v5e peaks at 10.6 GiB
                     # of HBM at batch 2, 15.1 GiB at batch 4 (of 15.75)
STEPS = 80           # warmup 20 (= steps // 4), then two 24-step windows
                     # (cooldown 10 + m 14): jumps after steps 43 and 67

# The streaming Gram is accumulated on the MXU, whose float32 matmuls round
# their operands to bfloat16 at the default precision: each product carries
# a relative error up to 2^-8, so by Cauchy-Schwarz an entry's error is
# bounded by ~2^-8 * sqrt(G_ii * G_jj) plus float32 accumulation error.
GRAM_TOL = 1e-2      # max |G_dev - G_ref| / sqrt(G_ii G_jj) over entries

# --chips 4 compares the 2x2 run with the one-device run step by step. At
# TPU default matmul precision (bfloat16 operand passes) the sharded
# contractions reduce in another order, so the losses differ slightly: the
# worst step differed by 1.8e-4 relative on four v5e chips. The limit keeps
# ~10x headroom over that and stays far below what a sharding fault moves
# (80 steps take the loss down by ~5.6%).
LOSS_RTOL = 2e-3


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# whisper-base through the launcher
# ---------------------------------------------------------------------------

def check_kernels(lowered: str) -> int:
    """The DMD kernels route to compiled Pallas (no interpret mode, no
    reference stand-in) and the train step calls them; returns the number
    of kernel calls in the lowered step."""
    from repro.kernels import ops

    check(ops.pallas_compiled(), "DMD kernels do not route to compiled Pallas")
    n = lowered.count("tpu_custom_call")
    check(n > 0, "the train step runs no Pallas kernel")
    return n


class CompileLog:
    """XLA compiles as JAX reports them: (program, seconds) per compile
    since the last ``take()``."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="?", **_):
        if event == self.EVENT:
            self.seen.append((fun_name, secs))

    def take(self) -> list:
        seen, self.seen = self.seen, []
        return seen

    @staticmethod
    def summary(seen) -> str:
        step = [s for f, s in seen if "train_step" in f or "dmd_step" in f]
        total = sum(s for _, s in seen)
        return (f"{len(seen)} XLA compile(s) in {total:.1f} s, of which "
                f"train/dmd step {len(step)} in {sum(step):.1f} s")


def route_counts(acc, params) -> dict:
    """How each DMD-managed leaf's data passes run: packed into an arena
    (one segmented kernel per bucket) or on its own per-leaf route."""
    from repro.core import arena as arena_mod
    from repro.core.leafplan import plan_entries

    packed = arena_mod.arena_paths(acc.arena_for(params))
    counts = {"arena": 0, "pallas_flat": 0, "pallas_shard_map": 0,
              "dot_general": 0}
    for p in plan_entries(acc.plans_for(params)):
        counts["arena" if p.path in packed else p.route] += 1
    return counts


def numpy_gram_errors(state, acc, chunk: int = 2048) -> dict:
    """{bucket: max |G_dev - G_ref| / sqrt(G_ii G_jj)} where G_ref is the
    float64 anchored Gram of each system's snapshot rows, accumulated on
    the host over ``chunk``-block slices of the (sharded) buffer."""
    import jax
    import numpy as np

    from repro.core import arena as arena_mod

    table = acc.arena_for(state.params)
    bufs, _ = arena_mod.split_state(state.dmd_buffers)
    grams, _ = arena_mod.split_state(state.dmd_gram)
    out = {}
    for key, b in sorted(table.items()):
        buf, m = bufs[key], b.m
        nb_local = b.n_blocks_local
        local_sys = b.block_sys()
        lane_shards = b.shard_factor
        g_ref = np.zeros((b.n_sys_global, m, m))
        for g0 in range(0, b.n_blocks, chunk):
            g1 = min(g0 + chunk, b.n_blocks)
            x = np.asarray(jax.device_get(buf[g0:g1, :m, :]), np.float64)
            d = x - x[:, :1, :]
            part = d @ np.swapaxes(d, 1, 2)               # (blocks, m, m)
            # global block -> (shard, local block) -> global system: the
            # block axis is sharded over sys_axes then lane_axes
            gb = np.arange(g0, g1)
            shard, local = gb // nb_local, gb % nb_local
            sys_ids = (shard // lane_shards) * b.n_sys + local_sys[local]
            np.add.at(g_ref, sys_ids, part)
        g_dev = np.asarray(jax.device_get(grams[key]), np.float64)
        diag = np.sqrt(np.maximum(np.einsum("sii->si", g_ref), 1e-300))
        scale = diag[:, :, None] * diag[:, None, :]
        out[key] = float(np.max(np.abs(g_dev - g_ref) / scale))
    return out


def train_whisper(devices, model_parallel: int, *, label: str,
                  check_gram: bool, clog: CompileLog) -> list:
    """Train whisper-base through build() -> Trainer.fit on ``devices``;
    return the per-step losses. Checks the compiled step, the jumps and
    (``check_gram``) the streaming Gram at the first window's close."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import DMDControllerConfig
    from repro.distributed.sharding import mesh_context
    from repro.launch.train import batches, build
    from repro.train import Trainer
    from repro.train.step import state_resident

    acfg, model, mesh = build(ARCH, steps=STEPS, global_batch=GLOBAL_BATCH,
                              seq=SEQ, model_parallel=model_parallel,
                              devices=devices)
    acfg = dataclasses.replace(acfg, dmd=dataclasses.replace(
        acfg.dmd, controller=DMDControllerConfig(enabled=True,
                                                 val_gate=True)))
    mc = acfg.model
    log(f"[{label}] {ARCH}: {model.param_count() / 1e6:.1f}M params, "
        f"{mc.n_encoder_layers}+{mc.n_layers} layers, d_model {mc.d_model}, "
        f"{mc.n_heads}x{mc.head_dim} heads, d_ff {mc.d_ff}, vocab "
        f"{mc.vocab_size}, {mc.encoder_seq_len} frames, batch "
        f"{GLOBAL_BATCH}x{SEQ}, mesh {dict(mesh.shape)}, dmd m={acfg.dmd.m} "
        f"s={acfg.dmd.s} {acfg.dmd.snapshot_dtype} snapshots")
    losses, outcomes, jumps = [], [], []

    def on_metrics(step, metrics):
        losses.append(float(metrics["loss"]))
        if "mean_rank" in metrics:
            jumps.append(step)
            if "ctrl_outcome" in metrics:
                outcomes.append(int(metrics["ctrl_outcome"]))

    with mesh_context(mesh):
        trainer = Trainer(model, acfg, mesh=mesh)
        jump_steps = [t for t in range(STEPS) if trainer.acc.apply_groups(t)]
        check(len(jump_steps) >= 2, f"only {len(jump_steps)} DMD windows "
              f"close in {STEPS} steps")
        state = trainer.init_state()
        log(f"[{label}] DMD routes per leaf: "
            f"{route_counts(trainer.acc, state.params)}")
        state = state_resident(trainer.acc, acfg, state)

        # What the train step runs: the segmented Pallas kernels, as TPU
        # custom calls in the lowered program (the trace is shared with
        # the jit's own first call).
        n_kernels = check_kernels(trainer.train_step.lower(
            state, next(batches(acfg, model)),
            jnp.asarray(0, jnp.int32)).as_text())
        log(f"[{label}] train_step lowers to {n_kernels} Pallas kernel "
            f"call(s)")

        # Through the first window's close: the jump step's state carries
        # the completed window (the jump leaves buffers and Grams as they
        # are, and the steps after it are unrecorded cooldown).
        clog.take()
        t0 = time.time()
        first = jump_steps[0] + 1
        state = trainer.fit(batches(acfg, model), steps=first, state=state,
                            on_metrics=on_metrics)
        jax.block_until_ready(state)
        log(f"[{label}] steps 0-{first - 1} in {time.time() - t0:.1f} s "
            f"with {clog.summary(clog.take())}")
        if check_gram:
            t0 = time.time()
            errs = numpy_gram_errors(state, trainer.acc)
            worst = max(errs.values())
            log(f"[{label}] streaming Gram vs float64 numpy Gram at the "
                f"window close (step {jump_steps[0]}): max relative error "
                f"{worst:.3e} over {len(errs)} bucket(s) {errs} "
                f"(tolerance {GRAM_TOL}, {time.time() - t0:.1f} s)")
            check(np.isfinite(worst) and worst <= GRAM_TOL,
                  f"streaming Gram disagrees with numpy: {errs}")

        clog.take()
        t0 = time.time()
        state = trainer.fit(batches(acfg, model, start=first), steps=STEPS,
                            state=state, on_metrics=on_metrics)
        jax.block_until_ready(state)
        again = clog.take()
        log(f"[{label}] steps {first}-{STEPS - 1} in "
            f"{time.time() - t0:.1f} s with {clog.summary(again)}")
        check(not any(f == "jit(train_step)" for f, _ in again),
              "the second fit recompiled the train step")
    del state, trainer
    gc.collect()

    from repro.core import controller as ctrl_mod
    n_acc = outcomes.count(ctrl_mod.ACCEPT)
    n_sc = outcomes.count(ctrl_mod.SCALED)
    n_rej = outcomes.count(ctrl_mod.REJECT)
    log(f"[{label}] {len(losses)} steps, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; jumps after steps {jumps}: {n_acc} accepted, "
        f"{n_sc} scaled, {n_rej} rejected")
    check(len(losses) == STEPS, f"{len(losses)} of {STEPS} steps ran")
    check(all(np.isfinite(losses)), "non-finite loss")
    check(np.mean(losses[-5:]) < np.mean(losses[:5]), "loss did not fall")
    check(jumps == jump_steps and len(outcomes) == len(jump_steps),
          f"jumps {jumps} / gate outcomes {outcomes}, expected a gated "
          f"jump after each of steps {jump_steps}")
    return losses


# ---------------------------------------------------------------------------
# the paper's pollutant MLP, eig mode
# ---------------------------------------------------------------------------

def pollutant_eig_phase() -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.mlp_net import PAPER_SIZES, MLPModel, init_mlp, \
        mlp_forward
    from repro.train import Trainer

    acfg = get_config("pollutant-mlp")
    check(acfg.dmd.mode == "eig", "the pollutant config is not in eig mode")
    # A seeded regression task of the paper's shapes: 6 inputs -> the 2670
    # probe concentrations of a random teacher network.
    rng = np.random.default_rng(0)
    x = jax.numpy.asarray(rng.uniform(-1, 1, (256, PAPER_SIZES[0])),
                          jax.numpy.float32)
    y = mlp_forward(init_mlp(jax.random.PRNGKey(1), PAPER_SIZES), x)
    batch = {"x": x, "y": y}

    trainer = Trainer(MLPModel(PAPER_SIZES, acfg.model.act), acfg)
    jump_steps = [t for t in range(100) if trainer.acc.apply_groups(t)]
    steps = jump_steps[0] + 1
    losses, ranks = [], []

    def on_metrics(step, metrics):
        losses.append(float(metrics["loss"]))
        if "mean_rank" in metrics:
            ranks.append(float(metrics["mean_rank"]))

    def stream():
        while True:
            yield batch

    t0 = time.time()
    state = trainer.fit(stream(), steps=steps, on_metrics=on_metrics)
    post = float(MLPModel(PAPER_SIZES).loss(state.params, batch)[0])
    finite = all(bool(np.all(np.isfinite(np.asarray(l))))
                 for l in jax.tree_util.tree_leaves(state.params))
    log(f"[eig] pollutant MLP {'-'.join(map(str, PAPER_SIZES))}, dmd "
        f"m={acfg.dmd.m} s={acfg.dmd.s} mode={acfg.dmd.mode}: {steps} steps "
        f"in {time.time() - t0:.1f} s, {len(ranks)} jump(s) with mean rank "
        f"{ranks}, loss {losses[0]:.5f} -> {losses[-1]:.5f} before the jump, "
        f"{post:.5f} after")
    check(len(ranks) == 1 and ranks[0] >= 1, "the eig phase did not jump")
    check(finite and np.isfinite(post), "non-finite params after the jump")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX found "
          f"{len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clog = CompileLog()
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"{len(devices)} device(s)")

    if args.chips == 1:
        train_whisper(devices[:1], 1, label="1 chip", check_gram=True,
                      clog=clog)
        pollutant_eig_phase()
    else:
        import numpy as np
        four = devices[:4]
        sharded = train_whisper(four, 2, label="2x2 mesh", check_gram=True,
                                clog=clog)
        for d in four:
            s = d.memory_stats() or {}
            log(f"[2x2 mesh] device {d.id}: peak "
                f"{s.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB, in use "
                f"after the run {s.get('bytes_in_use', 0) / 2**30:.2f} GiB")
        single = train_whisper(four[:1], 1, label="1 of the 4 devices",
                               check_gram=False, clog=clog)
        rel = np.abs(np.asarray(sharded) - np.asarray(single)) / \
            np.abs(np.asarray(single))
        log(f"loss curves 2x2 vs one device: max relative difference "
            f"{rel.max():.3e} at step {int(rel.argmax())} (tolerance "
            f"{LOSS_RTOL}); mean {rel.mean():.3e}")
        check(rel.max() <= LOSS_RTOL, "2x2 and one-device losses disagree")

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use on device {dev.id}: "
        f"{stats.get('peak_bytes_in_use', 0)} "
        f"({stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
