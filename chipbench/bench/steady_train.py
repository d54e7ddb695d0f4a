"""Steady training of a token model through ``Trainer.fit``: the driver of
the LM training cells.

Set-up builds one Trainer and one state from the seed: the weights from the
benchmark's generator, the optimizer state fresh, and the step index at
``start_step`` (the traffic file puts it where the DMD schedule's first
snapshot window opens). It drives that state through the window's own call
and feed: one step, then two, then on through the first DMD window's jump,
so every program the window runs is compiled and warm. The first three
steps are checked against the plain reference. The window is one ``fit``
call over the on-device ring of batches, stopped from ``on_metrics`` once
``--seconds`` have passed, after blocking on the last step's outputs.
"""
from __future__ import annotations

import gc
import itertools
import math
import time

from bench.compare import (Check, moving_leaves, norm_gap, rel_gap)
from bench.harness import Outcome, peak_bytes
from bench.trace import WINDOW_SPAN


class Stop(Exception):
    """Raised from ``on_metrics`` to end the window's ``fit``."""


def run(ctx) -> Outcome:
    import jax
    import jax.numpy as jnp

    from bench import lm_traffic, seeds, weights
    from repro.distributed.sharding import mesh_context
    from repro.train import Trainer
    from repro.train.state import TrainState

    cfg, traffic, mod = ctx.config, ctx.cell["traffic_params"], ctx.module
    acfg, model, mesh = mod.build(cfg, traffic, ctx.devices)
    shape = mod.traffic_shape(cfg, traffic)
    k_w = seeds.key_for(ctx.seed, 0)
    k_data = seeds.key_for(ctx.seed, 1)
    k_val = seeds.key_for(ctx.seed, 2)
    step0 = int(traffic["start_step"])
    dmd_on = bool(traffic["dmd"])

    with mesh_context(mesh):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params0 = weights.make(k_w, shapes, mod.weight_rule)
        ring = lm_traffic.ring(k_data, int(traffic["ring"]), **shape)
        val = lm_traffic.one(k_val, 0, **shape)
        trainer = Trainer(model, acfg, mesh=mesh, val_batch=val)
        acc = trainer.acc

        def fresh(params):
            bufs = acc.init(params) if dmd_on else None
            return TrainState(params, trainer.opt.init(params),
                              jnp.asarray(step0, jnp.int32), bufs,
                              acc.init_grams(bufs), acc.init_controller())

        state = jax.jit(fresh)(params0)
        feed = itertools.cycle(ring)
        losses = []

        def keep(step, metrics):
            losses.append(metrics["loss"])

        # --- set-up: the checked steps, then on through the first jump ---
        state = trainer.fit(feed, steps=step0 + 1, state=state,
                            on_metrics=keep)
        b1 = acfg.optimizer.b1
        grad1 = _leaf_norms(jax.tree_util.tree_map(
            lambda m: m / (1.0 - b1), state.opt_state.m))
        state = trainer.fit(feed, steps=step0 + 3, state=state,
                            on_metrics=keep)
        change3 = _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            state.params, params0))
        gram = _program_grams(acc, state) if dmd_on else None
        prog_losses = [float(l) for l in losses]
        cycle = acfg.dmd.cooldown_steps + acfg.dmd.m
        jumps = [t for t in range(step0, step0 + 2 * cycle)
                 if dmd_on and acc.apply_groups(t)]
        warm_to = (jumps[0] + 1) if jumps else step0 + cycle
        state = trainer.fit(feed, steps=warm_to, state=state,
                            on_metrics=keep)
        jax.block_until_ready(state)
        del params0

        # --- the measured window: one fit call ---
        losses.clear()
        ctx.compile_log.take()
        t_end = []
        seconds = ctx.seconds

        def stopper(step, metrics):
            losses.append(metrics["loss"])
            if time.perf_counter() - t0 >= seconds:
                jax.block_until_ready(metrics)
                t_end.append(time.perf_counter())
                raise Stop

        if ctx.trace:
            from bench.harness import TRACE_DIR
            jax.profiler.start_trace(str(TRACE_DIR))
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                trainer.fit(feed, steps=10 ** 9, state=state,
                            on_metrics=stopper)
        except Stop:
            pass
        if ctx.trace:
            jax.profiler.stop_trace()
        window_compiles = ctx.compile_log.take()
        del state
        t1 = t_end[0]
        steps = len(losses)
        window_losses = [float(l) for l in losses]
        dev = ctx.devices[0]
        peak = peak_bytes(dev)
        first = warm_to
        window_steps = range(first, first + steps)
        rec_steps = sum(1 for t in window_steps
                        if any(g.should_record(t) for g in acc.groups)) \
            if dmd_on else 0
        n_jumps = sum(1 for t in window_steps if acc.apply_groups(t)) \
            if dmd_on else 0
        record = {"steps": steps, "record_steps": rec_steps,
                  "jumps": n_jumps, "window_s": t1 - t0,
                  "flops_per_step": mod.flops_per_step(cfg, traffic),
                  "chips": len(ctx.devices)}
        if dmd_on:
            record.update(_arena_sizes(acc, shapes, cfg))
        tokens = steps * mod.tokens_per_step(cfg, traffic)
        e2e = {"train_tokens_per_s": tokens / (t1 - t0),
               "setup_s": t0 - ctx.t_start,
               "peak_hbm_gib": peak / 2 ** 30}
        del trainer, ring, feed
        gc.collect()

        # --- the reference follows the first three steps ---
        checks = reference_checks(mod, cfg, shapes, k_w, k_data, shape,
                                  int(traffic["ring"]), step0,
                                  prog_losses[:3], grad1, change3, gram)
    failed = sum(1 for l in window_losses if not math.isfinite(l))
    print(f"window: {steps} steps ({rec_steps} recorded, {n_jumps} jumps) "
          f"in {t1 - t0:.3f} s; {len(window_compiles)} XLA compile(s) "
          f"inside it {window_compiles}", flush=True)
    correct = all(c.ok for c in checks) and failed == 0 \
        and not window_compiles
    return Outcome(e2e, record, checks, steps, failed, correct, peak)


def _leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(tree)
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in ls])(leaves)
    return [float(n) for n in norms]


def _program_grams(acc, state) -> dict:
    """{leaf path: (n_sys, 3, 3) float array}: the first three rows and
    columns of the streaming Gram of every system, by leaf, as the program
    carries it."""
    import numpy as np
    from repro.core import arena as arena_mod
    table = acc.arena_for(state.params)
    grams, leaf = arena_mod.split_state(state.dmd_gram)
    out = {}
    for key, b in table.items():
        g = np.asarray(grams[key][:, :3, :3], np.float64)
        for seg in b.segments:
            out[seg.path] = g[seg.sys_start:seg.sys_start + seg.n_sys]
    import jax
    for kp, g in jax.tree_util.tree_flatten_with_path(leaf)[0]:
        if g is not None:
            out[_path(kp)] = np.asarray(g, np.float64).reshape(
                (-1,) + g.shape[-2:])[:, :3, :3]
    return out


def _path(kp) -> str:
    import jax
    from repro.distributed.sharding import normalize_path
    return normalize_path(jax.tree_util.keystr(kp))


def _arena_sizes(acc, shapes, cfg) -> dict:
    """DMD-managed lanes (real parameters, not padding) and the snapshot
    itemsize, for the kernels' roofline counts."""
    import jax
    import numpy as np
    table = acc.arena_for(shapes)
    n = sum(s.n_sys * s.flat_local for b in table.values()
            for s in b.segments)
    return {"dmd_lanes": n, "m": cfg["dmd"]["m"],
            "snapshot_itemsize": np.dtype(cfg["dmd"]["snapshot_dtype"]
                                          ).itemsize,
            "buckets": len(table)}


def reference_checks(mod, cfg, shapes, k_w, k_data, shape, ring, step0,
                     prog_losses, prog_grad1, prog_change3,
                     prog_gram) -> list:
    """Compare the program's readings of the first three steps with the
    plain reference's: each step's loss, each leaf's first-gradient norm,
    each moving leaf's parameter change after three steps and, with DMD on,
    each system's streaming Gram over the three recorded snapshots."""
    ref = reference_readings(mod, cfg, shapes, k_w, k_data, shape, ring,
                             step0, gram=prog_gram is not None)
    prog = {"losses": prog_losses, "grad1": prog_grad1,
            "change3": prog_change3, "gram": prog_gram}
    return compare(prog, ref, cfg["limits"])


def compare(prog: dict, ref: dict, limits: dict) -> list:
    keep = moving_leaves(ref["grad1"])
    checks = [Check("loss_gap", rel_gap(prog["losses"], ref["losses"]),
                    limits["loss_gap"]),
              Check("grad_gap", norm_gap(prog["grad1"], ref["grad1"]),
                    limits["grad_gap"]),
              Check("update_gap", norm_gap(prog["change3"], ref["change3"],
                                           keep),
                    limits["update_gap"])]
    if prog.get("gram") is not None:
        keep_path = {p for p, k in zip(ref["paths"], keep) if k}
        checks.append(Check("gram_gap", gram_gap(prog["gram"], ref["gram"],
                                                  keep_path),
                            limits["gram_gap"]))
    return checks


def reference_readings(mod, cfg, shapes, k_w, k_data, shape, ring, step0,
                       *, gram: bool, cast=None, store=None,
                       batch_filter=None) -> dict:
    """The plain reference over the first three steps, from the same
    weights and batches as the program (step ``i`` trains on batch
    ``i % ring`` of the traffic's ring): losses, per-leaf first-gradient
    and three-step change norms and, with ``gram``, each system's anchored
    Gram of the three snapshots. ``cast`` and ``store`` (a map of stored
    dtypes) compute it at a lower precision and ``batch_filter`` alters
    its batches, as controls and planted faults do."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import lm_traffic, weights
    from bench.train_ref import follow

    p0 = weights.make(k_w, shapes, mod.weight_rule)
    if store is not None:
        p0 = jax.tree_util.tree_map(lambda x: x.astype(store(x.dtype)), p0)
    batches = [lm_traffic.one(k_data, i % ring, **shape) for i in range(3)]
    if batch_filter is not None:
        batches = [batch_filter(b) for b in batches]
    snaps = []
    res = follow(p0, batches, mod.reference_loss(cfg, cast), cfg["optimizer"],
                 step0, after_step=lambda i, p: snaps.append(p))
    change = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        snaps[-1], p0)
    flat = jax.tree_util.tree_flatten_with_path(p0)[0]
    out = {"losses": res["losses"], "grad1": _leaf_norms(res["first_grad"]),
           "change3": _leaf_norms(change),
           "paths": [_path(kp) for kp, _ in flat], "gram": None}
    if gram:
        out["gram"] = {}
        for idx, path in enumerate(out["paths"]):
            xs = [np.asarray(jax.tree_util.tree_leaves(s)[idx], np.float64)
                  for s in snaps]
            n_sys = xs[0].shape[0] if _stacked(path) else 1
            d = [(x - xs[0]).reshape(n_sys, -1) for x in xs]
            g = np.zeros((n_sys, 3, 3))
            for i in range(3):
                for j in range(3):
                    g[:, i, j] = np.sum(d[i] * d[j], axis=1)
            out["gram"][path] = g
    return out


def _stacked(path: str) -> bool:
    """Leaves of a scanned layer stack (``/seg<i>/...``) hold one DMD
    system per layer."""
    return path.lstrip("/").startswith("seg")


def gram_gap(prog: dict, ref: dict, keep: set) -> float:
    """Worst gap of a streaming Gram entry G_ij (i, j >= 1: the anchored
    rows) against sqrt(G_ii G_jj) of the reference, or the median system's
    value where that is larger."""
    import numpy as np
    from statistics import median
    pairs = [(1, 1), (1, 2), (2, 2)]
    scales = []
    for path in keep:
        if path in prog and path in ref:
            r = ref[path]
            scales += list(np.sqrt(r[:, 2, 2] * r[:, 2, 2]))
    if not scales:
        return math.nan
    med = median(scales)
    out = 0.0
    for path in keep:
        if path not in prog or path not in ref:
            continue
        p, r = prog[path], ref[path]
        if p.shape != r.shape or not np.all(np.isfinite(p)):
            return math.nan
        for i, j in pairs:
            s = np.maximum(np.sqrt(r[:, i, i] * r[:, j, j]), med)
            out = max(out, float(np.max(np.abs(p[:, i, j] - r[:, i, j])
                                        / s)))
    return out
