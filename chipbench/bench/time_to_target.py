"""Back-to-back full-batch trainings to a target test MSE through
``Trainer.fit``: the driver of the time-to-target cells.

Each training starts from fresh weights and fresh optimizer state and runs
``fit`` in ``check_every``-step calls, reading the test MSE once after each
call, until it reaches the cell's target or the step cap. The inits come
from a fixed pool that every seed shares; the seed sets their order. The
window runs whole passes over the pool until ``--seconds`` have passed, so
every run does the same trainings.

Set-up runs a check training from the seed's own init by the same calls:
one step, then two, then on to ``check_every`` steps. The float64
reference follows its first three steps.
"""
from __future__ import annotations

import itertools
import time

import numpy as np

from bench.compare import Check, moving_leaves, norm_gap, rel_gap
from bench.harness import ROOT, Outcome, peak_bytes
from bench.trace import WINDOW_SPAN


def load_data(config: dict):
    with np.load(ROOT / config["data"]["file"]) as z:
        return {k: np.asarray(z[k], np.float32) for k in z.files}


def run(ctx) -> Outcome:
    import jax
    import jax.numpy as jnp

    from bench import seeds, weights
    from repro.train import Trainer
    from repro.train.state import TrainState

    cfg, traffic, mod = ctx.config, ctx.cell["traffic_params"], ctx.module
    if traffic["dmd"]:
        raise ValueError("a time-to-target cell with DMD on needs its jump "
                         "compared with the reference; see PERF.md")
    acfg, model = mod.build(cfg, traffic)
    data = load_data(cfg)
    every, cap = int(traffic["check_every"]), int(traffic["step_cap"])
    target = float(traffic["target_test_mse"])

    batch = {"x": jnp.asarray(data["x_train"]),
             "y": jnp.asarray(data["y_train"])}
    x_te, y_te = jnp.asarray(data["x_test"]), jnp.asarray(data["y_test"])
    trainer = Trainer(model, acfg)
    acc = trainer.acc
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    init = weights.maker(shapes, mod.weight_rule)

    @jax.jit
    def fresh(key):
        params = init(key)
        bufs = acc.init(params)
        return TrainState(params, trainer.opt.init(params),
                          jnp.zeros((), jnp.int32), bufs,
                          acc.init_grams(bufs), acc.init_controller())

    test_mse = jax.jit(lambda p: model.loss(p, {"x": x_te, "y": y_te})[0])
    feed = itertools.repeat(batch)

    def train(key) -> tuple:
        """One training to the target: (steps, reached)."""
        with jax.profiler.TraceAnnotation("chipbench.new_state"):
            state = fresh(key)
        steps = 0
        while steps < cap:
            with jax.profiler.TraceAnnotation("chipbench.fit"):
                state = trainer.fit(feed, steps=steps + every, state=state)
            steps += every
            with jax.profiler.TraceAnnotation("chipbench.test_mse"):
                mse = float(test_mse(state.params))
            if mse <= target:
                return steps, True
        return steps, False

    # --- set-up: the check training, by the window's calls ---
    k_check = seeds.key_for(ctx.seed, 0)
    state = fresh(k_check)
    p0 = jax.device_get(state.params)
    losses = []
    keep = lambda step, m: losses.append(m["loss"])
    state = trainer.fit(feed, steps=1, state=state, on_metrics=keep)
    grad1 = _leaf_norms(jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float64) / (1.0 - acfg.optimizer.b1),
        jax.device_get(state.opt_state.m)))
    state = trainer.fit(feed, steps=3, state=state, on_metrics=keep)
    p3 = jax.device_get(state.params)
    state = trainer.fit(feed, steps=every, state=state, on_metrics=keep)
    float(test_mse(state.params))
    prog_losses = [float(l) for l in losses[:3]]
    del state

    pool = [seeds.key_for(int(traffic["pool_seed"]), j)
            for j in range(int(traffic["pool"]))]
    order = np.random.default_rng(
        [ctx.seed & 0xFFFFFFFF, ctx.seed >> 32]).permutation(len(pool))
    jax.block_until_ready(pool)

    # --- the measured window ---
    ctx.compile_log.take()
    if ctx.trace:
        from bench.harness import TRACE_DIR
        jax.profiler.start_trace(str(TRACE_DIR))
    runs = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while time.perf_counter() - t0 < ctx.seconds:
            runs += [train(pool[j]) for j in order]
        t1 = time.perf_counter()
    if ctx.trace:
        jax.profiler.stop_trace()
    window_compiles = ctx.compile_log.take()
    dev = ctx.devices[0]
    peak = peak_bytes(dev)
    reached = sum(1 for _, ok in runs if ok)
    failed = len(runs) - reached
    e2e = {"time_to_target_s": (t1 - t0) / max(reached, 1),
           "setup_s": t0 - ctx.t_start,
           "peak_hbm_gib": peak / 2 ** 30}
    record = {"trainings": len(runs), "reached": reached,
              "steps": sum(s for s, _ in runs), "window_s": t1 - t0,
              "chips": len(ctx.devices)}
    print(f"window: {len(runs)} trainings ({reached} reached the target, "
          f"{record['steps']} steps, mean "
          f"{record['steps'] / max(len(runs), 1):.0f}) in {t1 - t0:.3f} s; "
          f"{len(window_compiles)} XLA compile(s) inside it "
          f"{window_compiles}", flush=True)

    checks = reference_checks(mod, cfg, batch, p0, prog_losses, grad1, p3)
    correct = all(c.ok for c in checks) and not window_compiles
    return Outcome(e2e, record, checks, len(runs), failed, correct,
                   peak)


def _leaf_norms(tree) -> list:
    import jax
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree_util.tree_leaves(tree)]


def _diff_norms(a, b) -> list:
    import jax
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def _flat(layers: list) -> list:
    """Reference layers in the program's leaf order (l<i>/b before l<i>/w)."""
    return [x for w, b in layers for x in (b, w)]


def reference_checks(mod, cfg, batch, p0, prog_losses, prog_grad1,
                     prog_p3) -> list:
    """Follow the check training's first three steps with the float64
    reference: each step's loss, each leaf's first-gradient norm and each
    moving leaf's parameter change."""
    x = np.asarray(batch["x"], np.float64)
    y = np.asarray(batch["y"], np.float64)
    layers0 = mod.to_lists(p0)
    snaps = []
    losses, g1, _ = mod.adam_steps(
        layers0, x, y, cfg["optimizer"], 3,
        after_step=lambda i, L: snaps.append(_flat(L)))
    ref_grad1 = _leaf_norms(_flat(g1))
    ref_change3 = [float(np.linalg.norm(a - b))
                   for a, b in zip(snaps[2], _flat(layers0))]
    keep = moving_leaves(ref_grad1)
    lim = cfg["limits"]
    return [Check("loss_gap", rel_gap(prog_losses, losses), lim["loss_gap"]),
            Check("grad_gap", norm_gap(prog_grad1, ref_grad1),
                  lim["grad_gap"]),
            Check("update_gap", norm_gap(_diff_norms(prog_p3, p0),
                                         ref_change3, keep),
                  lim["update_gap"])]
