"""A plain optimizer that follows a training run's first steps: Adam or
AdamW with global-norm clipping and the stated learning-rate schedule,
written from the published update rules, with no code of the program."""
from __future__ import annotations

import math
from typing import Callable, List


def lr_at(opt: dict, step: int) -> float:
    """Learning rate at ``step`` for a schedule stated as ``constant`` or
    ``cosine`` (cosine decay to min_lr_ratio after a linear warm-up)."""
    base, warm = opt["lr"], int(opt.get("warmup_steps", 0))
    ramp = 1.0 if warm == 0 else min((step + 1.0) / warm, 1.0)
    if opt.get("schedule", "constant") == "constant":
        return base * ramp
    if opt["schedule"] == "cosine":
        total = max(int(opt["total_steps"]), 1)
        floor = opt.get("min_lr_ratio", 0.1) * base
        t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
        return (floor + (base - floor) * 0.5 * (1 + math.cos(math.pi * t))) \
            * ramp
    raise ValueError(f"unknown schedule {opt['schedule']!r}")


def follow(params, batches: List, loss_fn: Callable, opt: dict, step0: int,
           after_step: Callable = None) -> dict:
    """Run ``len(batches)`` optimizer steps from ``params`` (a pytree held
    in its stored dtype) at step indices ``step0, step0 + 1, ...``.
    ``loss_fn(params_f32, batch)`` is the reference loss. Each leaf keeps
    its dtype: the update is rounded to it before it is added, and the sum
    again, as parameters held in that dtype are updated. Returns the losses,
    the first step's clipped gradient (float32 pytree), and the params after
    each step (``after_step(i, params)`` sees them as they come)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    b1, b2, eps = opt.get("b1", 0.9), opt.get("b2", 0.999), opt.get(
        "eps", 1e-8)
    wd, clip = opt.get("weight_decay", 0.0), opt.get("grad_clip", 0.0)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def update(p, m, v, g, lr, t):
        if clip:
            gn = jnp.sqrt(sum(jnp.sum(x * x)
                              for x in jax.tree_util.tree_leaves(g)))
            g = jax.tree_util.tree_map(
                lambda x: x * jnp.minimum(1.0, clip / (gn + 1e-12)), g)
        m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x,
                                   v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def step(x, mm, vv):
            u = -lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
            if wd:
                u = u - lr * wd * x.astype(jnp.float32)
            return (x.astype(jnp.float32)
                    + u.astype(x.dtype).astype(jnp.float32)).astype(x.dtype)

        return jax.tree_util.tree_map(step, p, m, v), m, v, g

    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params)
    m, v = zeros, zeros
    losses, first_grad = [], None
    for i, b in enumerate(batches):
        step = step0 + i
        loss, g = grad_fn(f32(params), b)
        params, m, v, g = update(params, m, v, g,
                                 jnp.float32(lr_at(opt, step)),
                                 jnp.float32(step + 1))
        losses.append(float(loss))
        if i == 0:
            first_grad = g
        if after_step is not None:
            after_step(i, params)
    return {"losses": losses, "first_grad": first_grad, "params": params}
