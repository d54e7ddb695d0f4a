"""The paper's regression DNN: feed-forward softsign MLP (6 -> 40 -> 200 ->
1000 -> 2670), Xavier init, trained with Adam on MSE — the network of Fig. 1.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def init_mlp(key, sizes: Sequence[int], dtype=jnp.float32):
    """sizes: [in, h1, ..., out]. Xavier/Glorot init (paper §2)."""
    params = {}
    keys = jax.random.split(key, len(sizes) - 1)
    for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        std = jnp.sqrt(2.0 / (fan_in + fan_out))
        params[f"l{i}"] = {
            "w": (jax.random.normal(keys[i], (fan_in, fan_out), jnp.float32)
                  * std).astype(dtype),
            "b": jnp.zeros((fan_out,), dtype),
        }
    return params


def mlp_forward(params, x, activation: str = "softsign"):
    act = {"softsign": jax.nn.soft_sign, "tanh": jnp.tanh,
           "relu": jax.nn.relu}[activation]
    n = len(params)
    h = x
    for i in range(n):
        p = params[f"l{i}"]
        h = h @ p["w"] + p["b"]
        if i < n - 1:
            h = act(h)
    return h


def mse_loss(params, x, y, activation: str = "softsign"):
    pred = mlp_forward(params, x, activation)
    return jnp.mean(jnp.square(pred - y))


PAPER_SIZES: Tuple[int, ...] = (6, 40, 200, 1000, 2670)


class MLPModel:
    """Trainer adapter for the regression MLP: ``init``/``loss`` is the
    whole contract the Trainer needs; batches are {"x", "y"} dicts."""

    def __init__(self, sizes: Sequence[int] = PAPER_SIZES,
                 act: str = "softsign"):
        self.sizes = tuple(sizes)
        self.act = act

    def init(self, key=None):
        return init_mlp(key if key is not None else jax.random.PRNGKey(0),
                        self.sizes)

    def loss(self, params, batch):
        return mse_loss(params, batch["x"], batch["y"], self.act), None
