"""Pallas TPU kernel: snapshot combination w = S^T c.

The DMD hot spot #2 (DESIGN.md §2): the extrapolated weights are a linear
combination of the m stored snapshots with coefficients c computed from the
Gram matrix. Bandwidth-bound pass: each n-tile streams once, multiplied by
the tiny (m,) coefficient vector held in VMEM; fused anchor fold-back is
unnecessary because the anchor is already folded into c (dmd_coefficients).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _combine_kernel(c_ref, x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)            # (m, block_n)
    c = c_ref[...].astype(jnp.float32)            # (1, m)
    out_ref[...] = jax.lax.dot_general(
        c, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (1, block_n)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def combine_pallas(snapshots: jnp.ndarray, c: jnp.ndarray, *,
                   block_n: int = 2048, interpret: bool = True) -> jnp.ndarray:
    """(m, n), (m,) -> (n,) fp32."""
    m, n = snapshots.shape
    n_pad = -(-n // block_n) * block_n
    x = snapshots
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        _combine_kernel,
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((1, m), lambda i: (0, 0)),
                  pl.BlockSpec((m, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(c.astype(jnp.float32).reshape(1, m), x)
    return out[0, :n]
