"""Pallas TPU kernel: one streaming Gram row r_j = <d_p, d_j>.

The streaming-Gram engine's hot pass (DESIGN.md §2): after the train step
writes the new snapshot p into its buffer slot, the running (m, m) Gram only
needs ONE new row — an O(m*n) anchored inner-product sweep over the buffer,
instead of the O(m^2*n) full recompute `gram.py` does. Bandwidth-bound: each
n-tile of the buffer streams HBM -> VMEM exactly once, together with the
matching tile of p; the (m, 1) fp32 accumulator lives in VMEM scratch across
the whole grid. The anchor subtraction (d = s - s_0) is fused: row 0 of each
buffer tile IS the anchor slice, so anchoring costs zero extra bandwidth.

Tiling matches gram.py: grid over n // block_n; blocks (m, block_n) with m
the array's own row count and block_n a multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_row_kernel(x_ref, p_ref, out_ref, acc_ref, *, anchor_first: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (m, block_n)
    q = p_ref[...].astype(jnp.float32)            # (1, block_n)
    if anchor_first:
        q = q - x[0:1, :]
        x = x - x[0:1, :]
    acc_ref[...] += jax.lax.dot_general(
        x, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (m, 1)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("anchor_first", "block_n", "interpret"))
def gram_row_pallas(snapshots: jnp.ndarray, p: jnp.ndarray, *,
                    anchor_first: bool = False, block_n: int = 2048,
                    interpret: bool = True) -> jnp.ndarray:
    """(m, n), (n,) -> (m,) fp32 row of <d_p, d_j>. Pads n to block_n (zero
    lanes contribute zero to every inner product, and the anchor row's
    padding is zero too, so padding is exact)."""
    m, n = snapshots.shape
    n_pad = -(-n // block_n) * block_n
    x = snapshots
    p2 = p.reshape(1, n)
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))
        p2 = jnp.pad(p2, ((0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        functools.partial(_gram_row_kernel, anchor_first=anchor_first),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((m, block_n), lambda i: (0, i)),
                  pl.BlockSpec((1, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((m, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, 1), jnp.float32)],
        interpret=interpret,
    )(x, p2)
    return out[:, 0]
