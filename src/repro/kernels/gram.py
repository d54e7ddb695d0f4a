"""Pallas TPU kernel: streaming snapshot Gram matrix G = D D^T.

The DMD hot spot #1 (DESIGN.md §2): a tall-skinny (m x n, n up to billions
per shard) self-Gram. Bandwidth-bound: each n-tile of the snapshot buffer
streams HBM -> VMEM exactly once; the m x m fp32 accumulator lives in VMEM
scratch across the whole grid (m <= 32). The anchor subtraction (D = S -
S[0], the fp32-conditioning fix) is fused into the same pass — row 0 of each
tile IS the anchor slice, so anchoring costs zero extra bandwidth.

Tiling: grid over n // block_n; block (m, block_n) — m is the array's own
row count, which the TPU tiling rule accepts without padding — and block_n
a multiple of 128 lanes. One MXU contraction (m x block_n) @ (block_n x m)
per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(x_ref, out_ref, acc_ref, *, anchor_first: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (m, block_n)
    if anchor_first:
        x = x - x[0:1, :]
    acc_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("anchor_first", "block_n", "interpret"))
def gram_pallas(snapshots: jnp.ndarray, *, anchor_first: bool = False,
                block_n: int = 2048, interpret: bool = True) -> jnp.ndarray:
    """(m, n) -> (m, m) fp32. Pads n to block_n (zero lanes contribute zero
    to the Gram, so padding is exact)."""
    m, n = snapshots.shape
    n_pad = -(-n // block_n) * block_n
    x = snapshots
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    return pl.pallas_call(
        functools.partial(_gram_kernel, anchor_first=anchor_first),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((m, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((m, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)],
        interpret=interpret,
    )(x)
