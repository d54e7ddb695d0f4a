"""Pallas TPU flash attention, forward and backward, for training.

Two kernels and a ``custom_vjp``:

  * forward, grid (B, H, q blocks, k blocks): each (bq, bk) tile of scores
    lives in VMEM only; the rows' max and sum are carried in VMEM scratch.
    It writes the output in f32 and each row's log-sum-exp;
  * backward, grid (B, H, k blocks, q blocks): recomputes P^T from the
    log-sum-exp, accumulates dK and dV of its k block over the q blocks,
    and dQ of the whole query sequence in VMEM (Sq x d f32: 0.4 MB at
    whisper's 1536 x 64), so each tile's scores are computed once. That
    bounds the query length the kernel takes (``fits``).

Nothing of size (Sq, Sk) reaches HBM or is saved for the backward pass.

The wrapper lays (B, S, H, d) out as (B, H, S, d) and pads S up to a
multiple of ``LANES``: padded keys are masked by position, padded query
rows are sliced off (their zero cotangent adds nothing to dK or dV). GQA
kv heads are repeated up to the query heads.

Precision is the jnp core's under XLA's DEFAULT precision on a TPU, where
a dot rounds f32 operands to bf16 and accumulates in f32: every product
takes bf16 operands (P and dS, f32 in the kernel, rounded to bf16 for the
P·V, P^T·dO, dS^T·Q and dS·K products) and accumulates in f32; the scale
1/sqrt(d) multiplies the f32 scores; the max, sum, exp and log are f32;
the row term of the softmax gradient, rowsum(dO * O), uses the f32
output, as autodiff of the jnp core does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                        # the kernels' block unit (TPU lanes)
DQ_VMEM = 8 << 20                  # VMEM bytes the backward's dQ may hold,
                                   # of a v5e's 16 MiB scoped limit
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
NN = (((1,), (0,)), ((), ()))      # a @ b
NT = (((1,), (1,)), ((), ()))      # a @ b.T
TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _pad_len(s: int) -> int:
    return -(-s // LANES) * LANES


def fits(sq: int, d: int, dtype) -> bool:
    """The backward's dQ of ``sq`` queries fits its VMEM budget: the f32
    scratch and the double-buffered output block, (Sq_pad, d) each, d
    padded to the lanes. 8192 positions at head dim 128 in bf16 fit."""
    per_row = 4 + 2 * jnp.dtype(dtype).itemsize
    return _pad_len(sq) * _pad_len(d) * per_row <= DQ_VMEM


def _block(s_pad: int, limit: int) -> int:
    """Largest multiple of LANES, at most ``limit``, that tiles s_pad."""
    return next(b for b in range(limit, 0, -LANES) if s_pad % b == 0)


def block_sizes(sq_pad: int, sk_pad: int):
    """(q block, k block) of both kernels: q blocks of up to 512 rows, and
    as many keys as fit (up to 1536: whisper's whole padded 1500 frames)."""
    return _block(sq_pad, 512), _block(sk_pad, 1536)


def _dot(a, b, dims):
    """a·b of ``b``'s dtype operands (``a`` rounded to it), accumulated in
    f32."""
    return lax.dot_general(a.astype(b.dtype), b, dims,
                           preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """A lane-replicated (rows, LANES) column, widened or cut to n lanes."""
    return jnp.tile(x, (1, n // LANES)) if n >= LANES else x[:, :n]


def _visible(qi, bq, ki, bk, causal: bool):
    """Some key of k block ki is visible to some query of q block qi."""
    return (qi + 1) * bq - 1 >= ki * bk if causal else True


def _keep(rows_q: bool, shape, q0, k0, causal: bool, sk: int, sk_pad: int):
    """Mask of visible (query, key) pairs of a tile; None where all are.
    rows_q: rows index queries (forward) or keys (backward)."""
    if not causal and sk == sk_pad:
        return None
    qa, ka = (0, 1) if rows_q else (1, 0)
    qpos = q0 + lax.broadcasted_iota(jnp.int32, shape, qa)
    kpos = k0 + lax.broadcasted_iota(jnp.int32, shape, ka)
    keep = kpos < sk
    if causal:
        keep &= kpos <= qpos
    return keep


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, causal, sk):
    bq, d = q_ref.shape[2:]
    bk, sk_pad = k_ref.shape[2], k_ref.shape[2] * pl.num_programs(3)
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(_visible(qi, bq, ki, bk, causal))
    def _step():
        s = lax.dot_general(q_ref[0, 0], k_ref[0, 0], NT,
                            preferred_element_type=jnp.float32) * scale
        keep = _keep(True, s.shape, qi * bq, ki * bk, causal, sk, sk_pad)
        if keep is not None:
            s = jnp.where(keep, s, MASK)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = (acc_sc[...] * _lanes(alpha, d)
                       + _dot(p, v_ref[0, 0], NN))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _out():
        l = l_sc[...]
        o_ref[0, 0] = acc_sc[...] / _lanes(l, d)
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l)).T[:1]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                scale, causal, sk):
    bq = q_ref.shape[2]
    bk, sk_pad = k_ref.shape[2], k_ref.shape[2] * pl.num_programs(2)
    ki, qi = pl.program_id(2), pl.program_id(3)
    last_q = qi == pl.num_programs(3) - 1

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(_visible(qi, bq, ki, bk, causal))
    def _step():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        st = lax.dot_general(k, q, NT,
                             preferred_element_type=jnp.float32) * scale
        keep = _keep(False, st.shape, qi * bq, ki * bk, causal, sk, sk_pad)
        pt = jnp.exp(st - lse_ref[0, 0])                       # (bk, bq)
        if keep is not None:
            pt = jnp.where(keep, pt, 0.0)
        dv_sc[...] += _dot(pt, do, NN)
        dpt = lax.dot_general(v, do, NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0, 0]) * scale
        dk_sc[...] += _dot(dst, q, NN)
        rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_sc[rows, :] += _dot(dst, k, TN)

    @pl.when(last_q)
    def _out_dkv():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(last_q & (ki == pl.num_programs(2) - 1))
    def _out_dq():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _forward(q, k, v, *, causal, sk, interpret):
    """(B, H, Sq_pad, d), (B, H, Sk_pad, d) -> f32 out, (B, H, 1, Sq_pad)
    log-sum-exp."""
    B, H, sq_pad, d = q.shape
    sk_pad = k.shape[2]
    bq, bk = block_sizes(sq_pad, sk_pad)
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, sk=sk)

    def kv_map(b, h, i, j):        # a skipped block fetches block 0 again
        return (b, h, jnp.where(_visible(i, bq, j, bk, causal), j, 0), 0)

    return pl.pallas_call(
        kernel, grid=(B, H, sq_pad // bq, sk_pad // bk),
        in_specs=[pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map)],
        out_specs=[pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
                   pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, sq_pad, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1, sq_pad), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret, name="flash_attention_fwd",
    )(q, k, v)


def _backward(q, k, v, do, lse, di, *, causal, sk, interpret):
    B, H, sq_pad, d = q.shape
    sk_pad = k.shape[2]
    bq, bk = block_sizes(sq_pad, sk_pad)
    kernel = functools.partial(_bwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, sk=sk)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0))
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, j, i: (b, h, 0, i))
    return pl.pallas_call(
        kernel, grid=(B, H, sk_pad // bk, sq_pad // bq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((1, 1, sq_pad, d),
                                lambda b, h, j, i: (b, h, 0, 0)),
                   k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((sq_pad, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="flash_attention_bwd",
    )(q, k, v, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sk, interpret):
    return _forward(q, k, v, causal=causal, sk=sk, interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, sk, interpret):
    o, lse = _forward(q, k, v, causal=causal, sk=sk, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sk, interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o * do, axis=-1)[:, :, None, :]
    return _backward(q, k, v, do.astype(q.dtype), lse, di, causal=causal,
                     sk=sk, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _heads_first(x, s_pad):
    x = jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool, interpret: bool = False):
    """q: (B, Sq, H, d); k/v: (B, Sk, K, d), H % K == 0 -> (B, Sq, H, d).

    Differentiable in q, k and v. Queries start at position 0 of the keys
    (``causal`` masks key j > query i)."""
    B, Sq, H, d = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    out = _flash(_heads_first(q, _pad_len(Sq)), _heads_first(k, _pad_len(Sk)),
                 _heads_first(v, _pad_len(Sk)), causal, Sk, interpret)
    return out.transpose(0, 2, 1, 3)[:, :Sq].astype(q.dtype)
