"""Attention: GQA with RoPE/M-RoPE, sliding windows, KV caches.

Two attention cores, chosen per call by what the call can observe:

  * the Pallas flash kernel with its backward pass (kernels/flash.py via
    `kernels.ops.flash_attention`) on a TPU, for attention with no cache
    over whole sequences: training forwards (self- and cross-attention),
    the DMD gate's forwards, and cross-attention wherever its shapes
    qualify. It needs queries from position 0 of the keys (no cache, no
    `kv_len`, no `k_positions`, `q_offset == 0`), no sliding window (it has
    no window mask), Sq and Sk of at least one kernel block
    (`flash.LANES`), and queries whose dQ fits the backward's VMEM
    (`flash.fits`: up to 8192 at head dim 128 in bf16). It keeps the scores
    in VMEM and saves none of them for the backward pass.
  * the blockwise jnp core (`blockwise_attention`: online softmax, kv
    chunks of `chunk_k`) everywhere else: decode and cached prefill, ring
    caches, local windows, reduced configs shorter than a block, query
    sequences too long for the backward's VMEM, and every backend but the
    TPU. It is also the kernel's oracle.

Both run under `jax.named_scope("attention")`.

Two distribution layouts (selected per arch by head divisibility; see
DESIGN.md §6):
  * head-TP:    q/k/v sharded on the head dim over "model". Zero attention
                collectives. Requires n_heads % tp == 0 (and kv likewise, or
                kv replicated when n_kv < tp). On a mesh with more than one
                device the kernel runs per shard under `shard_map` (batch on
                the batch axes, heads on "model"); a Pallas call cannot be
                partitioned by GSPMD.
  * kv-SP:      heads replicated over "model"; K/V sharded on the SEQUENCE
                dim. The softmax statistics and the PV contraction reduce over
                the sharded dim, so GSPMD emits exactly the flash-decoding
                partial-softmax pattern (two small all-reduces). Works for any
                head count; also the long_500k decode layout. Always the jnp
                core.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import batch_axes, constrain, current_mesh
from repro.kernels import flash, ops
from repro.models import layers

NEG_INF = -1e30


def attn_init(key, cfg, n_heads=None, n_kv=None, abstract=False):
    n_heads = n_heads or cfg.n_heads
    n_kv = n_kv or cfg.n_kv_heads
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    hd = cfg.head_dim
    return {
        "wq": layers.dense_init(ks[0], (cfg.d_model, n_heads * hd), dtype, abstract),
        "wk": layers.dense_init(ks[1], (cfg.d_model, n_kv * hd), dtype, abstract),
        "wv": layers.dense_init(ks[2], (cfg.d_model, n_kv * hd), dtype, abstract),
        "wo": layers.dense_init(ks[3], (n_heads * hd, cfg.d_model), dtype, abstract),
    }


def _chunk_mask(q_pos, k_pos, causal: bool, window: int):
    """(Sq, Sk) boolean mask for one (q-chunk, k-chunk) pair."""
    rel = q_pos[:, None] - k_pos[None, :]
    mask = jnp.ones(rel.shape, bool)
    if causal:
        mask &= rel >= 0
    if window and window > 0:
        mask &= rel < window
    return mask


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_offset=0, kv_len: Optional[jnp.ndarray] = None,
                        k_positions: Optional[jnp.ndarray] = None,
                        chunk_k: int = 1024, logit_dtype=jnp.float32):
    """Online-softmax attention, scanning kv in chunks of `chunk_k`.

    q: (B, Sq, H, hd);  k/v: (B, Sk, K, hd) with H % K == 0 (GQA).
    q_offset: absolute position of q[0] (decode: cache length). May be traced.
    kv_len: optional scalar; kv positions >= kv_len are masked (decode with a
      partially-filled cache).
    k_positions: optional (Sk,) absolute positions (ring/window caches store
      out-of-order slots); defaults to arange(Sk). Negative = invalid slot.
    Never materializes (Sq, Sk) for the full sequence: peak is (Sq, chunk_k).
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    rep = H // K
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qf = (q.astype(logit_dtype) * scale)

    n_chunks = max(-(-Sk // chunk_k), 1)
    pad = n_chunks * chunk_k - Sk
    if k_positions is None:
        k_positions = jnp.arange(Sk)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-1)
    kc = k.reshape(B, n_chunks, chunk_k, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk_k, K, hd).transpose(1, 0, 2, 3, 4)
    pc = k_positions.reshape(n_chunks, chunk_k)

    q_pos = jnp.arange(Sq) + q_offset
    limit = kv_len if kv_len is not None else Sk

    def scan_fn(carry, inp):
        m_prev, l_prev, acc = carry
        k_pos, kb, vb = inp                              # (ck,), (B, ck, K, hd)
        # logits: (B, K, rep, Sq, ck)
        qg = qf.reshape(B, Sq, K, rep, hd)
        s = jnp.einsum("bsgrh,bcgh->bgrsc", qg, kb.astype(logit_dtype))
        mask = _chunk_mask(q_pos, k_pos, causal, window)
        mask &= (k_pos >= 0)[None, :] & (k_pos < limit)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)                      # (B,K,rep,Sq)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bgrsc,bcgh->bgrsh", p, vb.astype(logit_dtype))
        acc = acc * corr[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, K, rep, Sq), NEG_INF, logit_dtype)
    l0 = jnp.zeros((B, K, rep, Sq), logit_dtype)
    a0 = jnp.zeros((B, K, rep, Sq, hd), logit_dtype)
    if n_chunks == 1:
        (m, l, acc), _ = scan_fn((m0, l0, a0), (pc[0], kc[0], vc[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(scan_fn, (m0, l0, a0), (pc, kc, vc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


def _flash_core(q, k, v, *, causal: bool, head_sharded: bool):
    """The Pallas flash kernel where it applies, else None (the caller's
    jnp core): a TPU, Sq and Sk of at least one kernel block, a dQ that
    fits the backward's VMEM, and on a mesh of more than one device the
    head-TP layout with heads and batch that divide it (the kernel then
    runs per shard)."""
    B, Sq, H, d = q.shape
    if (ops.active_backend() != "pallas" or min(Sq, k.shape[1]) < flash.LANES
            or not flash.fits(Sq, d, q.dtype)):
        return None
    attend_fn = lambda q, k, v: ops.flash_attention(q, k, v, causal=causal)
    mesh = current_mesh()
    sizes = dict(mesh.shape) if mesh is not None else {}
    axes = batch_axes(mesh)
    n_batch = math.prod(sizes.get(a, 1) for a in axes)
    n_model = sizes.get("model", 1)
    if n_batch * n_model == 1:
        return attend_fn(q, k, v)
    if not head_sharded or H % n_model or B % n_batch:
        return None
    K = k.shape[2]
    if K != H:                       # each head shard needs its kv heads
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    spec = P(axes, None, "model", None)
    return jax.shard_map(attend_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


class KVCache(NamedTuple):
    k: jnp.ndarray          # (B, S_max, K, hd)
    v: jnp.ndarray
    length: jnp.ndarray     # scalar int32: valid prefix


class RingKVCache(NamedTuple):
    """Fixed-window ring buffer for sliding-window layers (gemma3 local):
    O(window) memory at any context length — what makes long_500k decode
    sub-quadratic in memory for the 5:1 local:global archs."""
    k: jnp.ndarray          # (B, W, K, hd)
    v: jnp.ndarray
    pos: jnp.ndarray        # (W,) absolute position per slot; -1 = empty
    length: jnp.ndarray     # total tokens seen


def init_kv_cache(batch, s_max, n_kv, head_dim, dtype, abstract=False):
    shape = (batch, s_max, n_kv, head_dim)
    if abstract:
        z = jax.ShapeDtypeStruct(shape, dtype)
        return KVCache(z, z, jax.ShapeDtypeStruct((), jnp.int32))
    z = jnp.zeros(shape, dtype)
    return KVCache(z, z, jnp.zeros((), jnp.int32))


def init_ring_cache(batch, window, n_kv, head_dim, dtype, abstract=False):
    shape = (batch, window, n_kv, head_dim)
    if abstract:
        z = jax.ShapeDtypeStruct(shape, dtype)
        return RingKVCache(z, z, jax.ShapeDtypeStruct((window,), jnp.int32),
                           jax.ShapeDtypeStruct((), jnp.int32))
    z = jnp.zeros(shape, dtype)
    return RingKVCache(z, z, jnp.full((window,), -1, jnp.int32),
                       jnp.zeros((), jnp.int32))


def pad_heads(t, target_groups_rep):
    """Zero-pad heads per GQA group: (B, S, H, hd) with H = K*rep ->
    (B, S, K*rep_pad, hd), preserving the q-head -> kv-head grouping.

    Padded-head attention is exact: zero q rows produce zero outputs (sliced
    off), zero k/v rows are never created here (kv pads use the same rule
    when K itself is padded, with matching q-group pads)."""
    K, rep, rep_pad = target_groups_rep
    B, S, H, hd = t.shape
    g = t.reshape(B, S, K, rep, hd)
    g = jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    return g.reshape(B, S, K * rep_pad, hd)


def attend(x, p, cfg, *, positions, causal=True, window=0,
           cache=None, head_tp: bool = True, use_rope: bool = True,
           kv_override=None, chunk_k: int = 1024, pad_heads_to: int = 0):
    """Full attention sub-layer: projections + rope + core + output.

    cache: KVCache (append at cache.length) or RingKVCache (window ring,
      decode only, S==1). kv_override: (k, v) tensors for cross-attention
      (whisper decoder -> encoder states); no cache update, no rope on kv.
    pad_heads_to: §Perf "padded head-TP": transiently zero-pad q (and, for
      MHA, kv) heads to a multiple of the TP degree so the attention core is
      head-sharded — replaces the kv-SP layout's per-layer q/k/v all-gathers
      with one small reshard, at (H_pad/H)x extra core-attention flops.
    """
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    if use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)

    kv_len = None
    k_positions = None
    q_offset = 0
    new_cache = None

    if kv_override is not None:
        k, v = kv_override
        K = k.shape[2]
        causal = False
    else:
        k = (x @ p["wk"]).reshape(B, S, K, hd)
        v = (x @ p["wv"]).reshape(B, S, K, hd)
        if use_rope:
            k = layers.apply_rope(k, positions, cfg.rope_theta,
                                  cfg.mrope_sections)

    H_eff, K_eff = H, K
    pad_rep = None
    if pad_heads_to and H % pad_heads_to != 0 and kv_override is None \
            and cache is None:
        H_pad = -(-H // pad_heads_to) * pad_heads_to
        if K == H:
            # MHA: pad q AND k/v heads at the end (one group per head).
            pad_rep = (1, H, H_pad)
            q = pad_heads(q, (1, H, H_pad))
            k = pad_heads(k, (1, H, H_pad))
            v = pad_heads(v, (1, H, H_pad))
            H_eff = K_eff = H_pad
        elif H_pad % K == 0:
            # GQA: pad each group's rep so grouping is preserved.
            rep, rep_pad = H // K, H_pad // K
            pad_rep = (K, rep, rep_pad)
            q = pad_heads(q, pad_rep)
            H_eff = H_pad

    if pad_rep is not None:
        kv_tp = "model" if K_eff % 16 == 0 and K_eff >= 16 else None
        q = constrain(q, "batch", None, "model", None)
        k = constrain(k, "batch", None, kv_tp, None)
        v = constrain(v, "batch", None, kv_tp, None)
    elif head_tp:
        kv_tp = "model" if K >= 16 else None
        q = constrain(q, "batch", None, "model", None)
        k = constrain(k, "batch", None, kv_tp, None)
        v = constrain(v, "batch", None, kv_tp, None)
    else:                                   # kv-SP: shard sequence of k/v
        q = constrain(q, "batch", None, None, None)
        k = constrain(k, "batch", "model", None, None)
        v = constrain(v, "batch", "model", None, None)

    if cache is not None and kv_override is None:
        if isinstance(cache, RingKVCache):
            W = cache.k.shape[1]
            if S > 1:
                # prefill: attend over the in-context k/v with the window
                # mask, then build the ring from the LAST W tokens (rolled so
                # slot s holds the token with position % W == s).
                if S >= W:
                    k_last = k[:, S - W:]
                    v_last = v[:, S - W:]
                    shift = S % W
                    kc = jnp.roll(k_last, shift, axis=1).astype(cache.k.dtype)
                    vc = jnp.roll(v_last, shift, axis=1).astype(cache.v.dtype)
                    sl = jnp.arange(W)
                    pos_arr = (S - W + ((sl - S) % W)).astype(jnp.int32)
                else:
                    pad = W - S
                    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))
                                 ).astype(cache.k.dtype)
                    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))
                                 ).astype(cache.v.dtype)
                    pos_arr = jnp.concatenate(
                        [jnp.arange(S), jnp.full((pad,), -1)]).astype(jnp.int32)
                new_cache = RingKVCache(kc, vc, pos_arr,
                                        jnp.asarray(S, jnp.int32)
                                        + 0 * cache.length)
                # attention itself runs over the full in-context k/v
            else:
                slot = cache.length % W
                kc = jax.lax.dynamic_update_slice_in_dim(
                    cache.k, k.astype(cache.k.dtype), slot, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(
                    cache.v, v.astype(cache.v.dtype), slot, axis=1)
                pos_arr = jax.lax.dynamic_update_slice_in_dim(
                    cache.pos, cache.length[None].astype(jnp.int32), slot,
                    axis=0)
                new_cache = RingKVCache(kc, vc, pos_arr, cache.length + 1)
                k, v = kc, vc
                k_positions = pos_arr
                q_offset = cache.length
                kv_len = cache.length + 1   # slots hold ABSOLUTE positions
        else:
            start = cache.length
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), start, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), start, axis=1)
            new_cache = KVCache(kc, vc, start + S)
            k, v = kc, vc
            kv_len = start + S
            q_offset = start

    with jax.named_scope("attention"):
        out = None
        if cache is None and not window:   # whole sequences from position 0
            out = _flash_core(q, k, v, causal=causal,
                              head_sharded=head_tp or pad_rep is not None)
        if out is None:
            out = blockwise_attention(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset, kv_len=kv_len,
                                      k_positions=k_positions,
                                      chunk_k=chunk_k)
    if pad_rep is not None:                 # drop the padded q heads
        K_, rep, rep_pad = pad_rep
        out = out.reshape(B, S, K_, rep_pad, hd)[:, :, :, :rep]
        out = out.reshape(B, S, H, hd)
    out = out.reshape(B, S, H * hd)
    if head_tp:
        out = constrain(out, "batch", None, "model")
    y = out @ p["wo"]
    y = constrain(y, "batch", None, None)
    return y, new_cache
