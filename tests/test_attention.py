"""Attention: blockwise core vs naive oracle; prefill/decode consistency."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.kernels.ref import flash_attention_ref
from repro.models.attention import (blockwise_attention, init_kv_cache,
                                    init_ring_cache)
from repro.models.transformer import LanguageModel


@pytest.mark.parametrize("causal,window,chunk", [
    (True, 0, 16), (True, 0, 64), (False, 0, 32), (True, 8, 16),
])
def test_blockwise_matches_naive(causal, window, chunk):
    rng = np.random.default_rng(0)
    B, S, H, K, d = 2, 48, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, d)), jnp.float32)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              chunk_k=chunk)
    kr = jnp.repeat(k, H // K, axis=2)
    vr = jnp.repeat(v, H // K, axis=2)
    ref = flash_attention_ref(q, kr, vr, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_consistency_with_forward():
    """prefill + N decode steps must equal the one-shot forward logits."""
    acfg = get_config("tinyllama-1.1b")
    mc = reduced(acfg.model, n_layers=2)
    model = LanguageModel(mc, head_tp=False, chunk_k=16)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                mc.vocab_size)

    logits_full, _ = model.forward(params, {"tokens": tokens})

    n_pre = 16
    caches = model.init_cache(B, S + 8)
    logits_pre, caches = model.prefill(params, {"tokens": tokens[:, :n_pre]},
                                       caches)
    np.testing.assert_allclose(np.asarray(logits_pre[:, -1]),
                               np.asarray(logits_full[:, n_pre - 1]),
                               atol=2e-2, rtol=2e-2)
    for t in range(n_pre, S):
        logits_t, caches = model.decode_step(
            params, {"tokens": tokens[:, t:t + 1]}, caches)
        np.testing.assert_allclose(np.asarray(logits_t[:, 0]),
                                   np.asarray(logits_full[:, t]),
                                   atol=2e-2, rtol=2e-2)


def test_ring_cache_decode_matches_full_cache():
    """Sliding-window decode via O(W) ring cache == full cache + window mask."""
    acfg = get_config("gemma3-27b")
    mc = reduced(acfg.model, n_layers=6, sliding_window=8)
    model = LanguageModel(mc, head_tp=False, chunk_k=16)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 20
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                mc.vocab_size)
    logits_full, _ = model.forward(params, {"tokens": tokens})

    caches = model.init_cache(B, S + 4)     # local layers get W=8 ring caches
    n_pre = 12
    _, caches = model.prefill(params, {"tokens": tokens[:, :n_pre]}, caches)
    for t in range(n_pre, S):
        logits_t, caches = model.decode_step(
            params, {"tokens": tokens[:, t:t + 1]}, caches)
        np.testing.assert_allclose(np.asarray(logits_t[:, 0]),
                                   np.asarray(logits_full[:, t]),
                                   atol=3e-2, rtol=3e-2)


def test_kv_cache_append():
    cache = init_kv_cache(1, 8, 2, 4, jnp.float32)
    k = jnp.ones((1, 3, 2, 4))
    kc = jax.lax.dynamic_update_slice_in_dim(cache.k, k, cache.length, axis=1)
    assert float(kc[0, 2, 0, 0]) == 1.0 and float(kc[0, 3, 0, 0]) == 0.0


def test_ring_cache_positions():
    cache = init_ring_cache(1, 4, 2, 4, jnp.float32)
    assert cache.pos.shape == (4,)
    assert int(cache.pos[0]) == -1


def test_reduced_whisper_kernel_route_matches_jnp_route():
    """A reduced whisper-base (float32) gives the same loss and gradients
    with the kernel route forced (every self- and cross-attention through
    the Pallas flash kernel, in interpret mode) as with the jnp route. The
    lengths take several blocks on both axes: 1600 frames pad to 13 blocks
    of 128, and the 2048 causal tokens make 4 q blocks of 512 by 2 k
    blocks of 1024, one of them skipped."""
    import dataclasses
    from repro.kernels import ops

    acfg = get_config("whisper-base")
    mc = dataclasses.replace(
        reduced(acfg.model, encoder_seq_len=1600, max_seq_len=2048),
        dtype="float32")
    model = LanguageModel(mc, head_tp=False, chunk_k=64)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 2048
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          mc.vocab_size),
             "frames": jax.random.normal(jax.random.PRNGKey(2),
                                         (B, 1600, mc.d_model))}

    def run():           # a new function each time: JAX caches by function
        loss_and_grad = jax.value_and_grad(lambda p: model.loss(p, batch)[0])
        jaxpr = str(jax.make_jaxpr(loss_and_grad)(params))
        return jax.jit(loss_and_grad)(params), jaxpr.count("pallas_call")

    (l_jnp, g_jnp), n_jnp = run()
    ops.set_backend("pallas")
    try:
        (l_ker, g_ker), n_ker = run()
    finally:
        ops.set_backend(None)
    # forward and backward kernels of the three attentions (layers scanned)
    assert (n_jnp, n_ker) == (0, 6)
    np.testing.assert_allclose(float(l_ker), float(l_jnp), rtol=1e-5)
    for path, a in jax.tree_util.tree_leaves_with_path(g_ker):
        b = np.asarray(dict(jax.tree_util.tree_leaves_with_path(g_jnp))[path])
        np.testing.assert_allclose(np.asarray(a), b,
                                   atol=1e-4 * np.abs(b).max(),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("S,d,kernel", [(8192, 128, True), (8320, 128, False),
                                        (4096, 256, True), (4224, 256, False),
                                        (100, 64, False)])
def test_flash_route_bounds(S, d, kernel):
    """With the Pallas backend, whole-sequence attention takes the flash
    kernel from one block (128) up to the longest bf16 query sequence
    whose dQ fits the backward's VMEM, and the jnp core outside that."""
    from repro.kernels import ops
    from repro.models.attention import _flash_core

    x = jax.ShapeDtypeStruct((1, S, 2, d), jnp.bfloat16)
    ops.set_backend("pallas")
    try:
        out = jax.eval_shape(lambda q, k, v: _flash_core(
            q, k, v, causal=True, head_sharded=False), x, x, x)
    finally:
        ops.set_backend(None)
    assert (out is not None) == kernel
