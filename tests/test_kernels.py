"""Pallas kernels vs jnp oracles: shape x dtype sweeps in interpret mode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_shim import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("m,n", [(14, 5000), (8, 2048), (20, 333), (4, 128),
                                 (14, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("anchor", [False, True])
def test_gram_kernel(m, n, dtype, anchor):
    S = jnp.asarray(RNG.normal(size=(m, n)), dtype)
    g = ops.gram(S, anchor_first=anchor, interpret=True)
    g_ref = ref.gram_ref(S, anchor_first=anchor)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=tol,
                               atol=tol * max(1.0, float(jnp.max(jnp.abs(g_ref)))))


@pytest.mark.parametrize("m,n", [(14, 5000), (8, 2048), (20, 333), (4, 128),
                                 (14, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("anchor", [False, True])
def test_gram_row_kernel(m, n, dtype, anchor):
    """Streaming row kernel == ref, including row written into slot 0 (the
    anchor itself: the anchored row must be exactly zero)."""
    S = jnp.asarray(RNG.normal(size=(m, n)), dtype)
    for slot in (0, m // 2, m - 1):
        p = S[slot]
        r = ops.gram_row(S, p, anchor_first=anchor, interpret=True)
        r_ref = ref.gram_row_ref(S, p, anchor_first=anchor)
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(r_ref), rtol=tol,
            atol=tol * max(1.0, float(jnp.max(jnp.abs(r_ref)))))
        if anchor and slot == 0:
            assert float(jnp.max(jnp.abs(r))) == 0.0


@pytest.mark.parametrize("n", [7, 130])
@pytest.mark.parametrize("anchor", [False, True])
def test_tiny_leaf_kernels_match_oracle(n, anchor):
    """Regression (ISSUE 2): _block used to return blocks that were not
    128-lane multiples for 128 < n < block_n (n=130 -> block 130) and
    oversized tiles for n < 128; both now clamp to one lane-padded tile with
    the padding handled by the wrappers (zero lanes contribute zero)."""
    from repro.kernels.ops import _block
    assert _block(2048, 7) == 128
    assert _block(2048, 130) == 256
    assert _block(2048, 333) == 384              # lane multiple, < 2048
    assert _block(2048, 5000) == 2048
    m = 6
    rng = np.random.default_rng(100 + n)       # local stream: the shared RNG
    S = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)   # order must stay
    c = jnp.asarray(rng.normal(size=(m,)), jnp.float32)     # stable for the
                                                            # atol=0 tests
    g = ops.gram(S, anchor_first=anchor, interpret=True)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(ref.gram_ref(S, anchor_first=anchor)),
                               rtol=1e-5, atol=1e-5)
    r = ops.gram_row(S, S[2], anchor_first=anchor, interpret=True)
    np.testing.assert_allclose(
        np.asarray(r), np.asarray(ref.gram_row_ref(S, S[2],
                                                   anchor_first=anchor)),
        rtol=1e-5, atol=1e-5)
    w = ops.combine(S, c, interpret=True)
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref.combine_ref(S, c)),
                               rtol=1e-5, atol=1e-5)


def test_sharded_wrappers_local_path_matches_oracle():
    """kernels/sharded.py with no mesh degrades to local (vmapped) kernels —
    same contract as the flat kernels, per stacked layer."""
    from repro.configs.base import DMDConfig
    from repro.core import leafplan
    from repro.core.dmd import combine_snapshots, gram_matrix, gram_row_matrix
    from repro.kernels import sharded

    rng = np.random.default_rng(7)
    cfg = DMDConfig(m=5, anchor="first")
    params = {"seg": jnp.asarray(rng.normal(size=(3, 9, 11)), jnp.float32)}
    plans = leafplan.build_plans(params, cfg, stack_dims={"seg": 1})
    pl = plans["seg"]
    buf = jnp.asarray(rng.normal(size=(5, 3, 9, 11)), jnp.float32)
    g = sharded.gram(buf, pl, anchor_first=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(gram_matrix(buf, anchor="first",
                                              stack_dims=1)),
        rtol=1e-5, atol=1e-5)
    r = sharded.gram_row(buf, buf[2], pl, anchor_first=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(r), np.asarray(gram_row_matrix(buf, buf[2],
                                                  anchor="first",
                                                  stack_dims=1)),
        rtol=1e-5, atol=1e-5)
    c = jnp.asarray(rng.normal(size=(3, 5)), jnp.float32)
    w = sharded.combine(buf, c, pl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(combine_snapshots(buf, c, stack_dims=1)),
        rtol=1e-5, atol=1e-5)


def test_gram_row_matches_full_gram_row():
    """The kernel's row equals the corresponding row of the full Gram."""
    S = jnp.asarray(RNG.normal(size=(10, 700)), jnp.float32)
    g = ops.gram(S, anchor_first=True, interpret=True)
    for slot in (0, 4, 9):
        r = ops.gram_row(S, S[slot], anchor_first=True, interpret=True)
        np.testing.assert_allclose(np.asarray(r), np.asarray(g)[slot],
                                   rtol=1e-5, atol=1e-4)


def test_dispatch_routes_by_backend():
    """ops auto-routing: ref on CPU (never the Pallas interpreter), Pallas
    when forced; both agree numerically."""
    assert jax.default_backend() != "tpu"
    assert ops.active_backend() == "ref"
    S = jnp.asarray(RNG.normal(size=(6, 300)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(6,)), jnp.float32)
    auto_g = ops.gram(S, anchor_first=True)         # interpret=None -> ref
    auto_r = ops.gram_row(S, S[2], anchor_first=True)
    auto_w = ops.combine(S, c)
    try:
        ops.set_backend("pallas")                   # forced, interpret body
        assert ops.active_backend() == "pallas"
        pal_g = ops.gram(S, anchor_first=True, interpret=True)
        pal_r = ops.gram_row(S, S[2], anchor_first=True, interpret=True)
        pal_w = ops.combine(S, c, interpret=True)
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(auto_g), np.asarray(pal_g),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(auto_r), np.asarray(pal_r),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(auto_w), np.asarray(pal_w),
                               rtol=1e-5, atol=1e-4)


def test_dispatch_ref_path_no_flatten_multidim():
    """The ref route contracts trailing axes in place (sharding-safe) and
    matches the flattened kernel result."""
    S = jnp.asarray(RNG.normal(size=(6, 8, 12)), jnp.float32)
    g = ops.gram(S, anchor_first=True)
    flat = np.asarray(S).reshape(6, -1)
    flat = flat - flat[:1]
    np.testing.assert_allclose(np.asarray(g), flat @ flat.T, rtol=1e-5,
                               atol=1e-4)
    r = ops.gram_row(S, S[3], anchor_first=True)
    np.testing.assert_allclose(np.asarray(r), (flat @ flat[3]), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("m,n", [(14, 5000), (8, 100), (6, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_combine_kernel(m, n, dtype):
    S = jnp.asarray(RNG.normal(size=(m, n)), dtype)
    c = jnp.asarray(RNG.normal(size=(m,)), jnp.float32)
    w = ops.combine(S, c, interpret=True)
    w_ref = ref.combine_ref(S, c)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=tol,
                               atol=tol * 10)


def test_combine_multidim():
    S = jnp.asarray(RNG.normal(size=(6, 8, 12)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(6,)), jnp.float32)
    w = ops.combine(S, c, interpret=True)
    assert w.shape == (8, 12)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(ref.combine_ref(S.reshape(6, -1), c)
                                  ).reshape(8, 12), rtol=1e-5)


@pytest.mark.parametrize("B,Sq,Sk,H,K,d,causal", [
    (1, 128, 128, 4, 4, 64, True),
    (2, 256, 256, 4, 2, 64, True),
    (1, 640, 1664, 2, 1, 64, True),           # 5 q x 13 k blocks, skips
    (1, 100, 100, 2, 1, 32, False),
    (1, 64, 192, 2, 2, 128, True),            # Sq != Sk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(B, Sq, Sk, H, K, d, causal, dtype):
    """The kernel's forward against the oracle."""
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Sk, K, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Sk, K, d)), dtype)
    o = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    kr, vr = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    o_ref = ref.flash_attention_ref(q, kr, vr, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol * 50)


@pytest.mark.parametrize("B,Sq,Sk,H,K,d,causal", [
    (2, 100, 100, 2, 2, 64, False),       # pads queries and keys
    (2, 48, 200, 2, 2, 64, False),        # cross-attention's shape
    (1, 128, 128, 2, 2, 64, True),
    (1, 128, 128, 4, 2, 32, True),        # GQA
    (1, 2048, 2048, 2, 2, 64, True),      # 4 q x 2 k blocks, one skipped
    (1, 600, 1600, 2, 1, 64, False),      # 5 q x 13 k blocks, both padded
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads(B, Sq, Sk, H, K, d, causal, dtype):
    """The kernel's forward and its dq, dk, dv (the Pallas backward
    kernels, in TPU interpret mode) against autodiff of the jnp core, on
    one block and on several blocks of both axes (`flash.block_sizes`)."""
    from repro.models.attention import blockwise_attention
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Sk, K, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Sk, K, d)), dtype)
    w = jnp.asarray(RNG.normal(size=(B, Sq, H, d)), jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                       * w)

    kernel = lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                                 interpret=True)
    oracle = lambda q, k, v: blockwise_attention(q, k, v, causal=causal,
                                                 chunk_k=64)
    got = (kernel(q, k, v),) + jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    want = (oracle(q, k, v),) + jax.grad(loss(oracle), (0, 1, 2))(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype, name
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=tol * np.abs(b).max(), err_msg=name)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(4, 20), n=st.integers(16, 700),
       seed=st.integers(0, 100))
def test_gram_kernel_property(m, n, seed):
    rng = np.random.default_rng(seed)
    S = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    g = np.asarray(ops.gram(S, interpret=True))
    np.testing.assert_allclose(g, g.T, rtol=1e-5, atol=1e-5)  # symmetric
    assert np.all(np.diag(g) >= -1e-5)                        # PSD diag
    np.testing.assert_allclose(g, np.asarray(ref.gram_ref(S)), rtol=1e-4,
                               atol=1e-3)
