"""Packed leaf arenas (core/arena.py + kernels/arena.py, DESIGN.md §7).

Covers the ISSUE 5 satellite edge cases: leaf sizes that are not 128
multiples, a single-leaf bucket, an excluded-group-only config (empty
arena), a bf16 bucket under gram_upcast=False, and arena-vs-per-leaf
bit-exactness across full jump cycles (assert_array_equal on
integer-valued trajectories, where every fp32 sum is exact and any
segmentation/offset/masking slip would change bits).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import DMDConfig
from repro.core import DMDAccelerator
from repro.core import arena as arena_mod
from repro.core.schedule import DMDGroupRule
from repro.kernels import arena as ka
from repro.kernels import ops


def _cfg(**kw):
    kw.setdefault("m", 4)
    kw.setdefault("s", 5)
    kw.setdefault("warmup_steps", 0)
    kw.setdefault("cooldown_steps", 0)
    kw.setdefault("tol", 1e-6)
    return DMDConfig(**kw)


def _int_params(rng, sizes):
    """Integer-valued fp32 leaves (exact in any summation order)."""
    return {k: jnp.asarray(rng.integers(-8, 9, size=s), jnp.float32)
            for k, s in sizes.items()}


# ---------------------------------------------------------------------------
# Bucketing / layout
# ---------------------------------------------------------------------------

def test_bucket_layout_alignment_and_offsets():
    """Every segment starts on a block_n boundary, segments are disjoint
    and in pytree order, and the block->system table walks them in order."""
    rng = np.random.default_rng(0)
    params = _int_params(rng, {"a": (7,), "b": (10, 13), "c": (333,),
                               "d": (128,)})
    acc = DMDAccelerator(_cfg())
    table = acc.arena_for(params)
    assert len(table) == 1
    b = next(iter(table.values()))
    assert b.block_n % 128 == 0
    lane = 0
    for seg in b.segments:
        assert seg.lane_start == lane
        assert seg.lane_start % b.block_n == 0
        assert seg.seg_lanes % b.block_n == 0
        assert seg.seg_lanes >= seg.flat_local
        lane += seg.lanes
    assert b.n_lanes == lane
    bs = b.block_sys()
    assert bs.shape == (b.n_lanes // b.block_n,)
    assert (np.diff(bs) >= 0).all()          # sorted: systems consecutive
    assert bs[-1] == b.n_sys - 1


def test_single_leaf_bucket():
    params = {"w": jnp.arange(200, dtype=jnp.float32).reshape(8, 25)}
    acc = DMDAccelerator(_cfg())
    table = acc.arena_for(params)
    assert len(table) == 1
    (b,) = table.values()
    assert b.n_sys == 1 and len(b.segments) == 1
    bufs = acc.init(params)
    assert arena_mod.is_arena_state(bufs)
    assert all(l is None for l in jax.tree_util.tree_leaves(
        bufs["leaf"], is_leaf=lambda x: x is None))


def test_excluded_only_config_has_empty_arena():
    """Every leaf excluded by a group rule -> no buckets, no buffers; the
    state is NOT the arena wrapper (nothing to pack)."""
    cfg = _cfg(groups=(DMDGroupRule(name="none", path_regex=".",
                                    exclude=True),))
    params = {"w": jnp.ones((16, 16)), "b": jnp.ones((16,))}
    acc = DMDAccelerator(cfg)
    assert acc.arena_for(params) == {}
    bufs = acc.init(params)
    assert not arena_mod.is_arena_state(bufs)
    assert all(l is None for l in jax.tree_util.tree_leaves(
        bufs, is_leaf=lambda x: x is None))


def test_dot_general_route_keeps_per_leaf():
    cfg = _cfg(kernel_route="dot_general")
    params = {"w": jnp.ones((16, 16))}
    acc = DMDAccelerator(cfg)
    assert acc.arena_for(params) == {}
    assert not arena_mod.is_arena_state(acc.init(params))


def test_two_groups_two_buckets():
    cfg = _cfg(groups=(DMDGroupRule(name="vecs", max_ndim=1, m=3,
                                    phase=1),))
    params = {"w": jnp.ones((16, 16)), "b": jnp.ones((48,))}
    acc = DMDAccelerator(cfg)
    table = acc.arena_for(params)
    assert len(table) == 2
    ms = sorted(b.m for b in table.values())
    assert ms == [3, 4]


# ---------------------------------------------------------------------------
# Kernel contract: segmented Pallas (interpret) vs reference vs per-leaf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_segmented_kernels_on_stored_rows(dtype):
    """Buffers are stored at snapshot_rows (m rounded up to the sublane
    tile, zero rows below): every kernel, told the real m, matches the
    reference on the unpadded m-row buffer. 11 blocks is not a multiple of
    the query row's sublane tile, so the last q tile is ragged."""
    rng = np.random.default_rng(4)
    m, block_n, nb = 5, 128, 11
    rows = ka.snapshot_rows(m, dtype)
    assert rows > m and rows % 8 == 0
    bs = np.asarray([0] * 3 + [1] * 6 + [2] * 2, np.int32)
    x = jnp.asarray(rng.normal(size=(nb, m, block_n)), dtype)
    q = jnp.asarray(rng.normal(size=(nb, block_n)), dtype)
    xs = jnp.pad(x, ((0, 0), (0, rows - m), (0, 0)))
    c = jnp.asarray(rng.normal(size=(3, m)), jnp.float32)
    for anchor_first in (False, True):
        np.testing.assert_allclose(
            np.asarray(ka.gram_row_pallas(
                xs, q, bs, 3, anchor_first=anchor_first, block_n=block_n,
                m=m, interpret=True)),
            np.asarray(ka.gram_row_ref(x, q, bs, 3,
                                       anchor_first=anchor_first,
                                       block_n=block_n)),
            rtol=1e-5, atol=1e-4)
    for anchor in ("first", "mean"):
        kw = dict(anchor_first=anchor == "first",
                  anchor_mean=anchor == "mean", block_n=block_n)
        np.testing.assert_allclose(
            np.asarray(ka.gram_pallas(xs, bs, 3, m=m, interpret=True, **kw)),
            np.asarray(ka.gram_ref(x, bs, 3, **kw)), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ka.combine_pallas(xs, c, bs, block_n=block_n,
                                     interpret=True)),
        np.asarray(ka.combine_ref(x, c, bs, block_n=block_n)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("anchor_first", [False, True])
def test_segmented_kernels_match_reference(anchor_first):
    rng = np.random.default_rng(1)
    m, block_n = 5, 128
    sizes = [7, 130, 333, 128]                 # none except 128 lane-aligned
    segs = [-(-s // block_n) * block_n for s in sizes]
    n = sum(segs)
    x = np.zeros((m, n), np.float32)
    q = np.zeros((n,), np.float32)
    lane = 0
    block_sys = []
    for i, (s, p) in enumerate(zip(sizes, segs)):
        x[:, lane:lane + s] = rng.normal(size=(m, s))
        q[lane:lane + s] = rng.normal(size=s)
        block_sys += [i] * (p // block_n)
        lane += p
    x, q = jnp.asarray(x), jnp.asarray(q)
    bs = np.asarray(block_sys, np.int32)
    # the kernels take BLOCK-MAJOR inputs; the flat x/q stay around for the
    # per-leaf oracle slices below (blocking is a pure relayout)
    xb = x.reshape(m, n // block_n, block_n).transpose(1, 0, 2)
    qb = q.reshape(n // block_n, block_n)

    ref_row = ka.gram_row_ref(xb, qb, bs, 4, anchor_first=anchor_first,
                              block_n=block_n)
    pal_row = ka.gram_row_pallas(xb, qb, bs, 4, anchor_first=anchor_first,
                                 block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(pal_row), np.asarray(ref_row),
                               rtol=1e-6, atol=1e-5)

    ref_g = ka.gram_ref(xb, bs, 4, anchor_first=anchor_first,
                        block_n=block_n)
    pal_g = ka.gram_pallas(xb, bs, 4, anchor_first=anchor_first,
                           block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(pal_g), np.asarray(ref_g),
                               rtol=1e-6, atol=1e-5)

    c = jnp.asarray(rng.normal(size=(4, m)), jnp.float32)
    ref_c = ka.combine_ref(xb, c, bs, block_n=block_n)
    pal_c = ka.combine_pallas(xb, c, bs, block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(pal_c), np.asarray(ref_c),
                               rtol=1e-6, atol=1e-5)

    # per-leaf oracle: each segment's row/gram/combine equals the flat
    # kernels applied to that segment alone
    lane = 0
    for i, (s, p) in enumerate(zip(sizes, segs)):
        xs = x[:, lane:lane + s]
        qs = q[lane:lane + s]
        np.testing.assert_allclose(
            np.asarray(ref_row[i]),
            np.asarray(ops.gram_row(xs, qs, anchor_first=anchor_first,
                                    interpret=None)), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(ref_c[lane:lane + s]),
            np.asarray(ops.combine(xs, c[i], interpret=None)),
            rtol=1e-5, atol=1e-4)
        lane += p


# ---------------------------------------------------------------------------
# Arena vs per-leaf: bit-exact full jump cycles on integer trajectories
# ---------------------------------------------------------------------------

def _run_cycles(cfg, params, deltas, steps, quantize=False):
    """record/update/jump `steps` steps through the accelerator API;
    returns (params_after, buffers, grams). ``quantize`` rounds the params
    after every jump so SNAPSHOT VALUES stay integer across windows — the
    exactness precondition of the bit-exact route contract (the streaming
    row kernel contracts the RAW ring buffer via the part-anchor identity,
    so integer per-step drifts alone no longer guarantee exact sums once a
    jump emits full-mantissa params)."""
    acc = DMDAccelerator(cfg)
    bufs = acc.init(params)
    grams = acc.init_grams(bufs)
    p = params
    for t in range(steps):
        p = jax.tree_util.tree_map(lambda x, d: x + d, p, deltas)
        bufs, grams = acc.record(bufs, p, acc.slots(t), grams)
        if acc.should_apply(t):
            p, _ = acc.apply(p, bufs, grams=grams, step=t)
            if quantize:
                p = jax.tree_util.tree_map(jnp.round, p)
    return acc, p, bufs, grams


def test_arena_vs_perleaf_bitexact_full_cycles():
    """Two full jump cycles (window wrap + second jump) on integer-valued
    trajectories: with ``quantize`` keeping the post-jump params integer,
    every snapshot VALUE is integer, all Gram sums are exact in any
    summation order (including the arena's part-anchor identity on the raw
    buffer), and the two routes must agree BIT-EXACTLY on every leaf — any
    offset/masking/segmentation slip changes bits. Covers sizes off the
    128-lane grid and a stacked leaf. The unquantized cross-route bound
    lives in the float-trajectory test below."""
    rng = np.random.default_rng(7)
    sizes = {"a": (7,), "b": (10, 13), "c": (333,), "d": (2, 5, 6)}
    params = _int_params(rng, sizes)
    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg()
    acc_a, p_arena, bufs_a, grams_a = _run_cycles(cfg, params, deltas, 9,
                                                  quantize=True)
    cfg_o = dataclasses.replace(cfg, arena=False)
    acc_o, p_leaf, bufs_o, grams_o = _run_cycles(cfg_o, params, deltas, 9,
                                                 quantize=True)

    for k in sizes:
        np.testing.assert_array_equal(np.asarray(p_arena[k]),
                                      np.asarray(p_leaf[k]), err_msg=k)

    # buffers and Grams agree bit-exactly through the leaf-wise view
    from repro.train.state import TrainState
    st = TrainState(p_arena, None, jnp.zeros((), jnp.int32), bufs_a, grams_a)
    lw = acc_a.state_leafwise(st)
    flat_o = {k: v for k, v in zip(sizes, jax.tree_util.tree_leaves(bufs_o))}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(lw.dmd_buffers)[0]:
        k = jax.tree_util.keystr(kp).strip("[']").split("'")[0]
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat_o[k]), err_msg=k)
    flat_g = {k: v for k, v in zip(sizes, jax.tree_util.tree_leaves(grams_o))}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(lw.dmd_gram)[0]:
        k = jax.tree_util.keystr(kp).strip("[']").split("'")[0]
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat_g[k]), err_msg=k)


def test_arena_vs_perleaf_close_on_float_trajectories():
    """Real-valued trajectories: the DATA passes (buffers bit-exact, Grams
    at fp32 summation-order noise) must agree tightly. The post-jump params
    only get a loose bound: with the fp32 noise floor unmasked (tol below
    it) the eigensolve legitimately amplifies last-ulp Gram differences on
    a near-rank-deficient window — the integer-trajectory test above is
    the exact-equality guarantee; this one pins the passes feeding it."""
    rng = np.random.default_rng(3)
    sizes = {"a": (40,), "b": (10, 13), "c": (333,)}
    params = {k: jnp.asarray(rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    deltas = {k: jnp.asarray(0.01 * rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg(tol=1e-3)                      # mask the fp32 noise tail
    acc_a, p_arena, bufs_a, grams_a = _run_cycles(cfg, params, deltas, 4)
    acc_o, p_leaf, bufs_o, grams_o = _run_cycles(
        dataclasses.replace(cfg, arena=False), params, deltas, 4)

    from repro.train.state import TrainState
    lw = acc_a.state_leafwise(TrainState(
        p_arena, None, jnp.zeros((), jnp.int32), bufs_a, grams_a))
    order = sorted(sizes)
    for k, b_a, b_o, g_a, g_o in zip(
            order, jax.tree_util.tree_leaves(lw.dmd_buffers),
            jax.tree_util.tree_leaves(bufs_o),
            jax.tree_util.tree_leaves(lw.dmd_gram),
            jax.tree_util.tree_leaves(grams_o)):
        np.testing.assert_array_equal(np.asarray(b_a), np.asarray(b_o),
                                      err_msg=k)
        np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_o),
                                   rtol=1e-5, atol=1e-4, err_msg=k)
    for k in sizes:
        np.testing.assert_allclose(np.asarray(p_arena[k]),
                                   np.asarray(p_leaf[k]),
                                   rtol=0.05, atol=0.05, err_msg=k)


def test_bf16_bucket_gram_upcast_false():
    """bf16 snapshot storage + gram_upcast=False: the bucket stores bf16,
    Grams still come out fp32, and — the route contract — the arena agrees
    with the per-leaf route AT THE SAME CONFIG (both kernel routes upcast
    per block/tile in fp32; regression: an early arena ref downcast the
    combine coefficients to bf16, a 1.8% divergence this same-config
    oracle catches and the fp32-route comparison below never would).
    tol=1e-3 masks the fp32-ordering noise tail of the eigensolve."""
    rng = np.random.default_rng(5)
    sizes = {"w": (24, 9), "v": (130,)}
    params = {k: jnp.asarray(rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    deltas = {k: jnp.asarray(0.05 * rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg(snapshot_dtype="bfloat16", gram_upcast=False, anchor="first",
               tol=1e-3)
    acc, p_b, bufs, grams = _run_cycles(cfg, params, deltas, 4)
    for key, buf in bufs["__arena__"].items():
        assert buf.dtype == jnp.bfloat16, key
    for key, g in grams["__arena__"].items():
        assert g.dtype == jnp.float32, key
    # same-config per-leaf oracle: buffers bit-exact, params at fp32 noise
    acc_o, p_o, bufs_o, grams_o = _run_cycles(
        dataclasses.replace(cfg, arena=False), params, deltas, 4)
    from repro.train.state import TrainState
    lw = acc.state_leafwise(TrainState(
        p_b, None, jnp.zeros((), jnp.int32), bufs, grams))
    for k, b_o in zip(sorted(sizes), jax.tree_util.tree_leaves(bufs_o)):
        np.testing.assert_array_equal(
            np.asarray(lw.dmd_buffers[k].astype(jnp.float32)),
            np.asarray(b_o.astype(jnp.float32)), err_msg=k)
    for k in sizes:
        np.testing.assert_allclose(np.asarray(p_b[k]), np.asarray(p_o[k]),
                                   rtol=2e-3, atol=2e-3, err_msg=k)
    # and the bf16 storage stays close to the fp32-storage route
    _, p_f, _, _ = _run_cycles(
        dataclasses.replace(cfg, snapshot_dtype="float32", gram_upcast=True),
        params, deltas, 4)
    for k in sizes:
        np.testing.assert_allclose(np.asarray(p_b[k]), np.asarray(p_f[k]),
                                   rtol=0.15, atol=0.05, err_msg=k)


# ---------------------------------------------------------------------------
# Streaming vs recompute + leaf-wise checkpoint interop
# ---------------------------------------------------------------------------

def test_arena_streaming_gram_equals_recompute():
    """The per-bucket streaming rows reproduce the one-launch full Gram
    recompute at the window-complete point (the §2 invariant, arena'd)."""
    rng = np.random.default_rng(11)
    sizes = {"a": (40,), "b": (10, 13)}
    params = {k: jnp.asarray(rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg(anchor="first")
    acc = DMDAccelerator(cfg)
    bufs = acc.init(params)
    grams = acc.init_grams(bufs)
    p = params
    for t in range(4):
        p = jax.tree_util.tree_map(
            lambda x: x + 0.01 * jnp.ones_like(x) * (t + 1), p)
        bufs, grams = acc.record(bufs, p, acc.slots(t), grams)
    table = acc.arena_for(params)
    for key, b in table.items():
        full = ka.gram(bufs["__arena__"][key], b.block_sys(), b.n_sys,
                       anchor_first=True, block_n=b.block_n, m=b.m)
        np.testing.assert_allclose(np.asarray(grams["__arena__"][key]),
                                   np.asarray(full), rtol=1e-5, atol=1e-5)


def test_checkpoint_interop_arena_and_perleaf(tmp_path):
    """A checkpoint written by an arena run restores bit-exactly into a
    per-leaf run and vice versa: the on-disk format is the leaf-wise
    layout either way."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.train.state import TrainState

    rng = np.random.default_rng(13)
    sizes = {"a": (40,), "b": (10, 13), "c": (333,)}
    params = _int_params(rng, sizes)
    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg()
    acc_a, p_a, bufs_a, grams_a = _run_cycles(cfg, params, deltas, 6)
    st_a = TrainState(p_a, None, jnp.asarray(6, jnp.int32), bufs_a, grams_a)
    save_checkpoint(tmp_path / "arena", acc_a.state_leafwise(st_a), 6)

    # restore into a per-leaf run: template = per-leaf layout
    cfg_o = dataclasses.replace(cfg, arena=False)
    acc_o = DMDAccelerator(cfg_o)
    bufs_t = acc_o.init(params)
    st_t = TrainState(params, None, jnp.asarray(0, jnp.int32), bufs_t,
                      acc_o.init_grams(bufs_t))
    back = restore_checkpoint(tmp_path / "arena", st_t)
    oracle = acc_a.state_leafwise(st_a)
    for x, y in zip(jax.tree_util.tree_leaves(back.dmd_buffers),
                    jax.tree_util.tree_leaves(oracle.dmd_buffers)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    # and back the other way: per-leaf checkpoint -> arena run
    save_checkpoint(tmp_path / "leaf", back, 6)
    acc_b = DMDAccelerator(cfg)
    bufs_b = acc_b.init(params)
    st_b = TrainState(params, None, jnp.asarray(0, jnp.int32), bufs_b,
                      acc_b.init_grams(bufs_b))
    restored = restore_checkpoint(tmp_path / "leaf",
                                  acc_b.state_leafwise(st_b))
    packed = acc_b.state_arenaize(restored)
    assert arena_mod.is_arena_state(packed.dmd_buffers)
    for key in bufs_a["__arena__"]:
        np.testing.assert_array_equal(
            np.asarray(packed.dmd_buffers["__arena__"][key]),
            np.asarray(bufs_a["__arena__"][key]), err_msg=key)
        np.testing.assert_array_equal(
            np.asarray(packed.dmd_gram["__arena__"][key]),
            np.asarray(grams_a["__arena__"][key]), err_msg=key)


def test_jump_tree_requires_bucket_table_for_packed_buffers():
    """jump_tree on arena-packed buffers without the bucket table must
    raise, not silently leave every packed leaf unjumped."""
    from repro.core.accelerator import _none_like, jump_tree
    params = {"w": jnp.ones((16, 16))}
    acc = DMDAccelerator(_cfg())
    bufs = acc.init(params)
    plans = acc.plans_for(params)
    with pytest.raises(ValueError, match="bucket table"):
        jump_tree(acc.cfg, plans, params, bufs, _none_like(bufs), 1.0)


def test_state_specs_requires_bucket_table_for_packed_state():
    """Passing an arena-layout state to state_specs without the bucket
    table must raise, not silently mark lane-sharded ring buffers
    replicated (a multi-GiB-per-device cliff on real meshes)."""
    from repro.launch.inputs import state_specs
    from repro.train.state import TrainState
    params = {"w": jnp.ones((16, 16))}
    acc = DMDAccelerator(_cfg())
    bufs = acc.init(params)
    st = TrainState(params, None, jnp.zeros((), jnp.int32), bufs,
                    acc.init_grams(bufs))
    with pytest.raises(ValueError, match="bucket table"):
        state_specs(st, None)
    specs = state_specs(st, None, plans=acc.plans_for(params),
                        arena=acc.arena_for(params))
    assert jax.tree_util.tree_leaves(specs)


def test_plan_table_shows_arena_columns():
    params = {"w": jnp.ones((16, 16)), "b": jnp.ones((48,))}
    acc = DMDAccelerator(_cfg())
    table = acc.plan_table(params)
    assert "arena" in table and "g0-float32" in table
    acc2 = DMDAccelerator(_cfg(arena=False))
    table2 = acc2.plan_table(params)
    assert "g0-float32" not in table2


# ---------------------------------------------------------------------------
# Bucket-scope Koopman DMD (ISSUE 8 tentpole, DESIGN.md §9)
# ---------------------------------------------------------------------------

def test_bucket_scope_single_system_bucket_bitexact_leaf():
    """A single-segment single-system bucket is the degenerate case where
    the two scopes are the SAME program: the collapsed block->system table
    is already all zeros and n_sys is already 1, so bucket scope must be
    bit-exact with leaf scope — params, buffers, and Grams."""
    rng = np.random.default_rng(23)
    sizes = {"w": (8, 25)}
    params = _int_params(rng, sizes)
    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg()
    acc_l, p_l, bufs_l, grams_l = _run_cycles(cfg, params, deltas, 9,
                                              quantize=True)
    acc_b, p_b, bufs_b, grams_b = _run_cycles(
        dataclasses.replace(cfg, scope="bucket"), params, deltas, 9,
        quantize=True)
    (b,) = acc_b.arena_for(params).values()
    assert b.bucket_scoped("bucket") and b.n_sys == 1
    np.testing.assert_array_equal(np.asarray(p_b["w"]), np.asarray(p_l["w"]))
    for key in bufs_l["__arena__"]:
        np.testing.assert_array_equal(
            np.asarray(bufs_b["__arena__"][key]),
            np.asarray(bufs_l["__arena__"][key]), err_msg=key)
        np.testing.assert_array_equal(
            np.asarray(grams_b["__arena__"][key]),
            np.asarray(grams_l["__arena__"][key]), err_msg=key)


def test_bucket_scope_gram_is_segment_sum_across_wraps():
    """The streaming bucket Gram under scope="bucket" IS the segment-sum
    of the per-segment Grams (pad lanes are zero and all segments share
    one slot schedule, DESIGN.md §9): after the ring wraps, the (1, m, m)
    bucket Gram equals both (a) the leaf-scope run's Gram stack summed
    over systems and (b) a dot_general oracle on the anchored leaf-wise
    snapshots. Integer trajectories make every fp32 sum exact in any
    association order, so (a) is bit-exact. 8 steps with m=4 wraps the
    ring once and ends at a window-complete point, where the streaming
    Gram equals the full anchored recompute (the §2 invariant) and the
    oracle (b) is well-defined."""
    rng = np.random.default_rng(29)
    sizes = {"a": (7,), "b": (10, 13), "c": (333,), "d": (2, 5, 6)}
    params = _int_params(rng, sizes)
    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg()
    acc_l, p_l, bufs_l, grams_l = _run_cycles(cfg, params, deltas, 8,
                                              quantize=True)
    acc_b, p_b, bufs_b, grams_b = _run_cycles(
        dataclasses.replace(cfg, scope="bucket"), params, deltas, 8,
        quantize=True)

    (key,) = grams_b["__arena__"]
    gb = np.asarray(grams_b["__arena__"][key])
    assert gb.shape == (1, cfg.m, cfg.m)
    # (a) segment-sum of the leaf-scope Gram stack, bit-exact
    gl = np.asarray(grams_l["__arena__"][key]).sum(axis=0, keepdims=True)
    np.testing.assert_array_equal(gb, gl)

    # (b) dot_general oracle over the anchored leaf-wise snapshots: the
    # concatenated-bucket-state Gram. Buffers are scope-independent, so
    # the bucket run's leaf-wise view supplies the snapshot matrix.
    from repro.train.state import TrainState
    lw = acc_b.state_leafwise(TrainState(
        p_b, None, jnp.zeros((), jnp.int32), bufs_b, grams_b))
    rows = []
    for k in sorted(sizes):
        x = np.asarray(lw.dmd_buffers[k], np.float32)
        x = x.reshape(cfg.m, -1)                  # (m, flat leaf)
        rows.append(x - x[0])                     # anchor="first"
    d = np.concatenate(rows, axis=1)              # (m, sum of lanes)
    np.testing.assert_array_equal(gb[0], d @ d.T)


def test_bucket_scope_bf16_gram_upcast_false_segment_sum():
    """bf16 snapshot storage with gram_upcast=False under bucket scope:
    the (1, m, m) Gram stays fp32 and still equals the segment-sum of the
    leaf-scope Gram stack (same f32-accumulating block kernels, the only
    change is the collapsed segment reduction) at fp32 ordering noise."""
    rng = np.random.default_rng(31)
    sizes = {"w": (24, 9), "v": (130,)}
    params = {k: jnp.asarray(rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    deltas = {k: jnp.asarray(0.05 * rng.normal(size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg = _cfg(snapshot_dtype="bfloat16", gram_upcast=False, anchor="first",
               tol=1e-3)
    _, _, bufs_l, grams_l = _run_cycles(cfg, params, deltas, 4)
    acc_b, p_b, bufs_b, grams_b = _run_cycles(
        dataclasses.replace(cfg, scope="bucket"), params, deltas, 4)
    for key, g in grams_b["__arena__"].items():
        assert g.dtype == jnp.float32, key
        assert g.shape[0] == 1, key
        gl = np.asarray(grams_l["__arena__"][key], np.float32)
        np.testing.assert_allclose(np.asarray(g)[0], gl.sum(axis=0),
                                   rtol=1e-5, atol=1e-4, err_msg=key)
    for k in sizes:
        assert np.isfinite(np.asarray(p_b[k])).all(), k


def test_bucket_scope_tables_and_spectrum():
    """plan_table / layout_table grow a scope column, the bucket's solve
    count collapses to 1, and spectrum_table renders one Koopman
    eigenvalue row per bucket from the shared operator's Gram."""
    rng = np.random.default_rng(37)
    sizes = {"w": (16, 16), "b": (48,)}
    params = _int_params(rng, sizes)
    cfg = _cfg(scope="bucket")
    acc = DMDAccelerator(cfg)
    table = acc.arena_for(params)
    (b,) = table.values()
    assert b.gram_lead("bucket") == 1 and b.gram_lead("leaf") == b.n_sys
    assert (b.scope_block_sys("bucket") == 0).all()
    (rec,) = arena_mod.layout_table(table, scope="bucket")
    assert rec["scope"] == "bucket" and rec["n_solve"] == 1
    (rec_l,) = arena_mod.layout_table(table)          # default: leaf
    assert rec_l["scope"] == "leaf" and rec_l["n_solve"] == b.n_sys
    assert "scope" in acc.plan_table(params)

    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    _, _, bufs, grams = _run_cycles(cfg, params, deltas, 4)
    spec = acc.spectrum_table(bufs, grams)
    assert "|lam|max" in spec and "decay/step" in spec
    # leaf scope renders the SAME bucket-summed diagnostic (comparable)
    acc_l = DMDAccelerator(_cfg())
    acc_l.plans_for(params)
    _, _, bufs_l, grams_l = _run_cycles(_cfg(), params, deltas, 4)
    spec_l = acc_l.spectrum_table(bufs_l, grams_l)
    assert "|lam|max" in spec_l

    with pytest.raises(ValueError):
        DMDAccelerator(_cfg()).spectrum_table(bufs)


def test_bucket_scope_unknown_scope_raises():
    params = {"w": jnp.ones((16, 16))}
    acc = DMDAccelerator(_cfg())
    (b,) = acc.arena_for(params).values()
    with pytest.raises(ValueError, match="scope"):
        b.bucket_scoped("global")


def test_checkpoint_interop_bucket_and_leaf_scope(tmp_path):
    """Checkpoints stay leaf-wise on disk in BOTH scopes (DESIGN.md §9):
    a bucket-scope run's checkpoint restores bit-exactly into a leaf-scope
    run (per-leaf Grams recomputed from the buffers at save), and a
    leaf-scope checkpoint restores into a bucket-scope run (leaf Grams
    segment-summed at arenaize) — integer trajectories, exact sums. Runs
    to a window-complete point (8 steps, m=4): the bucket-scope save
    RECOMPUTES the leaf-wise Grams from the buffers, which matches the
    streaming Gram exactly there (mid-window the streaming rows carry the
    previous window's products and the Trainer rebuilds Grams on restore
    anyway — snapshots.recompute_grams)."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.train.state import TrainState

    rng = np.random.default_rng(41)
    sizes = {"a": (40,), "b": (10, 13), "c": (333,)}
    params = _int_params(rng, sizes)
    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    cfg_b = _cfg(scope="bucket")
    cfg_l = _cfg()
    acc_b, p_b, bufs_b, grams_b = _run_cycles(cfg_b, params, deltas, 8,
                                              quantize=True)
    acc_l, p_l, bufs_l, grams_l = _run_cycles(cfg_l, params, deltas, 8,
                                              quantize=True)

    # bucket-scope save -> leaf-scope restore: the leaf-wise Grams on disk
    # must equal the leaf-scope run's (buffers are scope-independent and
    # the integer sums are exact)
    st_b = TrainState(p_b, None, jnp.asarray(8, jnp.int32), bufs_b, grams_b)
    save_checkpoint(tmp_path / "bucket", acc_b.state_leafwise(st_b), 8)
    acc_t = DMDAccelerator(cfg_l)
    bufs_t = acc_t.init(params)
    st_t = TrainState(params, None, jnp.asarray(0, jnp.int32), bufs_t,
                      acc_t.init_grams(bufs_t))
    back = restore_checkpoint(tmp_path / "bucket",
                              acc_t.state_leafwise(st_t))
    packed = acc_t.state_arenaize(back)
    for key in grams_l["__arena__"]:
        np.testing.assert_array_equal(
            np.asarray(packed.dmd_gram["__arena__"][key]),
            np.asarray(grams_l["__arena__"][key]), err_msg=key)
        np.testing.assert_array_equal(
            np.asarray(packed.dmd_buffers["__arena__"][key]),
            np.asarray(bufs_l["__arena__"][key]), err_msg=key)

    # leaf-scope save -> bucket-scope restore: arenaize segment-sums the
    # leaf-wise Grams into the (1, m, m) bucket stack
    st_l = TrainState(p_l, None, jnp.asarray(8, jnp.int32), bufs_l, grams_l)
    save_checkpoint(tmp_path / "leaf", acc_l.state_leafwise(st_l), 8)
    acc_r = DMDAccelerator(cfg_b)
    bufs_r = acc_r.init(params)
    st_r = TrainState(params, None, jnp.asarray(0, jnp.int32), bufs_r,
                      acc_r.init_grams(bufs_r))
    rback = restore_checkpoint(tmp_path / "leaf",
                               acc_r.state_leafwise(st_r))
    rpacked = acc_r.state_arenaize(rback)
    for key in grams_b["__arena__"]:
        g = rpacked.dmd_gram["__arena__"][key]
        assert g.shape[0] == 1, key
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(grams_b["__arena__"][key]),
            err_msg=key)


def test_bucket_scope_sys_sharded_bucket_stays_per_system():
    """The carve-out: a system-sharded bucket (sys_axes nonempty) keeps
    per-system operators even under scope="bucket" — collapsing it would
    need a cross-shard psum over the sys axes the kernels never emit."""
    import numpy as _np
    from repro.distributed.sharding import set_rule_overrides

    class _FakeMesh:
        axis_names = ("data", "model")
        devices = _np.empty((2, 4))

    set_rule_overrides([(r"stacked", ("fsdp", None, "tp"))])
    try:
        cfg = _cfg(scope="bucket")
        params = {"stacked": jnp.ones((4, 64, 128)),
                  "w": jnp.ones((64, 128))}
        acc = DMDAccelerator(cfg, mesh=_FakeMesh(),
                             stack_dims={"stacked": 1, "w": 0})
        table = acc.arena_for(params)
        sys_b = [b for b in table.values() if b.sys_axes]
        lane_b = [b for b in table.values() if not b.sys_axes]
        assert sys_b and lane_b
        for b in sys_b:
            assert not b.bucket_scoped("bucket")
            assert b.gram_lead("bucket") == b.n_sys_global
            np.testing.assert_array_equal(b.scope_block_sys("bucket"),
                                          b.block_sys())
        for b in lane_b:
            assert b.bucket_scoped("bucket")
            assert b.gram_lead("bucket") == 1
    finally:
        set_rule_overrides(None)


# ---------------------------------------------------------------------------
# Eligibility (ISSUE 7 tentpole): mean-anchor and sharded-stack buckets
# ---------------------------------------------------------------------------

def _audit_arena(cfg, acc, params, mesh=None):
    """Run the shared arena-layout audit pass over one accelerator build."""
    import types
    from repro.audit.passes import arena_layout
    from repro.audit.targets import adhoc_context
    ctx = adhoc_context("test-arena", types.SimpleNamespace(dmd=cfg), {},
                        mesh=mesh, plans=acc.plans_for(params),
                        arena=acc.arena_for(params))
    violations, info = arena_layout(ctx)
    return [v for v in violations if v.severity == "error"], info


def test_mean_anchor_leaves_pack_and_match_perleaf():
    """anchor=mean leaves PACK (ISSUE 7): the full-recompute arena Gram
    kernel fuses the mean subtraction, so there is no per-leaf carve-out
    anymore (streaming stays structurally off — the anchor moves every
    record). The packed route must agree bit-exactly with the per-leaf
    route on integer trajectories, and the layout audit stays clean."""
    from repro.core import leafplan
    from repro.core.arena import arena_eligible, arena_paths

    cfg = _cfg(anchor="mean")
    rng = np.random.default_rng(17)
    sizes = {"w": (16, 16), "b": (48,)}
    params = _int_params(rng, sizes)
    deltas = {k: jnp.asarray(rng.integers(-2, 3, size=s), jnp.float32)
              for k, s in sizes.items()}
    acc = DMDAccelerator(cfg)
    assert not acc.streaming                     # mean: no one-pass row
    table = acc.arena_for(params)
    assert arena_paths(table) == frozenset({"/w", "/b"})
    for p in leafplan.plan_entries(acc.plans_for(params)):
        assert arena_eligible(p, cfg, None), p.path
    errors, info = _audit_arena(cfg, acc, params)
    assert errors == [], errors
    assert info["n_packed"] == 2 and info["n_leaves"] == 2

    acc_a, p_arena, _, _ = _run_cycles(cfg, params, deltas, 9)
    _, p_leaf, _, _ = _run_cycles(
        dataclasses.replace(cfg, arena=False), params, deltas, 9)
    for k in sizes:
        np.testing.assert_array_equal(np.asarray(p_arena[k]),
                                      np.asarray(p_leaf[k]), err_msg=k)


def test_sharded_stack_leaf_gets_single_segment_sys_bucket():
    """A leaf whose LEADING stack axis is device-sharded packs into its
    own single-segment bucket (ISSUE 7): each device owns whole systems
    (sys_axes), the Gram stack stays sharded over them, and shard-local
    accounting (n_sys vs n_sys_global) is consistent. A NON-leading
    sharded stack axis stays excluded (shard-major packing would
    interleave the global system order). The mesh here is structural
    (axis names + sizes are all the layout code reads)."""
    import numpy as _np
    from repro.core import leafplan
    from repro.core.arena import arena_eligible, arena_paths
    from repro.distributed.sharding import set_rule_overrides

    class _FakeMesh:
        axis_names = ("data", "model")
        devices = _np.empty((2, 4))

    mesh = _FakeMesh()
    set_rule_overrides([(r"stacked", ("fsdp", None, "tp"))])
    try:
        cfg = _cfg()
        params = {"stacked": jnp.ones((4, 64, 128)),
                  "w": jnp.ones((64, 128))}
        acc = DMDAccelerator(cfg, mesh=mesh,
                             stack_dims={"stacked": 1, "w": 0})
        table = acc.arena_for(params)
        packed = arena_paths(table)
        assert "/stacked" in packed              # leading-dim shard packs
        assert "/w" in packed
        plans = acc.plans_for(params)
        by_path = {p.path: p for p in leafplan.plan_entries(plans)}
        st = by_path["/stacked"]
        assert arena_eligible(st, cfg, mesh)
        assert st.param_spec[0] is not None      # the stack axis IS sharded
        sys_buckets = [b for b in table.values() if b.sys_axes]
        assert len(sys_buckets) == 1
        (b,) = sys_buckets
        assert len(b.segments) == 1              # own single-segment bucket
        assert b.sys_axes == ("data",) and b.sys_factor == 2
        assert b.segments[0].n_sys == 2          # shard-LOCAL systems (4/2)
        assert b.n_sys_global == 4
        assert b.gram_spec() == __import__("jax").sharding.PartitionSpec(
            "data", None, None)
        errors, info = _audit_arena(cfg, acc, params, mesh=mesh)
        assert errors == [], errors
        assert info["n_packed"] == 2
    finally:
        set_rule_overrides(None)

    # non-leading sharded stack dim: still excluded
    set_rule_overrides([(r"deep", (None, "fsdp", None, "tp"))])
    try:
        cfg = _cfg()
        params = {"deep": jnp.ones((3, 4, 16, 128))}
        acc = DMDAccelerator(cfg, mesh=mesh, stack_dims={"deep": 2})
        assert acc.arena_for(params) == {}
        (pl,) = leafplan.plan_entries(acc.plans_for(params))
        assert not arena_eligible(pl, cfg, mesh)
    finally:
        set_rule_overrides(None)
