"""Continuous-batching engine invariants (ISSUE 10 tentpole).

The load-bearing claims of repro.serve.engine, each pinned here:

  * bucketed padded prompts are BIT-EXACT against the reference
    serve_fns prefill+decode loop (exact-length caches, per-request),
    including batched admission with filler rows into a live slot table;
  * one decode dispatch per generated token and ZERO host syncs between
    dispatches — sampling (argmax / top-k) lives inside the jitted
    decode program (the seed drivers' per-token ``jnp.argmax`` host
    round-trip is the defect this pins against);
  * steady state never recompiles: after warming every bucket the
    program registry is frozen (mark_steady + steady_compiles == 0);
  * hot-swap: serving a swapped-in version is bit-exact with a
    cold-started server on those weights, in-flight requests adopt per
    policy ("step" immediately, "drain" finishes on the start version);
  * unsupported cache families fail loudly at construction.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.launch.serve import serve_fns
from repro.models.transformer import LanguageModel
from repro.serve import ServeConfig, ServeEngine

PROMPTS = [[1, 2, 3], [5, 6, 7, 8, 9], [2, 4], [7] * 8, [3, 1, 4, 1, 5, 9]]


@functools.lru_cache(maxsize=None)
def _model_and_params():
    acfg = get_config("tinyllama-1.1b")
    mc = reduced(acfg.model, n_layers=2, d_model=32, d_ff=64, vocab_size=128,
                 n_heads=2, n_kv_heads=1, head_dim=16)
    # scan_layers=False is the serving build (launch/serve.py)
    model = LanguageModel(mc, head_tp=False, chunk_k=16, scan_layers=False)
    return model, model.init(jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _reference_fns():
    model, _ = _model_and_params()
    return serve_fns(model, donate=False)


def _engine(**kw):
    model, params = _model_and_params()
    kw.setdefault("n_slots", 4)
    kw.setdefault("prompt_buckets", (4, 8))
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("max_new_tokens", 5)
    return ServeEngine(model, params, ServeConfig(**kw))


def _reference_greedy(prompt, n_new, params=None):
    """The pre-engine serving loop: exact-length prefill, then the
    (host-side) greedy argmax decode — the correctness oracle."""
    model, p0 = _model_and_params()
    fns = _reference_fns()
    params = p0 if params is None else params
    caches = model.init_cache(1, len(prompt) + n_new)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, caches = fns["prefill"](params, {"tokens": toks}, caches)
    out = []
    for _ in range(n_new):
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out.append(int(tok[0, 0]))
        logits, caches = fns["decode_step"](params, {"tokens": tok}, caches)
    return out


def test_engine_matches_reference_greedy():
    """Mixed prompt lengths across both buckets, concurrent slots, padded
    prefill: every request's tokens equal the exact-length reference."""
    eng = _engine()
    for p in PROMPTS:
        eng.submit(p)
    res = {r.uid: r for r in eng.run_until_drained()}
    assert len(res) == len(PROMPTS)
    for i, p in enumerate(PROMPTS):
        assert res[i].tokens == _reference_greedy(p, 5), (i, p)
        assert res[i].prompt_len == len(p)
    assert eng.stats["dropped"] == 0


def test_batched_admission_preserves_live_slots():
    """A batch-bucketed insert scatters per-request rows; filler rows
    carry an out-of-range sentinel slot and must not clobber anything —
    neither free slots nor mid-flight requests admitted earlier."""
    eng = _engine(n_slots=8, batch_buckets=(1, 2, 4))
    for p in ([1, 2, 3], [2, 4], [3, 3, 3, 1]):      # one bucket, 3 reqs
        eng.submit(p)
    eng.step()                                        # Bb=4 + filler row
    assert "insert_b4" in eng._programs
    eng.submit([9, 9, 9])                             # admit mid-flight
    res = {r.uid: r.tokens for r in eng.run_until_drained()}
    for i, p in enumerate([[1, 2, 3], [2, 4], [3, 3, 3, 1], [9, 9, 9]]):
        assert res[i] == _reference_greedy(p, 5), (i, p)


def test_one_dispatch_per_token_and_in_jit_sampling():
    """The dispatch-count pin for the per-token host-sync fix: N generated
    tokens cost exactly N decode dispatches of ONE compiled program, and
    the sampling argmax is inside that program's jaxpr — not host code
    between dispatches."""
    eng = _engine()
    eng.submit([1, 2, 3], max_new_tokens=5)
    eng.run_until_drained()
    assert eng.stats["decode_dispatches"] == 5
    assert eng.stats["prefill_dispatches"] == 1
    decode_programs = [n for n in eng._programs if n.startswith("decode")]
    assert decode_programs == ["decode"]
    assert "argmax" in str(eng._programs["decode"].jaxpr)

    # concurrent slots share dispatches: 2 more requests, still one
    # dispatch per decode STEP (not per request-token)
    eng.submit([4, 5]); eng.submit([6, 7, 8])
    eng.run_until_drained()
    assert eng.stats["decode_dispatches"] == 10
    assert eng.stats["tokens_emitted"] == 15


def test_steady_state_never_recompiles():
    eng = _engine()
    # warmup: touch both prompt buckets at batch buckets 1 and 2
    for wave in ([3, 3], [7, 7], [2], [5]):
        for n in wave:
            eng.submit(list(range(1, n + 1)))
        eng.run_until_drained()
    eng.mark_steady()
    warm = eng.n_programs
    for wave in ([4, 4], [8, 8], [1], [6]):           # new in-bucket lens
        for n in wave:
            eng.submit(list(range(1, n + 1)))
        eng.run_until_drained()
    assert eng.stats["steady_compiles"] == 0
    assert eng.n_programs == warm <= eng.max_programs


def test_topk_sampling_is_deterministic_and_in_jit():
    kw = dict(sampling="topk", top_k=4, seed=11)
    a, b = _engine(**kw), _engine(**kw)
    for e in (a, b):
        e.submit([1, 2, 3]); e.submit([4, 5])
    ra = {r.uid: r.tokens for r in a.run_until_drained()}
    rb = {r.uid: r.tokens for r in b.run_until_drained()}
    assert ra == rb
    assert all(len(t) == 5 for t in ra.values())
    assert a.stats["decode_dispatches"] == 5


def test_swap_is_bit_exact_vs_cold_start():
    """The swapped-in version serves tokens AND final logits identical to
    a server cold-started on those weights (ISSUE 10 satellite)."""
    model, params = _model_and_params()
    bumped = jax.tree_util.tree_map(lambda l: l * 1.5, params)
    hot = _engine()
    hot.submit([1, 2, 3])
    hot.run_until_drained()                       # serve v0 first
    assert hot.swap_weights(bumped, version=7) == 7
    assert hot.version == 7
    cold = ServeEngine(model, bumped, ServeConfig(
        n_slots=4, prompt_buckets=(4, 8), batch_buckets=(1, 2),
        max_new_tokens=5))
    for p in PROMPTS[:3]:
        hot.submit(p); cold.submit(p)
    rh = {r.uid: r for r in hot.run_until_drained()}
    rc = {r.uid: r for r in cold.run_until_drained()}
    # uids differ (hot served one request before), align by submit order
    for uh, uc in zip(sorted(rh), sorted(rc)):
        assert rh[uh].tokens == rc[uc].tokens
        np.testing.assert_array_equal(rh[uh].last_logits,
                                      rc[uc].last_logits)
        assert (rh[uh].version_start, rh[uh].version_end) == (7, 7)
    # the swap itself never compiles: same registry before and after
    assert hot.stats["compiles"] == cold.stats["compiles"]
    assert hot.stats["dropped"] == 0


def test_step_adopt_swaps_in_flight_requests():
    model, params = _model_and_params()
    bumped = jax.tree_util.tree_map(lambda l: l * 1.5, params)
    eng = _engine(adopt="step", max_new_tokens=6)
    eng.submit([1, 2, 3])
    eng.step(); eng.step()                        # 2 of 6 tokens on v0
    eng.swap_weights(bumped, version=3)
    (res,) = eng.run_until_drained()
    assert (res.version_start, res.version_end) == (0, 3)
    assert eng.stats["swaps"] == 1


def test_drain_adopt_holds_until_table_empties():
    model, params = _model_and_params()
    bumped = jax.tree_util.tree_map(lambda l: l * 1.5, params)
    eng = _engine(adopt="drain", max_new_tokens=4)
    eng.submit([1, 2, 3])
    eng.step()
    eng.swap_weights(bumped, version=3)
    assert eng.version == 0                       # active slot: no adopt
    eng.submit([4, 5])                            # held while pending
    res = {r.uid: r for r in eng.run_until_drained()}
    assert (res[0].version_start, res[0].version_end) == (0, 0)
    assert (res[1].version_start, res[1].version_end) == (3, 3)
    assert eng.version == 3
    # the held request was NOT dropped, just deferred
    assert res[1].tokens == _reference_greedy([4, 5], 4, params=bumped)


def test_submit_validation():
    eng = _engine()
    with pytest.raises(ValueError, match="exceeds the largest"):
        eng.submit(list(range(20)))
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], max_new_tokens=99)
    with pytest.raises(ValueError, match="stale publish"):
        _, params = _model_and_params()
        eng.swap_weights(params, version=0)


def test_unsupported_families_fail_loudly():
    _, params = _model_and_params()
    acfg = get_config("gemma3-27b")               # ring caches (dense_local)
    mc = reduced(acfg.model, n_layers=2, d_model=32, d_ff=64, vocab_size=128,
                 n_heads=2, n_kv_heads=1, head_dim=16)
    ring = LanguageModel(mc, head_tp=False, chunk_k=16, scan_layers=False)
    with pytest.raises(NotImplementedError, match="segment kinds"):
        ServeEngine(ring, ring.init(jax.random.PRNGKey(0)), ServeConfig())

    model, params = _model_and_params()
    scanned = LanguageModel(model.cfg, head_tp=False, chunk_k=16,
                            scan_layers=True)
    with pytest.raises(ValueError, match="scan_layers"):
        ServeEngine(scanned, params, ServeConfig())


def test_serve_state_specs_cover_the_slot_table():
    """launch/inputs.serve_state_specs: slot axis over the batch axes,
    kv-head TP preserved, PRNG key and scalars replicated."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.inputs import serve_state_specs
    from repro.launch.mesh import make_mesh

    eng = _engine(n_slots=4)
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = serve_state_specs(eng._dstate, mesh)
    flat = {jax.tree_util.keystr(kp): s
            for kp, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert flat["['key']"] == P()
    # entries compare as PartitionSpecs: JAX stores a one-axis tuple entry
    # ("data",) as the bare axis name
    assert P(flat["['out_buf']"][0]) == P(("data",))
    # cache k/v leaves: slot axis first, nothing on the garbage dims
    cache_specs = [s for p, s in flat.items() if "caches" in p]
    assert cache_specs, flat.keys()
    for s in cache_specs:
        assert P(s[0]) in (P(("data",)), P(None))
    # same structure as the decode state: shardings_of can map it 1:1
    jax.tree_util.tree_map(lambda a, b: None, specs, eng._dstate)