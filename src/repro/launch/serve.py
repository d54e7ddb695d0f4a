"""Serving launcher: a thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced [--requests 12] [--new-tokens 16] [--sampling topk] \
        [--swap-every 8]

Submits a mixed-length synthetic request stream to ``repro.serve``'s
``ServeEngine`` (DESIGN.md §10): padded prompt/batch buckets — one
compiled program per bucket, zero steady-state recompiles — slot-based
decode over donated KV/decode state with in-jit sampling (no host sync
per token), and optional live weight hot-swaps mid-stream
(``--swap-every``) to demo the version-stamped double-buffered publish
path. Without --reduced the full config runs on a (data, model) mesh over
every device JAX finds, with the slot table sharded per
``launch/inputs.serve_state_specs``.

The per-token decode loop of the seed-era launcher (an
``argmax(logits[:, -1])`` host round-trip between every pair of
dispatches) lives on only inside the engine's jitted decode program;
``serve_fns`` below stays as the audited two-program serving contract
the engine's decode donation mirrors (tests/test_serve_audit.py).
"""
import argparse
import time


def serve_fns(model, donate=True):
    """The serving programs, jitted the way the engine runs them: the KV
    caches (positional arg 2 of both prefill and decode_step) are donated
    so the per-token cache update is in-place — a decode step that COPIES
    its caches doubles the serving HBM footprint and shows up in the
    compiled HLO as cache-shaped copy ops. tests/test_serve_audit.py
    routes both programs through the shared donation/collective passes
    (``python -m repro.audit`` machinery, DESIGN.md §8); ``donate=False``
    exists only so that audit can prove it bites."""
    import jax
    dn = (2,) if donate else ()
    return {"prefill": jax.jit(model.prefill, donate_argnums=dn),
            "decode_step": jax.jit(model.decode_step, donate_argnums=dn)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--sampling", choices=("greedy", "topk"),
                    default="greedy")
    ap.add_argument("--swap-every", type=int, default=0,
                    help="hot-swap perturbed weights every N engine steps "
                         "(0 = frozen server)")
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.distributed.sharding import mesh_context
    from repro.models.transformer import LanguageModel
    from repro.serve import ServeConfig, ServeEngine

    acfg = get_config(args.arch)
    mc = reduced(acfg.model) if args.reduced else acfg.model
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh_cm = None
    if not args.reduced:
        from repro.launch.mesh import make_mesh_for_devices
        mesh_cm = mesh_context(make_mesh_for_devices(
            len(jax.devices()), args.model_parallel))

    def run():
        # scan_layers=False: serving unrolls the layer stack so XLA updates
        # the donated caches fully in place — a lax.scan over layers carries
        # the stacked cache as (xs, stacked-ys) and double-buffers it by
        # construction, which both costs a cache-sized copy per token and
        # would trip the serve donation audit (tests/test_serve_audit.py).
        model = LanguageModel(mc, head_tp=not args.reduced, chunk_k=64,
                              scan_layers=False)
        params = model.init(jax.random.PRNGKey(0))
        cfg = ServeConfig(n_slots=args.slots, prompt_buckets=(16, 64),
                          batch_buckets=(1, 4), sampling=args.sampling,
                          max_new_tokens=args.new_tokens,
                          adopt="step")
        engine = ServeEngine(model, params, cfg)
        rng = np.random.default_rng(0)
        for _ in range(args.requests):
            n = int(rng.integers(4, cfg.prompt_buckets[-1] + 1))
            engine.submit(rng.integers(
                1, mc.vocab_size, size=(n,)).tolist())

        swap_src = jax.tree_util.tree_map(lambda l: l * 1.001, params)
        done, steps = [], 0
        t0 = time.time()
        while engine.queue_len or engine.active_slots:
            done.extend(engine.step())
            steps += 1
            if args.swap_every and steps % args.swap_every == 0:
                engine.swap_weights(swap_src)
        engine.sync()
        wall = time.time() - t0
        s = engine.stats
        print(f"{len(done)} requests, {s['tokens_emitted']} tokens in "
              f"{wall*1e3:.0f}ms -> {s['tokens_emitted']/max(wall,1e-9):.0f}"
              f" tok/s | swaps={s['swaps']} dropped={s['dropped']} "
              f"programs={engine.n_programs}/{engine.max_programs}")
        first = min(done, key=lambda r: r.uid)
        print(f"ids[{first.uid}] v{first.version_start}->"
              f"{first.version_end}: {first.tokens}")

    if mesh_cm is not None:
        with mesh_cm:
            run()
    else:
        run()


if __name__ == "__main__":
    main()
