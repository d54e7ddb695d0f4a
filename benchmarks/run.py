"""Benchmark harness: one function per paper table/figure + kernel timings.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--out DIR]

Prints ``name,...`` CSV rows AND writes one ``BENCH_<suite>.json`` per suite
(the perf-trajectory files CI archives run-over-run): each file carries the
raw rows plus the wall time so regressions are diffable. The roofline table
(per arch x shape) is a separate, much heavier pass: ``python -m
benchmarks.roofline`` (it needs the 512-device dry-run environment).
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, "src")

# Give the sharded_gram suite a real multi-device mesh on CPU hosts (set
# before jax initializes; harmless for the single-device suites).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp


def bench_kernels() -> list:
    """Kernel wall times (interpret-mode on CPU: correctness path; the
    numbers are the jnp-oracle equivalents, useful as relative baselines)."""
    from repro.kernels import ops, ref
    rows = ["kernel,name,us_per_call,derived"]
    rng = np.random.default_rng(0)
    m, n = 14, 1_000_000
    S = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(m,)), jnp.float32)

    def timeit(f, *a, reps=5):
        out = f(*a)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(reps):
            out = f(*a)
        jax.block_until_ready(out)
        return (time.time() - t0) / reps * 1e6

    t_ref = timeit(jax.jit(ref.gram_ref), S)
    rows.append(f"kernel,gram_ref_jnp,{t_ref:.0f},m={m} n={n} "
                f"{2*m*m*n/t_ref*1e-3/1e9:.1f}GFLOP/s")
    t_c = timeit(jax.jit(ref.combine_ref), S, c)
    rows.append(f"kernel,combine_ref_jnp,{t_c:.0f},bw~"
                f"{4*m*n/t_c*1e-3/1e9:.1f}GB/s")
    q = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    t_f = timeit(jax.jit(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=True)), q, k, k)
    rows.append(f"kernel,flash_ref_jnp,{t_f:.0f},B1 S512 H4 d64")
    return rows


def losing_rows(rows: list) -> list:
    """Rows that report a LOSING direction (ISSUE 9): suites mark a metric
    that regressed vs its baseline with an explicit ``_LOSES`` token (e.g.
    fig4's signed-delta final rows). Surfacing them here keeps a regression
    from hiding inside a wall of higher-is-better ratios."""
    return [r for r in rows if "_LOSES" in r]


def write_suite(out_dir: Path, suite: str, rows: list, wall_s: float,
                quick: bool) -> None:
    path = out_dir / f"BENCH_{suite}.json"
    path.write_text(json.dumps({
        "suite": suite,
        "rows": rows,
        "wall_s": round(wall_s, 2),
        "quick": quick,
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
    }, indent=1))
    print(f"# wrote {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=".",
                    help="directory for the BENCH_<suite>.json files")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.paper_benches import (arena_bench, bucket_dmd,
                                          controller, fig3_sensitivity,
                                          fig4_curves, sec3_overhead,
                                          sharded_gram, staggered_jump,
                                          streaming_gram)
    from benchmarks.serving import serve_bench
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    suites = [
        ("arena", (lambda: arena_bench(n_mlp_layers=12, width=128, reps=5))
         if args.quick else arena_bench),
        ("bucket_dmd", (lambda: bucket_dmd(n_mlp_layers=12, width=128,
                                           reps=5, fig_steps=300,
                                           lm_steps=40))
         if args.quick else bucket_dmd),
        ("sec3_overhead", sec3_overhead),
        ("streaming_gram", lambda: streaming_gram(
            n=1_000_000 if args.quick else 4_000_000)),
        ("sharded_gram", sharded_gram),
        ("staggered_jump", (lambda: staggered_jump(
            sizes=(6, 400, 400, 400), reps=5)) if args.quick
         else staggered_jump),
        ("controller", (lambda: controller(
            steps=300, sizes=(6, 40, 80, 200))) if args.quick
         else controller),
        ("serve", (lambda: serve_bench(n_requests=12, new_tokens=12))
         if args.quick else serve_bench),
        ("kernels", bench_kernels),
        ("fig3", (lambda: fig3_sensitivity(ms=(6, 14), ss=(10, 55),
                                           steps=300))
         if args.quick else fig3_sensitivity),
        ("fig4", (lambda: fig4_curves(steps=300))
         if args.quick else fig4_curves),
    ]

    t_total = time.time()
    all_rows = []
    for suite, fn in suites:
        t0 = time.time()
        rows = fn()
        write_suite(out_dir, suite, rows, time.time() - t0, args.quick)
        for r in losing_rows(rows):
            print(f"# LOSING DIRECTION [{suite}]: {r}")
        all_rows += rows
    print("\n".join(all_rows))
    losers = losing_rows(all_rows)
    if losers:
        print(f"\n# {len(losers)} metric(s) in a LOSING direction — "
              "see rows above")
    print(f"\n# total bench wall: {time.time() - t_total:.0f}s")


if __name__ == "__main__":
    main()
