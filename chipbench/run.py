#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's files are found by name (see bench/harness.py). The run exits
nonzero and prints no result when JAX finds no TPU or fewer chips than the
cell asks for, or when the program is not in the checkout. Otherwise it
loads and warms up, measures for ``--seconds``, checks what the measured
path produced against a plain reference, and prints one JSON line as the
last line of standard output; the numbers compared, each with its limit,
are also the last lines of standard error.
"""
import argparse
import json
import sys
import time

T_START = time.perf_counter()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from bench import harness

    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START)
    except (harness.NoChip, FileNotFoundError, KeyError) as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 2
    for text in harness.checks_text(line):
        print(text, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
