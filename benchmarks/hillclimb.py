import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimbing driver: named (hypothesis -> change) experiments per
cell, measured with the same unit-decomposition roofline as the baseline.

    PYTHONPATH=src:. python -m benchmarks.hillclimb --cell llama4_train \
        --variant act_stationary

Each variant is a config transform; results land in results/hillclimb/ and
EXPERIMENTS.md §Perf records hypothesis / predicted / measured / verdict.
"""
import argparse
import dataclasses
import json
from pathlib import Path


def _llama4_act_stationary(acfg):
    """H1: llama4 train is collective-bound by FSDP re-gathering 386B expert
    weights every microbatch (measured ~2 GB/layer/microbatch). Keep expert
    weights resident (FSDP their ffn dim) and move the ~50 MB of dispatched
    activations instead. Predicted: MoE-layer collective bytes drop ~20-40x;
    total t_collective drops ~5-10x (dense layers + grads unchanged)."""
    from repro.distributed.sharding import set_rule_overrides
    set_rule_overrides([
        (r"experts_(gate|in)$", ("tp", None, "fsdp")),
        (r"experts_out$", ("tp", "fsdp", None)),
    ])
    moe = dataclasses.replace(acfg.model.moe, weight_stationary=False)
    return dataclasses.replace(
        acfg, model=dataclasses.replace(acfg.model, moe=moe))


def _llama4_act_stationary_ga8(acfg):
    """H1b: on top of H1, halve grad_accum 16->8: the remaining param-part
    collectives (dense FSDP gathers) scale with ga; activation memory
    doubles (fits: peak was 3.8 GiB at ga=16)."""
    acfg = _llama4_act_stationary(acfg)
    return dataclasses.replace(
        acfg, parallel=dataclasses.replace(acfg.parallel, grad_accum=8))


def _qwen3_dmd_bf16_math(acfg):
    """H3: qwen3 is the MoE-DMD showcase (DMD over ALL params). The jump's
    cost is bandwidth: gram+combine read the m x params buffer in fp32
    (astype materializes a 2x copy of bf16 buffers). Keep the streaming math
    in bf16 with fp32 accumulation (preferred_element_type): predicted DMD
    bytes ~/2, flops unchanged."""
    return dataclasses.replace(
        acfg, dmd=dataclasses.replace(acfg.dmd, gram_upcast=False))


def _ga_half(acfg):
    ga = max(acfg.parallel.grad_accum // 2, 1)
    return dataclasses.replace(
        acfg, parallel=dataclasses.replace(acfg.parallel, grad_accum=ga))


CELLS = {
    "llama4_train": ("llama4-maverick-400b-a17b", "train_4k"),
    "minicpm_train": ("minicpm-2b", "train_4k"),
    "qwen3_train": ("qwen3-moe-30b-a3b", "train_4k"),
    "qwen2vl_train": ("qwen2-vl-7b", "train_4k"),
    "whisper_train": ("whisper-base", "train_4k"),
    "minicpm_prefill": ("minicpm-2b", "prefill_32k"),
}

VARIANTS = {
    "baseline": lambda a: a,
    "act_stationary": _llama4_act_stationary,
    "act_stationary_ga8": _llama4_act_stationary_ga8,
    "ga_half": _ga_half,
    "dmd_bf16_math": _qwen3_dmd_bf16_math,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=sorted(CELLS))
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--out", default="results/hillclimb")
    args = ap.parse_args()
    from benchmarks.roofline import analyze_cell
    arch, shape = CELLS[args.cell]
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    rec = analyze_cell(arch, shape, "single", None,
                       overrides=VARIANTS[args.variant])
    rec["variant"] = args.variant
    (out / f"{args.cell}__{args.variant}.json").write_text(
        json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
