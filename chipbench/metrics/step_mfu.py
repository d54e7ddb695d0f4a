"""Model FLOP utilization of the whole window: the forward and backward
matmul FLOPs the model needs per step (bench/counts.py, no recomputation,
no gathers), times the steps, over the traced window, the chips and the
chip's bf16 peak. Every step, jump and idle gap of the window is in the
time."""


def read(view, record, peak):
    if not record.get("steps") or not peak or view.window_s <= 0:
        return None
    flops = record["flops_per_step"] * record["steps"]
    return 100.0 * flops / (view.window_s * record["chips"]
                            * peak["flops_bf16"])
