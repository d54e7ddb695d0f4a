"""The streaming Gram row kernel's share of its roofline: the least time
the chip needs to read m snapshot rows of every DMD-managed lane at the
snapshot dtype (bench/counts.py), once per recorded step, over the summed
device time of the kernel's executions in the window."""
from bench.counts import gram_row_cost, least_time

KERNEL = "gram_row_pallas"


def read(view, record, peak):
    secs, n = view.op_time(lambda name: KERNEL in name)
    if n == 0 or not record.get("record_steps") or not peak:
        return None
    flops, nbytes = gram_row_cost(record["m"], record["dmd_lanes"],
                                  record["snapshot_itemsize"])
    return 100.0 * record["record_steps"] * least_time(flops, nbytes, peak) \
        / secs
