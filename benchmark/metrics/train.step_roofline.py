"""The step executable against its roofline. Compute-bound: its least time is
one chip's share of the step's model FLOPs over the bf16 peak (16x512 rows a
chip move 1.6e13 FLOPs against some 10 GB of traffic: 83 ms against 12 ms)."""
from benchmark.lib import readers


def read(r):
    c, dev_s = r["counters"], readers.step_launch_seconds(r)
    if dev_s is None:
        return None
    least = c["flops_per_step"] / c["chips"] / r["peak"]["bf16_flops_per_s"]
    return 100.0 * least / dev_s
