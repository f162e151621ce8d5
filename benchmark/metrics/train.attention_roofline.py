"""The attention kernels against their roofline. Compute-bound: the least time
is the score and value products of every layer, forward and backward, over
the pairs the masks leave (`arith_lm.attention_flops_per_step`: causal pairs,
in-window pairs on a sliding layer) over the bf16 peak, against the device
time a step spends in the `splash_*` kernels. A kernel that masks a sliding
layer's whole product does some sixteen times that layer's least work, and
the share shows it."""
from benchmark.lib import arith_lm, readers_lm


def read(r):
    dev_s = readers_lm.kernel_seconds_per_step(r, ("splash_",))
    if dev_s is None:
        return None
    tr, cfg = r["cell"]["traffic"], r["config"]
    flops = arith_lm.attention_flops_per_step(
        cfg["published"], cfg["model"], tr["rows"], tr["seq"])
    return 100.0 * flops / r["counters"]["chips"] \
        / r["peak"]["bf16_flops_per_s"] / dev_s
