"""The experts' grouped products in a token step against their roofline: the
larger of the held choices' FLOPs (three products of 2 x hidden x expert width
each) over the bf16 peak and the touched experts' weights over the HBM
bandwidth, against the device time a launch spends in the `ragged-dot`
operations (`lib/readers_lm.py`). Both sides cover the traced part: the held
choices and the touched experts a launch are the program's counts there
(`counters.moe_traced`). At some four rows an expert the weights bound it."""
from benchmark.lib import arith_mla, readers_lm


def read(r):
    moe = r["counters"].get("moe_traced")
    dev_s = readers_lm.kernel_seconds_per_step(r, ("ragged-dot",))
    if not moe or dev_s is None:
        return None
    return 100.0 * arith_mla.expert_least_seconds(
        r["config"]["published"], moe["held_choices_per_step"],
        moe["touched_experts_per_step"], r["peak"],
        r["counters"]["w_itemsize"]) / dev_s
