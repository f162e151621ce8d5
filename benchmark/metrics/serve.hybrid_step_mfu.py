"""The hybrid state-space token step's share of the chip's bf16 peak: positions
advanced (prompt and answer) x model FLOPs a position, over the window's wall
clock: the share of the whole step. FLOPs by the least work
(`lib/arith_hybrid.py:flops_per_position`): every matrix once, the
convolution and the state's update of a Mamba-2 layer, the attention layer's
live rows at the mean live context, router, latent projections and shared
expert of an expert layer, the head's slice, and the experts' products for the
held choices a position that the program counted over the window
(`dl4j_moe_held_choices_total` over the rows `dl4j_moe_choices_total` says
were fed), not the even share."""
from benchmark.lib import arith_hybrid


def read(r):
    c = r["counters"]
    moe = c.get("moe_window")
    if not c.get("positions") or not moe:
        return None
    per_pos = arith_hybrid.flops_per_position(
        r["config"]["published"], r["config"]["model"], c["mean_context"],
        moe["held_choices_per_position"])
    return 100.0 * c["positions"] * per_pos / c["seconds"] \
        / r["peak"]["bf16_flops_per_s"]
