"""The token step of a latent-attention LM on a residual path of several
streams, as a share of the chip's bf16 peak: positions advanced (prompt and
answer) x model FLOPs a position, over the window's wall clock. FLOPs by the
least work (`lib/arith_hc.py:flops_per_position`): `serve.lm_step_mfu`'s terms
at this model's sizes (the absorbed latent attention at the mean live context,
the dense MLPs, routers and shared experts, the head's slice, the experts'
products for the held choices a position that the program counted over the
window), and every sublayer's residual path: the maps' products, the read-out
and the mix."""
from benchmark.lib import arith_hc


def read(r):
    c = r["counters"]
    moe = c.get("moe_window")
    if not c.get("positions") or not moe:
        return None
    per_pos = arith_hc.flops_per_position(
        r["config"]["published"], r["config"]["model"], c["mean_context"],
        moe["held_choices_per_position"])
    return 100.0 * c["positions"] * per_pos / c["seconds"] \
        / r["peak"]["bf16_flops_per_s"]
