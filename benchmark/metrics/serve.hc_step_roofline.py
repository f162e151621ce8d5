"""The token step of a latent-attention LM on a residual path of several
streams against its roofline, whatever implements it: the least time of a
launch (`lib/arith_hc.py:step_bytes`: the weights once, without the
embedding's unread rows and the held experts no choice fell on; the live
slots' latent rows once a layer; one row written a slot and layer; every
sublayer's float32 maps once; against the launch's FLOPs) over the launch's
device time in the traced part. The held choices and touched experts a launch
are the program's own counts over the traced part (`counters.moe_traced`)."""
from benchmark.lib import arith, arith_hc, readers


def read(r):
    c, dev_s = r["counters"], readers.step_launch_seconds(r)
    moe = c.get("moe_traced")
    if dev_s is None or not c.get("live_slots") or not moe:
        return None
    pub, model = r["config"]["published"], r["config"]["model"]
    live = int(round(c["live_slots"]))
    flops = live * arith_hc.flops_per_position(
        pub, model, c["mean_context"], moe["held_choices_per_step"] / live)
    nbytes = arith_hc.step_bytes(
        pub, model, live, c["mean_context"], moe["touched_experts_per_step"],
        c["w_itemsize"], c["kv_itemsize"])
    least, _ = arith.roofline_seconds(flops, nbytes, r["peak"])
    return 100.0 * least / dev_s
