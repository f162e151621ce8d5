"""The token step against its roofline, whatever implements it. Memory-bound:
the least time is the weights once, the live contexts' K and V once and one
row written per slot and layer over the HBM bandwidth (about 0.5 GB: 0.6 ms;
the FLOPs of 64 positions would take 0.1 ms)."""
from benchmark.lib import arith, readers


def read(r):
    c, dev_s = r["counters"], readers.step_launch_seconds(r)
    if dev_s is None or not c["live_slots"]:
        return None
    s = c["sizes"]
    args = (s["hidden"], s["ffn"], s["num_layers"], s["vocab_size"])
    live = int(round(c["live_slots"]))
    flops = live * arith.decoder_flops_per_position(*args, c["mean_context"])
    nbytes = arith.decoder_step_bytes(*args, [c["mean_context"]] * live,
                                      c["w_itemsize"], c["kv_itemsize"])
    least, _ = arith.roofline_seconds(flops, nbytes, r["peak"])
    return 100.0 * least / dev_s
