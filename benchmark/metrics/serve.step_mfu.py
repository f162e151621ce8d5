"""The token step's share of the chip's bf16 peak: positions advanced (prompt
and answer) x model FLOPs per position at the mean live context, over the
window's wall clock."""
from benchmark.lib import arith


def read(r):
    c = r["counters"]
    if not c["positions"]:
        return None
    s = c["sizes"]
    per_pos = arith.decoder_flops_per_position(
        s["hidden"], s["ffn"], s["num_layers"], s["vocab_size"],
        c["mean_context"])
    return 100.0 * c["positions"] * per_pos / c["seconds"] \
        / r["peak"]["bf16_flops_per_s"]
