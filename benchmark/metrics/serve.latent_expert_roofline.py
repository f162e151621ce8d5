"""The latent experts' products in a token step against their roofline: the
larger of the held choices' FLOPs (two products of 2 x latent x expert width
each) over the bf16 peak and the touched experts' weights over the HBM
bandwidth, against the device time a launch spends in the expert products,
whatever implements them. Both sides cover the traced part: the held choices
and the touched experts a launch are the program's counts there
(`counters.moe_traced`).

How the trace names them (found on a traced run, PR 34): at up to 256 slots the
products run dense, and the compiler makes a layer's two batched products, the
relu², the selection, the weighing and the sum over the held experts ONE
fusion that reads `up` and `down` and yields the weighted latent rows: `fusion
bf16[slots,latent]`, at the cell's sizes `fusion bf16[128,1024]`. The rows'
projection into the latent space before it yields the same shape and so goes
by the same name (0.006 ms beside 1.87 ms a layer): its time is counted and its
8 MB are not, so the share errs low by a third of a percent. Past 256 slots the
products are grouped (`ragged-dot-*`, the name `serve.expert_matmul_roofline`
reads): both names are summed, so the reader does not go silent when the form
changes."""
from benchmark.lib import arith_hybrid, readers_lm


def ops(config):
    return ("fusion bf16[%d,%d]" % (
        config["engine"]["max_slots"],
        config["published"]["moe_latent_size"]), "ragged-dot")


def read(r):
    moe = r["counters"].get("moe_traced")
    dev_s = readers_lm.kernel_seconds_per_step(r, ops(r["config"]))
    if not moe or dev_s is None:
        return None
    return 100.0 * arith_hybrid.expert_least_seconds(
        r["config"]["published"], moe["held_choices_per_step"],
        moe["touched_experts_per_step"], r["peak"],
        r["counters"]["w_itemsize"]) / dev_s
