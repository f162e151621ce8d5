"""How unevenly the router loads the experts held here, in a token step: the
fullest held expert's choices over the held experts' mean, a launch and sparse
layer, meaned over the engine's life (`dl4j_moe_load_max_over_mean_sum`, one
sample a sparse layer of the configuration, over `dl4j_moe_steps_total`, both
under the label of the model the driver registered). 1 is even; at some four rows an expert it is
the Poisson spread and not the router's skew that sets it."""
from benchmark.lib import program_spans as ps

SUM = "dl4j_moe_load_max_over_mean_sum"


def read(r):
    snap = ps.snapshot()
    label = {"model": r["counters"].get("moe_model")}
    layers = r["config"]["model"]["layer_kinds"].count("sparse")
    total = ps.sample_sum(snap, SUM, **label)
    steps = ps.sample_sum(snap, "dl4j_moe_steps_total", **label)
    if total is None or not steps:
        return None
    return total / (layers * steps)
