"""How unevenly the router loads the experts held here: the fullest held
expert's choices over the held experts' mean, a step and sparse layer, meaned
over the engine's life (`dl4j_moe_load_max_over_mean_sum{layer}`, one sample
a sparse layer, over `dl4j_moe_steps_total`). 1 is even; the fullest expert's
group is the longest grouped product."""
from benchmark.lib import program_spans as ps

SUM = "dl4j_moe_load_max_over_mean_sum"


def read(r):
    snap = ps.snapshot()
    layers = sum(1 for key in snap if key.partition("{")[0] == SUM)
    steps = ps.sample_sum(snap, "dl4j_moe_steps_total")
    if not layers or not steps:
        return None
    return ps.sample_sum(snap, SUM) / (layers * steps)
