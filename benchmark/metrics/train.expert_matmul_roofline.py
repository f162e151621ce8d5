"""The experts' grouped products against their roofline. Compute-bound: the
least time is three products of 2 x hidden x expert width for every choice
that fell on a held expert, forward and backward (3x), over the bf16 peak,
against the device time a step spends in the `ragged-dot` operations. Both
sides cover the traced part's steps: the driver hands over the held choices a
step that the program counted there (`held_choices_per_step_traced`: the delta
of `dl4j_moe_held_choices_total` over that of `dl4j_moe_steps_total` between
the tracer's start and its stop, all sparse layers together)."""
from benchmark.lib import arith_lm, readers_lm


def read(r):
    held = r["counters"].get("held_choices_per_step_traced")
    dev_s = readers_lm.kernel_seconds_per_step(r, ("ragged-dot",))
    if held is None or dev_s is None:
        return None
    flops = arith_lm.expert_matmul_flops_per_step(r["config"]["published"],
                                                  held)
    return 100.0 * flops / r["counters"]["chips"] \
        / r["peak"]["bf16_flops_per_s"] / dev_s
