"""The whole step's share of the chips' bf16 peak: model FLOPs from shapes x
steps completed, over the window's wall clock and the chips."""


def read(r):
    c = r["counters"]
    if not c["steps"]:
        return None
    return 100.0 * c["flops_per_step"] * c["steps"] / c["seconds"] \
        / (c["chips"] * r["peak"]["bf16_flops_per_s"])
