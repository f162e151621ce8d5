"""Share of the positions the token step advanced that were prompt positions,
by the engine's own count over its whole life
(`dl4j_decode_positions_total{executable="step"}`): the part of the step's work
that a prefill block would take out of it."""
from benchmark.lib import program_spans as ps


def read(r):
    snap = ps.snapshot()
    name = "dl4j_decode_positions_total"
    share = ps.ratio(ps.sample_sum(snap, name, executable="step",
                                   kind="prompt"),
                     ps.sample_sum(snap, name, executable="step"))
    return None if share is None else 100.0 * share
