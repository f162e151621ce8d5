"""Share of the token-step boundaries whose successor was dispatched before
their tokens were read, over the engine's life:
`dl4j_decode_overlapped_boundaries_total` over
`dl4j_decode_boundaries_total{executable="step"}`. It says how often the
engine kept one token step in flight, so that the host's read-back, emission
and admission ran under the next launch. A program without the counter (a
commit before it was added) reads nothing, and the metric is left out."""
from benchmark.lib import program_spans as ps


def read(r):
    snap = ps.snapshot()
    share = ps.ratio(
        ps.sample_sum(snap, "dl4j_decode_overlapped_boundaries_total"),
        ps.sample_sum(snap, "dl4j_decode_boundaries_total",
                      executable="step"))
    return None if share is None else 100.0 * share
