"""Mean time from `submit()` to the boundary at which the request took a slot,
over every request the engine admitted in its life: sum over count of the
`dl4j_decode_queue_wait_seconds` histogram."""
from benchmark.lib import program_spans as ps


def read(r):
    snap = ps.snapshot()
    name = "dl4j_decode_queue_wait_seconds"
    mean = ps.ratio(ps.sample_sum(snap, name + "_sum"),
                    ps.sample_sum(snap, name + "_count"))
    return None if mean is None else 1e3 * mean
