"""Mean share of the KV pool's pages that were reserved, over every boundary of
the engine's life: `dl4j_decode_kv_fill_sum` (the occupancy gauge's value added
up once a boundary) over `dl4j_decode_boundaries_total`."""
from benchmark.lib import program_spans as ps


def read(r):
    snap = ps.snapshot()
    fill = ps.ratio(ps.sample_sum(snap, "dl4j_decode_kv_fill_sum"),
                    ps.sample_sum(snap, "dl4j_decode_boundaries_total"))
    return None if fill is None else 100.0 * fill
