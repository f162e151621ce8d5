"""Mean share of the page table that the token step's attention visits, over
every token-step boundary of the engine's life: `dl4j_decode_live_pages_sum`
(the pages the active slots' contexts reach, added up once a boundary) over
`dl4j_decode_boundaries_total{executable="step"}` over `max_slots x
max_pages_per_slot`, the pages a loop over the whole table would walk."""
from benchmark.lib import program_spans as ps


def read(r):
    snap = ps.snapshot()
    pages = ps.ratio(ps.sample_sum(snap, "dl4j_decode_live_pages_sum"),
                     ps.sample_sum(snap, "dl4j_decode_boundaries_total",
                                   executable="step"))
    eng = r["config"]["engine"]
    return None if pages is None else \
        100.0 * pages / (eng["max_slots"] * eng["max_pages_per_slot"])
