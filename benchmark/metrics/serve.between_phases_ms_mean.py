"""Mean time of a dispatch interval that the engine's thread spent under none
of its five phase spans, over the engine's life: the wait for the interpreter
lock between them, and anything that has no span. Sum over count of
`dl4j_decode_between_phases_seconds`."""
from benchmark.lib import program_accounts as pa
from benchmark.lib import program_spans as ps


def read(r):
    return pa.mean_ms(ps.snapshot(), pa.BETWEEN)
