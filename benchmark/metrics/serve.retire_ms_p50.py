"""Host time after the device has finished a boundary, median over the engine's
iterations inside the traced part: from the end of the iteration's launch of
the step executable to the end of `dl4j.decode.emit` (the read-back's tail,
tokens to their callers' queues, requests finished, the counts)."""
from benchmark.lib import program_spans


def read(r):
    return program_spans.decode_host_ms_p50(r, 1)
