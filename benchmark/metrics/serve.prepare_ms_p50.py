"""Host time before the device starts on a boundary, median over the engine's
iterations inside the traced part: from the start of `dl4j.decode.admit` to the
start of the iteration's launch of the step executable (admission, the numpy
inputs, the page table's copy, the dispatch)."""
from benchmark.lib import program_spans


def read(r):
    return program_spans.decode_host_ms_p50(r, 0)
