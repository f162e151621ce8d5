"""Host time `BertTrainer.train_step` takes a step, median over the steps
inside the traced part: its `dl4j.train.gather` span (the masked positions
gathered on the host) plus its `dl4j.train.dispatch` span (the key, the token
array, the call of the step executable up to its return)."""
from benchmark.lib import arith, program_spans


def read(r):
    t = r["trace"]
    p50 = arith.percentile(program_spans.train_host_seconds(
        t["host"], t["t0"], t["t1"]), 50)
    return None if p50 is None else 1e3 * p50
