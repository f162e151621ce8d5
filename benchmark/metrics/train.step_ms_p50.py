"""Median start-to-start interval of the step executable on the first device,
over the traced part."""
from benchmark.lib import arith, trace


def read(r):
    t = r["trace"]
    p50 = arith.percentile(trace.start_intervals(
        t["devices"][t["used"][0]]["modules"],
        r["counters"]["step_executable"], t["t0"], t["t1"]), 50)
    return None if p50 is None else 1e3 * p50
