"""Median gap between consecutive tokens of one request, from the callers'
own stamps: the engine's iteration time as a caller sees it."""


def read(r):
    p50 = r["counters"]["itl_p50_s"]
    return None if p50 is None else 1e3 * p50
