"""Share of the traced part in which a collective ran on the first device,
hidden behind compute or not: the collectives among the operations and, where
XLA made them asynchronous, from their `-start` to their `-done`."""
from benchmark.lib import trace


def read(r):
    t = r["trace"]
    dev = t["devices"][t["used"][0]]
    ops = dev["ops"] + dev["async"]
    if not any(trace.COLLECTIVE.search(e[0]) for e in ops):
        return None
    return 100.0 * trace.collective_share(ops, t["t0"], t["t1"])
