"""Share of the traced part in which no operation ran on the device (on
several, the one that idled most)."""
from benchmark.lib import readers


def read(r):
    return readers.max_idle_share(r)
