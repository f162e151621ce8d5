"""What the per-layer metrics' readers share. A reader gets `r`: the reduced
trace (`benchmark.lib.trace.load` plus the traced part's ends `t0`, `t1` and
the device numbers `used`), the driver's counters, the chip's peaks, the
cell's file and its configuration; it returns a number, or None where it
finds nothing to read."""

from benchmark.lib import trace


def step_launch_seconds(r):
    """Mean device time of one launch of the cell's step executable."""
    t = r["trace"]
    return trace.device_seconds_per_launch(
        t["devices"][t["used"][0]]["modules"],
        r["counters"]["step_executable"], t["t0"], t["t1"])


def max_idle_share(r):
    """Idle share of the traced part, on the device that idled most, in %."""
    t = r["trace"]
    return 100.0 * max(trace.idle_share(t["devices"][d]["ops"], t["t0"],
                                        t["t1"]) for d in t["used"])
