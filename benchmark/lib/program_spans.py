"""What the program says of itself, read from outside it: the spans it puts on
the profiler's clock (`dl4j.decode.<phase>` around the five phases of a
boundary of `DecodeEngine._loop`, `dl4j.train.gather` and `dl4j.train.dispatch`
inside `BertTrainer.train_step`) and the counts in its telemetry registry.

Pure functions over the `(name, start_s, duration_s)` lists that
`trace.load` returns and over the registry's flat snapshot, so that a test can
hand them a few events and samples written by hand. A count is summed over
the `model` label: which name the driver gave its engine is not written here.
Where the program has no such span or counter (a commit before they were
added), every function returns None or nothing, and the metric is left out."""

import re

from benchmark.lib import arith, trace

DECODE = "dl4j.decode."
ADMIT, EMIT = DECODE + "admit", DECODE + "emit"
GATHER, DISPATCH = "dl4j.train.gather", "dl4j.train.dispatch"

_LABEL = re.compile(r'(\w+)="([^"]*)"')


# -- spans --------------------------------------------------------------------

def decode_iterations(host, launches, t0, t1):
    """[(prepare_s, retire_s)] of every iteration of the engine's loop that
    lies wholly inside [t0, t1] and launched something: an iteration is the
    stretch from one `dl4j.decode.admit` start to the next on one thread;
    `prepare` runs from there to the start of its first launch on the device,
    `retire` from the end of its last launch to the end of its last
    `dl4j.decode.emit`. The engine is serial, so the two are the device's
    idle gap as the program sees it."""
    out = []
    for events in host.values():
        admits = sorted(s for n, s, _ in events if n == ADMIT)
        emits = sorted(s + d for n, s, d in events if n == EMIT)
        for a, b in zip(admits, admits[1:]):
            if a < t0 or b > t1:
                continue
            ran = [(s, s + d) for _, s, d in launches if a <= s < b]
            done = [e for e in emits if a < e <= b]
            if ran and done:
                out.append((ran[0][0] - a, done[-1] - ran[-1][1]))
    return out


def decode_host_ms_p50(r, which):
    """Median `prepare` (which=0) or `retire` (which=1) over the traced part,
    in ms, against the launches of the cell's step executable on its first
    device."""
    t = r["trace"]
    launches = trace.launches(t["devices"][t["used"][0]]["modules"],
                              r["counters"]["step_executable"],
                              t["t0"], t["t1"])
    p50 = arith.percentile([it[which] for it in decode_iterations(
        t["host"], launches, t["t0"], t["t1"])], 50)
    return None if p50 is None else 1e3 * p50


def train_host_seconds(host, t0, t1):
    """[gather + dispatch seconds] of every `train_step` call whose two spans
    lie wholly inside [t0, t1]: each `dl4j.train.gather` with the
    `dl4j.train.dispatch` that follows it on the same thread."""
    out = []
    for events in host.values():
        gather = None
        for name, s, d in sorted((e for e in events
                                  if e[0] in (GATHER, DISPATCH)),
                                 key=lambda e: e[1]):
            if name == GATHER:
                gather = (s, d)
            elif gather is not None:
                if gather[0] >= t0 and s + d <= t1:
                    out.append(gather[1] + d)
                gather = None
    return out


def phase_medians(host, prefix="dl4j."):
    """{span name: [how many, median ms]} of the program's own spans, for
    PERF.md's breakdown by phase."""
    by_name = {}
    for events in host.values():
        for name, _, d in events:
            if name.startswith(prefix):
                by_name.setdefault(name, []).append(d)
    return {n: [len(ds), 1e3 * arith.percentile(ds, 50)]
            for n, ds in sorted(by_name.items())}


# -- counts -------------------------------------------------------------------

def snapshot():
    """The flat {sample name: value} of the program's registry, as it stands
    once the window has closed: counts over the engine's whole life."""
    from deeplearning4j_tpu import telemetry

    return telemetry.get_registry().snapshot()


def sample_sum(snap, name, **labels):
    """Sum of every sample `name{...}` whose labels include `labels`,
    whatever its other labels say; None where there is none."""
    found, total = False, 0.0
    for key, value in snap.items():
        base, _, inner = key.partition("{")
        if base != name:
            continue
        have = dict(_LABEL.findall(inner))
        if all(have.get(k) == v for k, v in labels.items()):
            found, total = True, total + value
    return total if found else None


def ratio(num, den):
    """num / den, or None where either is missing or there is nothing to
    divide by."""
    if num is None or not den:
        return None
    return num / den
