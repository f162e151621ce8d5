"""From a profiler trace (`.xplane.pb`) to numbers.

Two halves. `load()` turns the profiler's file into plain lists of
`(name, start_s, duration_s)` per device and per host thread, and nothing
else knows the file's format. The reductions below it work on such lists,
so a test can hand them a few events written by hand.

What the planes look like on a TPU v5e (jax 0.9.0, libtpu 0.0.34; looked at
by hand with `benchmark/tools/dump_trace.py`, PERF.md section 3): one plane
`/device:TPU:<n>` per chip with the lines `Steps`, `XLA Modules` (one event
per launch of a compiled executable, named `jit_<fn>(<fingerprint>)`: the
train step is `jit_step(...)`, the decode step `jit__fn(...)`), `XLA Ops`
(one event per HLO operation that ran, named by its whole HLO text; a `while`
has an event of its own around its body's), `Async XLA Ops` (`copy-start`,
`slice-start`, collectives' `-start` to `-done`) and an empty `TC Overlay`;
the plane `/host:CPU` holds one line per host thread with the runtime's own
spans (`PjitFunction(<fn>)`, `np.asarray(jax.Array)`, ...) and the
`TraceAnnotation` spans. All on one clock. The other planes (`#Chip0 ...`,
`/host:metadata`, `/device:CUSTOM:Megascale Trace`) are empty."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Modules": "modules", "XLA Ops": "ops", "Async XLA Ops": "async"}
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)


def find_xplane(log_dir):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path=None, data=None):
    """{"devices": {n: {"modules": [...], "ops": [...], "async": [...]}},
    "host": {thread: [...]}} with every event a `(name, start_s,
    duration_s)` tuple."""
    from jax.profiler import ProfileData

    pd = (ProfileData.from_serialized_xspace(data) if data is not None
          else ProfileData.from_file(path))
    out = {"devices": {}, "host": {}}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    dev[key] = _events(line)
            out["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].setdefault(line.name, []).extend(_events(line))
    return out


def _events(line):
    return sorted(((short_name(e.name), e.start_ns * 1e-9,
                    e.duration_ns * 1e-9) for e in line.events),
                  key=lambda e: e[1])


_HLO = re.compile(r"^%?([^ ]+?)(?:\.\d+)? = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(name):
    """An operation's event is named by its whole HLO text. Keep the
    instruction's name without its number and the shape it yields, so that
    the same operation of every layer adds up under one name:
    `%fusion.3040 = bf16[16,77,1024]{...} fusion(...)` is `fusion
    bf16[16,77,1024]`. Other names stay as they are."""
    m = _HLO.match(name)
    if not m:
        return name
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


# -- reductions over lists of (name, start_s, duration_s) ---------------------

def clip(events, t0, t1):
    """The events' parts inside [t0, t1]."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged(events):
    """Union of the events' intervals as sorted, disjoint (start, end)."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def busy_seconds(events, t0, t1):
    return sum(b - a for a, b in merged(clip(events, t0, t1)))


def idle_share(events, t0, t1):
    return 1.0 - busy_seconds(events, t0, t1) / (t1 - t0)


def op_totals(events, top=10):
    """[[name, seconds], ...] of the operations that took most time."""
    tot = {}
    for name, _, d in events:
        tot[name] = tot.get(name, 0.0) + d
    return [[n, s] for n, s in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:top]]


def idle_gaps(events, t0, t1):
    """(start, end) of every stretch of [t0, t1] in which no event ran."""
    gaps, at = [], t0
    for a, b in merged(clip(events, t0, t1)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def attribute_gaps(gaps, host, top=10, prefer=("bench.",), longest=200):
    """[[what the host was doing, seconds], ...] for the idle time, longest
    first. Each of the `longest` gaps goes to the host span that covers most
    of it; a span whose name starts with one of `prefer` (the benchmark's own
    annotations) wins over the spans it contains. Gaps that no span covers
    are `(no host span)`; the many short gaps between one operation and the
    next are summed as `(gaps under <the shortest one attributed>)`."""
    spans = [e for evs in host.values() for e in evs if e[2] > 0]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    tot = {}
    if len(gaps) > longest:
        cut = gaps[longest - 1][1] - gaps[longest - 1][0]
        tot[f"(gaps under {cut * 1e9:.0f} ns)"] = sum(
            b - a for a, b in gaps[longest:])
        gaps = gaps[:longest]
    for a, b in gaps:
        best, best_key = "(no host span)", (0, 0.0)
        for name, s, d in spans:
            ov = min(b, s + d) - max(a, s)
            if ov <= 0:
                continue
            key = (1 if name.startswith(prefer) else 0, ov)
            if key > best_key:
                best, best_key = name, key
        tot[best] = tot.get(best, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:top]]


def collective_share(ops, t0, t1):
    """Share of [t0, t1] in which a collective operation ran on the device."""
    coll = [e for e in ops if COLLECTIVE.search(e[0])]
    return busy_seconds(coll, t0, t1) / (t1 - t0)


def launches(modules, prefix, t0, t1):
    """Launches of the executable whose name starts with `prefix` that lie
    wholly inside [t0, t1]."""
    return [e for e in modules
            if e[0].startswith(prefix) and e[1] >= t0 and e[1] + e[2] <= t1]


def device_seconds_per_launch(modules, prefix, t0, t1):
    """Mean device time of one launch of the named executable, or None."""
    runs = launches(modules, prefix, t0, t1)
    if not runs:
        return None
    return sum(d for _, _, d in runs) / len(runs)


def start_intervals(modules, prefix, t0, t1):
    """Start-to-start intervals between consecutive launches."""
    starts = [s for _, s, _ in launches(modules, prefix, t0, t1)]
    return [b - a for a, b in zip(starts, starts[1:])]


def span(host, name):
    """(start, end) of the first host span of that name, or None."""
    for evs in host.values():
        for n, s, d in evs:
            if n == name:
                return s, s + d
    return None
