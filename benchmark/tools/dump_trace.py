"""Look at one trace by hand: which planes, which lines, how the executables
and operations are named.

    python benchmark/tools/dump_trace.py <file.xplane.pb> [events per line]
    python benchmark/tools/dump_trace.py --keep DIR <run.py's arguments>

The second form makes a traced run of a cell (on the chip), copies its
`.xplane.pb` into DIR before the run removes it, and dumps that."""

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def record(keep, argv):
    """`run.py <argv> --trace 1` with the trace's file copied to `keep`."""
    sys.path.insert(0, ROOT)
    from benchmark import run
    from benchmark.lib import trace

    os.makedirs(keep, exist_ok=True)
    kept, load = [], trace.load

    def load_and_keep(path):
        kept.append(shutil.copy(path, keep))
        return load(path)

    trace.load = load_and_keep
    rc = run.main(argv + ["--trace", "1"])
    if rc or not kept:
        raise SystemExit(rc or "the run made no trace")
    return kept[0]


def main():
    from jax.profiler import ProfileData

    if sys.argv[1] == "--keep":
        sys.argv[1:] = [record(sys.argv[2], sys.argv[3:])]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    pd = ProfileData.from_file(sys.argv[1])
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            names = {}
            for e in events:
                names[e.name] = names.get(e.name, 0.0) + e.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(names)} names")
            for e in events[:n]:
                print(f"    {e.name[:90]!r} start_ns={e.start_ns:.0f} "
                      f"dur_ns={e.duration_ns:.0f}")
            for name, ns in sorted(names.items(), key=lambda kv: -kv[1])[:n]:
                print(f"    total {ns / 1e6:10.3f} ms  {name[:90]!r}")


if __name__ == "__main__":
    main()
