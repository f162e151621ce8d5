"""One cell of the benchmark, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Nothing about any cell, configuration or metric is written in this file. It
finds the cell in `BENCHMARK.json` (its configuration, its chips, the metrics
it reports), the cell's parameters in `benchmark/workloads/<cell>.json`, its
configuration in the file the manifest names, its driver in
`benchmark/drivers/<driver>.py` and each per-layer metric's reader in
`benchmark/metrics/<metric>.py`. Each fact has one place: what the manifest's
entry says, the cell's file does not say again. The last line of standard
output is the result; everything before it is progress."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACED_PART = "bench.traced_part"


class Refused(RuntimeError):
    """The run cannot be made here: no result is printed, the exit is not 0."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"{what} {name!r} is not in BENCHMARK.json "
                  f"(has: {[e['name'] for e in entries]})")


def cell_metrics(manifest, section, cell):
    """The metrics of `section` this cell reports: those that list it, and
    those that list no cells and (per layer) move a metric it reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if section == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in reported]


def load_cell(root, manifest, name):
    """The manifest's entry, the cell's own file with the entry's `chips` and
    `config` beside its parameters, and the configuration's file."""
    entry = find(manifest["workloads"], name, "workload")
    cell = dict(load_json(root, "benchmark", "workloads", name + ".json"),
                chips=entry["chips"], config=entry["config"])
    config = load_json(root, find(manifest["configs"], entry["config"],
                                  "config")["file"])
    return entry, cell, config


def load_reader(name):
    """`metrics/<name>.py`; a quantity split by the end-to-end metric it moves
    (`<quantity>.train`, `<quantity>.serve`) may share `<quantity>.py`."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.isfile(path):
        path = os.path.join(HERE, "metrics", name.rpartition(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a driver gets: the cell, its configuration, the seed, and the
    tracer's switch. `seed31` is the seed folded under 2**31 for the
    program's own `jax.random.key(seed)` calls."""

    def __init__(self, cell, config, seed, seconds, trace, devices):
        self.cell, self.config = cell, config
        self.seed, self.seconds, self.trace = int(seed), seconds, bool(trace)
        self.seed31 = self.seed % (2 ** 31 - 1)
        self.devices = devices
        self.trace_dir = None

    def say(self, msg):
        print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}", flush=True)

    def start_trace(self):
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._part = jax.profiler.TraceAnnotation(TRACED_PART)
        self._part.__enter__()

    def stop_trace(self):
        import jax

        self._part.__exit__(None, None, None)
        jax.profiler.stop_trace()


def devices_or_refuse(cell, peaks):
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise Refused(f"the benchmark measures a TPU; jax resolved to "
                      f"{d.platform!r} ({d.device_kind!r}). No fallback.")
    if d.device_kind not in peaks:
        raise Refused(f"device kind {d.device_kind!r} is not in "
                      f"benchmark/peaks.json")
    if len(devices) < cell["chips"]:
        raise Refused(f"the cell asks for {cell['chips']} chips, jax finds "
                      f"{len(devices)}")
    return devices


def memory_peak(devices):
    """`peak_bytes_in_use` on the fullest device (live arrays; XLA's
    temporaries are not in it on this runtime, PERF.md section 2)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if devices[0].platform == "tpu" and any(p is None for p in peaks):
        raise RuntimeError("the backend reports no peak_bytes_in_use")
    return max(p or 0 for p in peaks)


def reduce_trace(ctx, chips):
    """The trace as plain lists, the traced part's ends on its clock, and the
    contract's `busy_s`, `window_s` and `breakdown`."""
    from benchmark.lib import trace as tr

    path = tr.find_xplane(ctx.trace_dir)
    t = tr.load(path)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    part = tr.span(t["host"], TRACED_PART)
    used = sorted(t["devices"])[:chips]
    if part is None or not used:
        raise RuntimeError(f"trace has no {TRACED_PART} span or no device "
                           f"plane (devices {sorted(t['devices'])})")
    t0, t1 = part
    busy = [tr.busy_seconds(t["devices"][d]["ops"], t0, t1) for d in used]
    dev0 = t["devices"][used[0]]["ops"]
    host = {k: [e for e in v if e[0] != TRACED_PART]
            for k, v in t["host"].items()}
    breakdown = {
        "device_ops": tr.op_totals(tr.clip(dev0, t0, t1)),
        "idle_gaps": tr.attribute_gaps(tr.idle_gaps(dev0, t0, t1), host)}
    t.update(t0=t0, t1=t1, used=used)
    return t, sum(busy) / len(busy), t1 - t0, breakdown


def enable_compile_cache():
    """The program's own placement (`JAX_COMPILATION_CACHE_DIR`, or
    `<checkout>/.jax_cache`), and small programs kept too, so that a second
    run finds every program there. The entry point's business, not `run`'s:
    the repo's tests count compiles and must not meet a warm cache."""
    import jax

    from deeplearning4j_tpu.runtime import RuntimeConfig

    path = RuntimeConfig.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache at {path}", flush=True)


def run(args, root=ROOT, devices=None, peak=None):
    """One run. `root` holds BENCHMARK.json and the files it names; a test
    hands in `devices` and `peak` and so skips the look for a chip."""
    manifest = load_json(root, "BENCHMARK.json")
    entry, cell, config = load_cell(root, manifest, args.workload)
    if devices is None:
        peaks = load_json(HERE, "peaks.json")
        devices = devices_or_refuse(cell, peaks)
        peak = peaks[devices[0].device_kind]

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == COMPILE_EVENT else None)

    ctx = Context(cell, config, args.seed, args.seconds, args.trace, devices)
    ctx.say(f"cell {entry['name']} seed {ctx.seed} on {len(devices)} x "
            f"{devices[0].device_kind}")
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - T_START
    ctx.say(f"set-up done ({len(compiles)} compile events); window opens")
    win = driver.measure(ctx, state)
    in_window = sum(1 for t in compiles if win["t0"] <= t <= win["t1"])
    used = devices[:cell["chips"]]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak(used)}
    ctx.say(f"window closed; memory peak {device['memory_peak_bytes']}")

    numbers = driver.check(ctx, state)
    del state
    gc.collect()
    numbers["window_compiles"] = in_window
    from benchmark.lib import compare

    rows = compare.verdict(numbers, cell["limits"])

    result = {"correct": all(ok for *_, ok in rows),
              "attempted": win["attempted"], "failed": win["failed"]}
    metrics = cell_metrics(
        manifest, "per_layer" if ctx.trace else "end_to_end", entry["name"])
    values = dict(win["end_to_end"], setup_s=setup_s)
    if ctx.trace:
        trace, busy_s, window_s, breakdown = reduce_trace(ctx, cell["chips"])
        device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = breakdown
        r = {"trace": trace, "counters": win["counters"], "peak": peak,
             "cell": cell, "config": config}
        values = {m["name"]: load_reader(m["name"])(r) for m in metrics}
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in metrics if values.get(m["name"]) is not None}
    result["device"] = device
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim, _ in rows}
    ctx.say(f"done in {time.perf_counter() - T_START:.1f}s")
    return result, rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        enable_compile_cache()
        result, rows = run(args)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, value, limit, ok in rows:
        print(f"compared {name} = {value} limit {limit} "
              f"{'ok' if ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
