"""Each driver end to end at a toy configuration on the CPU: everything a run
does but the look for a chip. A CPU timing is no device number; these tests
read the control flow, the result's shape and `correct`."""

import argparse
import itertools
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TOY = os.path.join(ROOT, "tests", "benchmark", "data", "toy")

from benchmark import run  # noqa: E402
from benchmark.lib import trace  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAK = json.load(f)["TPU v5 lite"]


def run_cell(cell, seed=2 ** 31 + 7, seconds=1.0, trace_=0, root=TOY):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace_)
    return run.run(args, root=root, devices=jax.devices(), peak=PEAK)


@pytest.mark.parametrize("cell, metrics", [
    ("toy-mlm.train-1", {"train_tokens_per_s_per_chip"}),
    ("toy-mlm.train-dp4", {"train_tokens_per_s_per_chip"}),
    ("toy-decoder.closed-4", {"decode_tokens_per_s", "ttft_p95_ms",
                              "itl_p95_ms"}),
])
def test_driver_end_to_end(cell, metrics):
    result, rows = run_cell(cell)
    assert list(result)[-1] == "compared"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "compared"}
    assert set(result["metrics"]) == metrics | {"setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"], rows
    assert result["compared"]["window_compiles"] == {"value": 0, "limit": 0}
    json.dumps(result)


def test_a_cell_added_as_data_runs(tmp_path):
    """What a later PR does to add a cell: one new file of traffic
    parameters, one appended entry, its name appended to the cell lists of
    the end-to-end metrics it reports. Nothing else, and no code."""
    import shutil

    root = shutil.copytree(TOY, tmp_path / "toy")
    manifest = run.load_json(root, "BENCHMARK.json")
    body = run.load_json(root, "benchmark", "workloads",
                         "toy-decoder.closed-4.json")
    body["traffic"]["prompt"] = {"median": 4, "sigma": 0.3, "min": 2, "max": 6}
    with open(root / "benchmark" / "workloads" / "toy-decoder.short-4.json",
              "w") as f:
        json.dump(body, f)
    manifest["workloads"].append({"name": "toy-decoder.short-4", "chips": 1,
                                  "config": "toy-decoder"})
    for m in manifest["end_to_end"]:
        if "toy-decoder.closed-4" in m.get("workloads", []):
            m["workloads"].append("toy-decoder.short-4")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    result, rows = run_cell("toy-decoder.short-4", root=root)
    assert result["correct"], rows
    assert set(result["metrics"]) == {"decode_tokens_per_s", "ttft_p95_ms",
                                      "itl_p95_ms", "setup_s"}
    assert [m["name"] for m in run.cell_metrics(
        manifest, "per_layer", "toy-decoder.short-4")] == [
        m["name"] for m in run.cell_metrics(
            manifest, "per_layer", "toy-decoder.closed-4")]


def test_the_seed_decides_the_inputs():
    from benchmark.lib import traffic

    tr = {"rows": 4, "seq": 32, "mask_frac": 0.15}
    a, b, c = (next(traffic.mlm_batches(tr, 128, s)) for s in (5, 5, 6))
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (a[0] != c[0]).any()
    cell = run.load_json(TOY, "benchmark", "workloads",
                         "toy-decoder.closed-4.json")["traffic"]
    r5, r5b, r6 = (list(itertools.islice(traffic.requests(cell, s), 40))
                   for s in (5, 5, 2 ** 31 + 6))
    assert r5 == r5b and r5 != r6
    sizes = lambda reqs: sorted((len(p), n) for p, n in reqs[:8])  # noqa: E731
    assert sizes(r5) == sizes(r6)      # the same work in another order


def test_a_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """The tracer's start and stop around the window's end on the CPU, then
    the reduction and the readers over the trace recorded on the chip."""
    import gzip

    with gzip.open(os.path.join(ROOT, "tests", "benchmark", "data",
                                "toy-mlm.train-1.xplane.pb.gz")) as f:
        recorded = f.read()
    real_load = trace.load
    monkeypatch.setattr(trace, "load",
                        lambda path: real_load(data=recorded))
    result, rows = run_cell("toy-mlm.train-1", trace_=1)
    assert result["correct"], rows
    # the recorded part holds one launch of the step: no interval between
    # two, so that reader finds nothing to read and its metric is left out
    assert set(result["metrics"]) == {
        "train.step_mfu", "train.step_roofline", "device_idle_share.train"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    ops, gaps = (result["breakdown"][k] for k in ("device_ops", "idle_gaps"))
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(s > 0 for _, s in ops + gaps)


def test_run_py_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "bert-large-mlm.train-16x512", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "refused" in out.stderr and "TPU" in out.stderr
    assert not out.stdout.strip().endswith("}")
