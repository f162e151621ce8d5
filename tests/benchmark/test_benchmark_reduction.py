"""The reduction from a trace to numbers: on a few events written by hand,
and on one small trace recorded on a TPU v5e."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import trace  # noqa: E402

# name, start, duration: two launches of a step with a gap between them, a
# collective overlapping the second launch's first fusion
OPS = [("fusion.1", 1.0, 0.5), ("fusion.2", 1.5, 0.25), ("copy.3", 1.625, 0.25),
       ("fusion.1", 2.0, 0.5), ("all-reduce.7", 2.25, 0.5),
       ("fusion.2", 2.75, 0.125)]
MODULES = [("jit_step(123)", 1.0, 0.875), ("jit_other(9)", 1.875, 0.0625),
           ("jit_step(123)", 2.0, 0.875), ("jit_step(123)", 2.9375, 0.75)]
HOST = {"main": [("bench.train_step", 0.75, 0.25),
                 ("bench.readback", 1.75, 0.5), ("inner.wait", 1.8125, 0.25),
                 ("bench.make_batch", 2.875, 0.25)]}


def test_busy_union_and_idle_share():
    assert trace.merged(OPS) == [(1.0, 1.875), (2.0, 2.875)]
    assert trace.busy_seconds(OPS, 1.0, 3.0) == 1.75
    assert trace.busy_seconds(OPS, 1.25, 2.125) == 0.75
    assert trace.idle_share(OPS, 1.0, 3.0) == 0.125
    assert trace.idle_share([], 0.0, 1.0) == 1.0


def test_per_op_totals_are_sorted_and_cut():
    assert trace.op_totals(OPS, top=2) == [["fusion.1", 1.0],
                                           ["all-reduce.7", 0.5]]
    clipped = trace.op_totals(trace.clip(OPS, 1.25, 2.125))
    assert dict(map(tuple, clipped))["fusion.1"] == 0.375


def test_gaps_go_to_the_host_span_that_covers_them():
    gaps = trace.idle_gaps(OPS, 0.875, 3.0)
    assert gaps == [(0.875, 1.0), (1.875, 2.0), (2.875, 3.0)]
    got = dict(map(tuple, trace.attribute_gaps(gaps, HOST)))
    # the benchmark's own span wins over the span it contains
    assert got == {"bench.readback": 0.125, "bench.train_step": 0.125,
                   "bench.make_batch": 0.125}
    assert trace.attribute_gaps([(5.0, 6.0)], HOST) == \
        [["(no host span)", 1.0]]


def test_collective_share_counts_hidden_and_exposed_time():
    assert trace.collective_share(OPS, 2.0, 3.0) == 0.5
    assert trace.collective_share(OPS, 1.0, 2.0) == 0.0


def test_device_time_and_intervals_of_a_named_executable():
    # the launch that ends after the window does not count
    assert trace.device_seconds_per_launch(MODULES, "jit_step", 0.875,
                                           3.0) == 0.875
    assert trace.device_seconds_per_launch(MODULES, "jit_none", 0.875,
                                           3.0) is None
    assert trace.start_intervals(MODULES, "jit_step", 0.875, 4.0) == \
        [1.0, 0.9375]
    assert trace.span(HOST, "bench.readback") == (1.75, 2.25)
    assert trace.span(HOST, "absent") is None


@pytest.fixture(scope="module")
def recorded():
    """toy-mlm.train-1 traced on one TPU v5e chip by `benchmark/run.py
    --keep-trace` (PR 24's chip run): 3.4 ms, one launch of the step."""
    import gzip

    with gzip.open(os.path.join(ROOT, "tests", "benchmark", "data",
                                "toy-mlm.train-1.xplane.pb.gz")) as f:
        return trace.load(data=f.read())


def test_recorded_trace_has_the_planes_the_reduction_expects(recorded):
    assert sorted(recorded["devices"]) == [0]
    dev = recorded["devices"][0]
    assert dev["ops"] and dev["modules"]
    assert any(n.startswith("jit_step") for n, _, _ in dev["modules"])
    part = trace.span(recorded["host"], "bench.traced_part")
    assert part is not None and part[1] > part[0]
    names = {n for evs in recorded["host"].values() for n, _, _ in evs}
    assert {"bench.train_step", "bench.make_batch"} <= names


def test_recorded_trace_reduces_to_sane_numbers(recorded):
    t0, t1 = trace.span(recorded["host"], "bench.traced_part")
    dev = recorded["devices"][0]
    busy = trace.busy_seconds(dev["ops"], t0, t1)
    assert 0 < busy < t1 - t0
    assert 0 < trace.idle_share(dev["ops"], t0, t1) < 1
    per = trace.device_seconds_per_launch(dev["modules"], "jit_step", t0, t1)
    assert per == pytest.approx(49.6e-6, rel=0.01)
    assert trace.start_intervals(dev["modules"], "jit_step", t0, t1) == []
    # the ops of one launch lie inside it, so they cannot outlast it
    assert busy <= sum(d for n, s, d in trace.clip(dev["modules"], t0, t1)) \
        * 1.001
    top = trace.op_totals(trace.clip(dev["ops"], t0, t1))
    assert len(top) <= 10 and top[0][1] >= top[-1][1] > 0
    gaps = trace.attribute_gaps(trace.idle_gaps(dev["ops"], t0, t1),
                                recorded["host"])
    assert gaps and sum(s for _, s in gaps) <= (t1 - t0) - busy + 1e-9
    assert trace.collective_share(dev["ops"], t0, t1) == 0.0
