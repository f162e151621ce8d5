"""The readers of the program's own spans and counts
(`benchmark/lib/program_spans.py` and the six metrics on it), on events and
registry samples written by hand."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.lib import program_spans as ps  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry  # noqa: E402

P = "dl4j.decode."


def iteration(at, launch_at, launch_for, emit_end):
    """The five spans of one engine iteration that starts at `at`."""
    return [(P + "admit", at, 0.25), (P + "build", at + 0.25, 0.25),
            (P + "dispatch", at + 0.5, 0.25),
            (P + "readback", at + 0.75, launch_at + launch_for - at - 0.75),
            (P + "emit", launch_at + launch_for, emit_end - launch_at
             - launch_for)]


# the traced part is [10, 30]: an iteration cut by its start, two whole ones
# (launch 1.0 after the admit's start then 0.5 to the emit's end; 1.5 then
# 0.25), an idle stretch, and one with no admit after it
ENGINE = (iteration(8.0, 9.0, 2.0, 12.0) + iteration(12.0, 13.0, 4.0, 17.5)
          + iteration(18.0, 19.5, 3.0, 22.75) + iteration(26.0, 27.0, 2.0, 29.5))
LAUNCHES = [("jit__fn(7)", 9.0, 2.0), ("jit__fn(7)", 13.0, 4.0),
            ("jit_other(1)", 17.0, 0.25), ("jit__fn(7)", 19.5, 3.0),
            ("jit__fn(7)", 27.0, 2.0)]
CALLER = [("bench.submit", 12.5, 0.125), ("bench.submit", 20.0, 0.125)]
TRAIN = [("dl4j.train.gather", 9.5, 1.0), ("dl4j.train.dispatch", 10.5, 0.5),
         ("bench.train_step", 11.75, 1.0),
         ("dl4j.train.gather", 12.0, 0.25), ("dl4j.train.dispatch", 12.25, 0.5),
         ("dl4j.train.gather", 14.0, 0.5), ("dl4j.train.dispatch", 14.5, 1.0),
         ("dl4j.train.gather", 29.0, 0.5), ("dl4j.train.dispatch", 29.5, 1.0)]


def traced(host, launches=LAUNCHES, executable="jit__fn"):
    return {"trace": {"host": host, "t0": 10.0, "t1": 30.0, "used": [0],
                      "devices": {0: {"modules": launches, "ops": [],
                                      "async": []}}},
            "counters": {"step_executable": executable}}


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    yield reg
    telemetry.set_registry(prev)


def fill(reg, model="a", scale=1):
    """What an engine named `model` would have counted, written by hand."""
    pos = reg.counter("dl4j_decode_positions_total", "",
                      ("model", "executable", "kind"))
    for exe, kind, n in (("step", "prompt", 60), ("step", "answer", 20),
                         ("prefill", "prompt", 1000)):
        pos.labels(model=model, executable=exe, kind=kind).inc(n * scale)
    bounds = reg.counter("dl4j_decode_boundaries_total", "",
                         ("model", "executable"))
    bounds.labels(model=model, executable="step").inc(6 * scale)
    bounds.labels(model=model, executable="prefill").inc(2 * scale)
    reg.counter("dl4j_decode_kv_fill_sum", "", ("model",)).labels(
        model=model).inc(2.0 * scale)
    wait = reg.histogram("dl4j_decode_queue_wait_seconds", "",
                         ("model",)).labels(model=model)
    for s in (0.25, 0.5, 0.75):
        wait.observe(s)


def test_two_whole_iterations_give_prepare_and_retire_by_eye():
    launches = [e for e in LAUNCHES if e[0].startswith("jit__fn")]
    host = {"engine": ENGINE, "caller": CALLER}
    assert ps.decode_iterations(host, launches, 10.0, 30.0) == \
        [(1.0, 0.5), (1.5, 0.25)]
    # nothing is cut when the traced part holds the first iteration whole
    assert ps.decode_iterations(host, launches, 8.0, 30.0)[0] == (1.0, 1.0)


@pytest.mark.parametrize("metric, host, want", [
    ("serve.prepare_ms_p50", {"engine": ENGINE, "caller": CALLER}, 1250.0),
    ("serve.retire_ms_p50", {"engine": ENGINE, "caller": CALLER}, 375.0),
    ("serve.prepare_ms_p50", {"caller": CALLER}, None),
    ("serve.retire_ms_p50", {"caller": CALLER}, None),
    ("train.host_ms_per_step_p50", {"main": TRAIN}, 1125.0),
    ("train.host_ms_per_step_p50", {"main": CALLER}, None),
])
def test_span_metrics(metric, host, want):
    """Medians over what lies wholly inside the traced part; a trace with no
    span of the program's reads None."""
    executable = "jit_step" if metric.startswith("train") else "jit__fn"
    assert run.load_reader(metric)(traced(host, executable=executable)) == want


def test_a_step_cut_by_the_traced_part_is_left_out():
    # the first step's gather starts before 10, the last one's dispatch ends
    # after 30: the two in between remain
    assert ps.train_host_seconds({"main": TRAIN}, 10.0, 30.0) == [0.75, 1.5]
    assert ps.train_host_seconds({"main": TRAIN}, 0.0, 40.0) == \
        [1.5, 0.75, 1.5, 1.5]


def test_an_iteration_without_a_launch_is_left_out():
    idle = [(P + "admit", 11.0, 0.25), (P + "admit", 12.0, 0.25),
            (P + "emit", 12.5, 0.25), (P + "admit", 13.0, 0.25)]
    assert ps.decode_iterations({"engine": idle}, [], 10.0, 30.0) == []
    assert run.load_reader("serve.prepare_ms_p50")(
        traced({"engine": idle}, launches=[])) is None


@pytest.mark.parametrize("metric, want", [
    ("serve.prompt_position_share", 75.0),
    ("serve.kv_page_fill", 25.0),
    ("serve.queue_wait_ms_mean", 500.0),
])
def test_counter_metrics(registry, metric, want):
    """Ratios of the engine's counts; two engines' counts add up, whatever
    they are called; an empty registry reads None."""
    read = run.load_reader(metric)
    assert read({}) is None
    fill(registry)
    assert read({}) == want
    fill(registry, model="another", scale=3)
    assert read({}) == want


def test_sample_sum_matches_whole_names_and_given_labels(registry):
    fill(registry)
    snap = registry.snapshot()
    name = "dl4j_decode_positions_total"
    assert ps.sample_sum(snap, name) == 1080
    assert ps.sample_sum(snap, name, executable="step") == 80
    assert ps.sample_sum(snap, name, executable="verify") is None
    assert ps.sample_sum(snap, "dl4j_decode_positions") is None
    assert ps.sample_sum(snap, "dl4j_decode_queue_wait_seconds_count") == 3
    assert ps.ratio(None, 2.0) is None and ps.ratio(1.0, 0.0) is None


def test_phase_medians_count_and_take_the_median():
    got = ps.phase_medians({"engine": ENGINE, "caller": CALLER})
    assert got[P + "admit"] == [4, 250.0]
    assert got[P + "emit"] == [4, 500.0]
    assert "bench.submit" not in got
