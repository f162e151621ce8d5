"""`serve.overlapped_boundary_share`'s reader on registry samples written by
hand (a file of its own, as `test_benchmark_live_page_share.py` is: a PR adds
to the benchmark's files and edits none)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry  # noqa: E402

NAME = "serve.overlapped_boundary_share"


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    yield reg
    telemetry.set_registry(prev)


def boundaries(reg, model, steps, blocks=0):
    fam = reg.counter("dl4j_decode_boundaries_total", "",
                      ("model", "executable"))
    fam.labels(model=model, executable="step").inc(steps)
    if blocks:
        fam.labels(model=model, executable="prefill").inc(blocks)


def overlapped(reg, model, n):
    reg.counter("dl4j_decode_overlapped_boundaries_total", "",
                ("model",)).labels(model=model).inc(n)


def test_overlapped_over_the_token_step_boundaries(registry):
    """198 of 200 token-step boundaries had their successor dispatched before
    they were read: 99%. Block boundaries do not divide it; a second engine's
    counts add up with the first's, whatever it is called."""
    read = run.load_reader(NAME)
    assert read({}) is None
    boundaries(registry, "a", steps=200, blocks=50)
    overlapped(registry, "a", 198)
    assert read({}) == 99.0
    boundaries(registry, "serial", steps=200)
    overlapped(registry, "serial", 0)
    assert read({}) == 49.5


def test_a_serial_engine_reads_zero_and_the_parent_nothing(registry):
    """An engine that has the counter and never overlapped reads 0; the parent
    commit counts boundaries and has no such counter: the metric is left out,
    nothing is raised."""
    boundaries(registry, "a", steps=6, blocks=2)
    assert run.load_reader(NAME)({}) is None
    overlapped(registry, "a", 0)
    assert run.load_reader(NAME)({}) == 0.0


def test_the_manifest_lists_it_where_tokens_are_decoded():
    """An `engine` metric from the program's counter, with no cell list of its
    own: it goes wherever `decode_tokens_per_s` is reported."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = run.find(manifest["per_layer"], NAME, "metric")
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "engine",
                     "moves": "decode_tokens_per_s"}
    decoding = run.find(manifest["end_to_end"], "decode_tokens_per_s",
                        "metric")["workloads"]
    for w in manifest["workloads"]:
        names = [m["name"] for m in run.cell_metrics(manifest, "per_layer",
                                                     w["name"])]
        assert (NAME in names) == (w["name"] in decoding)
