"""`serve.live_page_share`'s reader on registry samples written by hand (the
case `test_benchmark_program_spans.py::test_counter_metrics` makes of its
neighbours; a file of its own, since a PR adds to the benchmark's files and
edits none)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry  # noqa: E402

R = {"config": {"engine": {"max_slots": 8, "max_pages_per_slot": 50}}}


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    yield reg
    telemetry.set_registry(prev)


def boundaries(reg, model, steps, blocks):
    fam = reg.counter("dl4j_decode_boundaries_total", "",
                      ("model", "executable"))
    fam.labels(model=model, executable="step").inc(steps)
    fam.labels(model=model, executable="prefill").inc(blocks)


def live_pages(reg, model, pages):
    reg.counter("dl4j_decode_live_pages_sum", "", ("model",)).labels(
        model=model).inc(pages)


def test_live_pages_a_step_boundary_over_the_page_table(registry):
    """600 pages over 6 token-step boundaries of a table of 400: a quarter.
    Block boundaries add nothing to the sum and do not divide it; two
    engines' counts add up, whatever they are called."""
    read = run.load_reader("serve.live_page_share")
    assert read(R) is None
    boundaries(registry, "a", steps=6, blocks=2)
    live_pages(registry, "a", 600)
    assert read(R) == 25.0
    boundaries(registry, "another", steps=18, blocks=0)
    live_pages(registry, "another", 1800)
    assert read(R) == 25.0


def test_a_program_without_the_counter_reads_nothing(registry):
    """The parent commit counts boundaries and no live pages: the metric is
    left out, nothing is raised."""
    boundaries(registry, "a", steps=6, blocks=2)
    assert run.load_reader("serve.live_page_share")(R) is None


def test_the_manifest_lists_it_where_tokens_are_decoded():
    """No cell list of its own (a serving cell added as data reports it too):
    it goes wherever `decode_tokens_per_s` is reported."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = run.find(manifest["per_layer"], "serve.live_page_share", "metric")
    assert entry["moves"] == "decode_tokens_per_s" and "workloads" not in entry
    decoding = run.find(manifest["end_to_end"], "decode_tokens_per_s",
                        "metric")["workloads"]
    for w in manifest["workloads"]:
        names = [m["name"] for m in run.cell_metrics(manifest, "per_layer",
                                                     w["name"])]
        assert ("serve.live_page_share" in names) == (w["name"] in decoding)
