"""ISSUE 36's two per-layer metrics of the host's chain without a chip: the
pure functions of `benchmark/lib/program_accounts.py` on snapshots written by
hand, each reader on registry samples written by hand (and on a registry that
has none, as the parent commit's), and the manifest's two entries."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.lib import program_accounts as pa  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.telemetry.registry import (  # noqa: E402
    DECODE_PHASES, MetricsRegistry, ServingInstruments)

SERVING = ["bert-base-decoder.closed-64", "deepseek-v3.closed-128",
           "nemotron3-super.closed-128"]
TRAINING = ["bert-large-mlm.train-16x512", "bert-large-mlm.train-dp4-64x512",
            "laguna-xs2.train-2x8192"]
TWO = ["serve.host_chain_ms_mean", "serve.between_phases_ms_mean"]
PHASE_SECONDS = dict(zip(DECODE_PHASES, (0.0001, 0.0002, 0.0012, 0.0006,
                                         0.0004)))


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    yield reg
    telemetry.set_registry(prev)


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def engine(reg, model, boundaries, between, phases):
    """An engine's series as `ServingInstruments` leaves them after
    `boundaries` token steps: `phases` seconds a boundary under each span and
    `between` under none."""
    inst = ServingInstruments(reg, model)
    for _ in range(boundaries):
        inst.between.observe(between)
        for phase, seconds in phases.items():
            inst._phases[phase][0].observe(seconds)
        inst.boundary("step")
    return inst


# -- the pure functions --------------------------------------------------------

def test_the_mean_and_the_host_chain_over_two_engines(registry):
    snap = registry.snapshot()
    assert pa.mean_ms(snap, pa.BETWEEN) is None
    assert pa.host_chain_ms(snap) is None
    engine(registry, "a", 100, 0.0008, PHASE_SECONDS)
    engine(registry, "b", 300, 0.0004, PHASE_SECONDS)
    snap = registry.snapshot()
    assert pa.mean_ms(snap, pa.BETWEEN) == pytest.approx(0.5)
    # admit + build + dispatch + emit + between, the wait for the device not
    assert pa.host_chain_ms(snap) == pytest.approx(
        1e3 * (0.0001 + 0.0002 + 0.0012 + 0.0004) + 0.5)


def test_block_boundaries_are_not_in_the_divisor(registry):
    inst = engine(registry, "a", 10, 0.001, PHASE_SECONDS)
    before = pa.host_chain_ms(registry.snapshot())
    inst.boundary("prefill")
    inst.boundary("verify")
    assert pa.host_chain_ms(registry.snapshot()) == before


@pytest.mark.parametrize("missing", [pa.BETWEEN,
                                     pa.PHASES + '_sum{model="a",phase="emit"',
                                     "dl4j_decode_boundaries_total"])
def test_the_host_chain_lacking_a_series_reads_none(registry, missing):
    engine(registry, "a", 10, 0.001, PHASE_SECONDS)
    snap = {k: v for k, v in registry.snapshot().items() if missing not in k}
    assert pa.host_chain_ms(snap) is None


# -- the readers ---------------------------------------------------------------

@pytest.mark.parametrize("name", TWO)
def test_a_reader_reads_nothing_from_a_program_without_the_series(registry,
                                                                  name):
    """The parent commit counts boundaries and phases and has no time between
    them: each reader returns None, nothing is raised, and `run.py` leaves the
    metric out of the line."""
    inst = ServingInstruments(registry, "a")
    del registry._metrics["dl4j_decode_between_phases_seconds"]
    inst.boundary("step")
    inst._phases["emit"][0].observe(0.001)
    assert run.load_reader(name)({}) is None


def test_the_readers_over_an_engines_life(registry):
    engine(registry, "bench-decoder", 1000, 0.001, PHASE_SECONDS)
    read = lambda name: run.load_reader(name)({})  # noqa: E731
    assert read("serve.between_phases_ms_mean") == pytest.approx(1.0)
    assert read("serve.host_chain_ms_mean") == pytest.approx(2.9)


# -- the manifest --------------------------------------------------------------

def test_the_two_entries_are_appended_without_a_list(manifest):
    per = manifest["per_layer"]
    assert [m["name"] for m in per[-2:]] == TWO
    for m in per[-2:]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_counter", "layer": "engine",
                     "moves": "itl_p95_ms"}
        assert callable(run.load_reader(m["name"]))


@pytest.mark.parametrize("cell", SERVING + TRAINING)
def test_every_serving_cell_reports_them_and_no_training_cell(manifest,
                                                              cell):
    assert cell in [w["name"] for w in manifest["workloads"]]
    names = [m["name"] for m in run.cell_metrics(manifest, "per_layer", cell)]
    assert [n for n in names if n in TWO] == (TWO if cell in SERVING else [])
