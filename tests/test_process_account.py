"""The process's own account (ISSUE 36): garbage collections through a
`gc.callbacks` entry, the stages on the way to an executable through jax's
monitoring events, and the start-up account frozen at the first train step or
decode boundary while the running totals go on."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import registry as registry_mod
from deeplearning4j_tpu.telemetry.registry import GC_SPAN, MetricsRegistry

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
STARTUP_PARTS = ("total", "trace", "lower", "compile", "cache_load")


@pytest.fixture
def registry():
    """A registry of its own with the process-wide series bound to it, and
    the start-up account open."""
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    telemetry.enable()
    frozen = registry_mod._startup["frozen"]
    registry_mod._startup["frozen"] = False
    yield reg
    registry_mod._startup["frozen"] = frozen
    telemetry.set_registry(prev)
    telemetry.enable()


def stage(snap, name):
    return snap[f'dl4j_compile_stage_seconds_total{{stage="{name}"}}']


def startup(snap):
    return {p: snap.get(f'dl4j_startup_seconds{{part="{p}"}}')
            for p in STARTUP_PARTS}


# -- collections ---------------------------------------------------------------

class RecordedAnnotation:
    seen = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).seen.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        type(self).seen.append(("exit", self.name))


def test_a_collection_raises_the_generation_2_series_and_leaves_a_span(
        registry, monkeypatch):
    RecordedAnnotation.seen = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", RecordedAnnotation)
    before = registry_mod._gc_seconds[0]
    gc.collect()
    monkeypatch.undo()
    snap = registry.snapshot()
    assert snap['dl4j_process_gc_collections_total{generation="2"}'] == 1
    pause = snap['dl4j_process_gc_pause_seconds_total{generation="2"}']
    assert pause > 0
    assert snap["dl4j_process_gc_pause_max_seconds"] >= pause
    # the cell a decode engine reads at each dispatch holds every generation
    assert registry_mod._gc_seconds[0] - before >= pause
    # one span a generation-2 collection, none for the younger ones
    assert RecordedAnnotation.seen == [("enter", GC_SPAN), ("exit", GC_SPAN)]
    gc.collect(0)
    assert registry.snapshot()[
        'dl4j_process_gc_collections_total{generation="0"}'] >= 1
    assert RecordedAnnotation.seen == [("enter", GC_SPAN), ("exit", GC_SPAN)]


def test_the_span_stands_in_a_profiler_trace(registry, tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.lib import trace

    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    host = trace.load(trace.find_xplane(str(tmp_path)))["host"]
    if not any(host.values()):
        pytest.skip("the CPU profiler wrote no host plane")
    names = [n for events in host.values() for n, _, _ in events]
    assert names.count(GC_SPAN) == 1


def test_the_callback_touches_nothing_while_telemetry_is_off():
    class CountingStub:
        calls = 0

        def __getattr__(self, name):
            CountingStub.calls += 1
            raise AssertionError(f"registry.{name} touched while disabled")

    prev = telemetry.set_registry(CountingStub())
    telemetry.disable()
    before = registry_mod._gc_seconds[0]
    try:
        gc.collect()
        jax.jit(lambda x: x - 7)(np.ones(3, np.float32))   # and a compile
        telemetry.startup_done()
        assert CountingStub.calls == 0
        assert registry_mod._gc_seconds[0] == before
    finally:
        telemetry.set_registry(prev)
        telemetry.enable()


# -- the stages on the way to an executable ------------------------------------

def test_a_compile_adds_to_every_stage_and_the_cache_counts_nothing_here(
        registry):
    jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(np.ones(5, np.float32))
    snap = registry.snapshot()
    assert snap["dl4j_compile_total"] >= 1
    assert snap["dl4j_compile_seconds_total"] > 0
    assert stage(snap, "trace") > 0 and stage(snap, "lower") > 0
    # tier-1 runs without the persistent cache: nothing is read from it
    assert stage(snap, "cache_load") == 0
    assert snap["dl4j_compile_cache_hits_total"] == 0


def test_nested_spans_count_once(registry):
    """jax reports a span as it ends, the inner before the outer, and its
    durations nest: the stages hold each span's own time. Spans written by
    hand: a trace of 3 s that holds one of 1 s; a lowering of 2 s that holds
    a trace of 0.5 s; a trace of 4 s that holds a whole compile of a small
    function (trace 0.25, lower 0.25, backend compile 1 s)."""
    span = jax.monitoring.record_event_time_span
    # a span from the epoch claims what this process traced before, and the
    # spans below, a fortnight after it, are claimed by no real one later
    span(COMPILE, 0.0, 0.0)
    t = 1e6
    span(TRACE, t + 1.0, t + 2.0)
    span(TRACE, t + 0.0, t + 3.0)
    snap = registry.snapshot()
    assert stage(snap, "trace") == pytest.approx(3.0)
    span(TRACE, t + 3.5, t + 4.0)
    span(LOWER, t + 3.0, t + 5.0)
    snap = registry.snapshot()
    assert stage(snap, "trace") == pytest.approx(3.5)
    assert stage(snap, "lower") == pytest.approx(1.5)
    span(TRACE, t + 6.0, t + 6.25)
    span(LOWER, t + 6.25, t + 6.5)
    span(COMPILE, t + 6.5, t + 7.5)
    span(TRACE, t + 5.5, t + 9.5)
    snap = registry.snapshot()
    assert stage(snap, "trace") == pytest.approx(3.5 + 0.25 + 2.5)
    assert stage(snap, "lower") == pytest.approx(1.5 + 0.25)
    # the backend compile's seconds come from its duration event alone
    assert snap["dl4j_compile_seconds_total"] == 0


def test_two_threads_spans_are_told_apart(registry):
    """A span claims what its own thread reported inside it and nothing of
    another thread's: a checker's thread traces 1 s inside the 4 s for which
    the engine's thread traces (0.5 s of them in a nested function), reported
    in between. Neither takes from the other, and the checker's lowering
    around its trace claims that one alone."""
    from concurrent.futures import ThreadPoolExecutor

    span = jax.monitoring.record_event_time_span
    span(COMPILE, 0.0, 0.0)
    t = 2e6
    with ThreadPoolExecutor(max_workers=1) as checker:
        span(TRACE, t + 1.0, t + 1.5)                       # engine, inner
        checker.submit(span, TRACE, t + 2.0, t + 3.0).result()
        span(TRACE, t + 0.0, t + 4.0)                       # engine, outer
        assert stage(registry.snapshot(), "trace") == pytest.approx(
            0.5 + 1.0 + 3.5)
        checker.submit(span, LOWER, t + 0.5, t + 3.5).result()
    snap = registry.snapshot()
    assert stage(snap, "lower") == pytest.approx(3.0 - 1.0)
    assert stage(snap, "trace") == pytest.approx(5.0)


def test_a_cache_hit_is_a_compile_event_whose_seconds_are_a_load(registry):
    """jax 0.9 wraps the persistent cache's read in the backend-compile
    event: the events of one hit and one miss, recorded by hand."""
    mon = jax.monitoring
    mon.record_event("/jax/compilation_cache/cache_hits")
    mon.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    mon.record_event_duration_secs(COMPILE, 0.6)
    mon.record_event("/jax/compilation_cache/cache_misses")
    mon.record_event_duration_secs(COMPILE, 7.0)
    telemetry.startup_done()
    snap = registry.snapshot()
    assert snap["dl4j_compile_total"] == 2
    assert snap["dl4j_compile_cache_hits_total"] == 1
    assert snap["dl4j_compile_cache_misses_total"] == 1
    assert stage(snap, "cache_load") == 0.5
    account = startup(snap)
    assert account["cache_load"] == 0.5
    assert account["compile"] == pytest.approx(7.1)
    assert snap['dl4j_startup_executables{outcome="hit"}'] == 1
    assert snap['dl4j_startup_executables{outcome="compiled"}'] == 1


# -- the start-up account ------------------------------------------------------

def test_the_account_freezes_once_and_the_totals_run_on(registry):
    jax.jit(lambda x: x * 2 + 5)(np.ones(3, np.float32))
    assert startup(registry.snapshot())["total"] is None
    telemetry.startup_done()
    snap = registry.snapshot()
    first = startup(snap)
    assert all(v is not None for v in first.values()), first
    assert first["compile"] == snap["dl4j_compile_seconds_total"] > 0
    assert first["trace"] == stage(snap, "trace")
    named = sum(first[p] for p in STARTUP_PARTS[1:])
    assert named <= first["total"]
    compiled = snap['dl4j_startup_executables{outcome="compiled"}']
    assert compiled == snap["dl4j_compile_total"] >= 1
    # a compile after the freeze: the running totals move, the account not
    jax.jit(lambda x: x * 3 - 2)(np.ones(4, np.float32))
    telemetry.startup_done()
    snap = registry.snapshot()
    assert startup(snap) == first
    assert snap['dl4j_startup_executables{outcome="compiled"}'] == compiled
    assert snap["dl4j_compile_total"] > compiled
    assert snap["dl4j_compile_seconds_total"] > first["compile"]
    assert stage(snap, "trace") > first["trace"]


def test_the_first_train_step_freezes_it(registry):
    from deeplearning4j_tpu.models.bert import (BertConfig, BertTrainer,
                                                synthetic_mlm_batch)
    from deeplearning4j_tpu.parallel.mesh import MeshConfig

    cfg = BertConfig(vocab_size=200, hidden=32, num_layers=1, num_heads=2,
                     ffn=64, max_len=16)
    trainer = BertTrainer(cfg, MeshConfig(
        data=1, devices=jax.devices()[:1]).build(), lr=1e-4)
    tok, lab = synthetic_mlm_batch(cfg, 2, 16, seed=0)
    assert startup(registry.snapshot())["total"] is None
    loss = trainer.train_step(tok, lab)
    first = startup(registry.snapshot())
    # frozen once the step is queued: its trace, lowering and compile are in
    assert first["total"] is not None
    assert first["trace"] > 0 and first["lower"] > 0 and first["compile"] > 0
    float(loss)
    float(trainer.train_step(tok, lab))
    assert startup(registry.snapshot()) == first


def test_the_process_age_comes_from_proc_and_covers_the_import():
    age = registry_mod._process_age()
    if age is None:
        pytest.skip("no /proc here")
    import time

    since_import = time.perf_counter() - registry_mod._IMPORTED_AT
    # /proc counts in clock ticks of 10 ms
    assert age >= since_import - 0.02


def test_without_proc_the_total_runs_from_the_modules_import(
        registry, monkeypatch):
    monkeypatch.setattr(registry_mod, "_process_age", lambda: None)
    monkeypatch.setattr(registry_mod, "_IMPORTED_AT",
                        registry_mod.time.perf_counter() - 5.0)
    telemetry.startup_done()
    assert 5.0 <= startup(registry.snapshot())["total"] < 6.0
