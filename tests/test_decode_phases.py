"""The decode engine's phases and counts, and `BertTrainer.train_step`'s two
spans (ISSUE 26): what the engine counts once a boundary adds up to what its
requests were given, on each of its three executables, and the spans stand in
a profiler trace under the names the benchmark's readers look for. Since
ISSUE 31 a plain engine keeps one token step in flight: each phase is still
observed once a delivered boundary, `readback` and `emit` one iteration after
the boundary's `dispatch`."""

import os
import sys

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.serving.decode import (DecodeEngine,
                                               TransformerDecodeModel)
from deeplearning4j_tpu.serving.speculative import SpeculativeConfig
from deeplearning4j_tpu.telemetry.registry import (DECODE_PHASES,
                                                   MetricsRegistry)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib.program_spans import sample_sum  # noqa: E402

REQUESTS = [(7, 5), (2, 3), (11, 4), (1, 6)]   # prompt length, max_new


def _model(seed=0):
    return TransformerDecodeModel.init(
        vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64, seed=seed,
        max_slots=2, page=8, max_pages_per_slot=4)


def _sample(snap, name, **labels):
    return sample_sum(snap, name, **labels) or 0.0


@pytest.fixture(scope="module", params=["step", "chunk", "verify"])
def served(request):
    """One tiny engine run to completion on a registry of its own: the
    snapshot it leaves, what its requests were given, and how often each
    boundary ran by a count kept outside the engine."""
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    telemetry.enable()
    options = {"step": {}, "chunk": {"chunk": 4},
               "verify": {"speculative": SpeculativeConfig(
                   draft=_model(), k=2)}}[request.param]
    eng = DecodeEngine(_model(), name="phases",
                       instruments=telemetry.serving_instruments("phases"),
                       **options).warmup()
    calls = {}
    for name in ("_step_boundary", "_speculative_boundary"):
        def counted(inst, name=name, inner=getattr(eng, name)):
            calls[name] = calls.get(name, 0) + 1
            return inner(inst)
        setattr(eng, name, counted)
    rng = np.random.default_rng(3)
    try:
        reqs = [eng.submit([int(t) for t in rng.integers(1, 32, size=n)], m)
                for n, m in REQUESTS]
        answers = [r.result(timeout=120.0) for r in reqs]
    finally:
        eng.close()
        telemetry.set_registry(prev)
    assert [len(a) for a in answers] == [m for _, m in REQUESTS]
    return {"mode": request.param, "snap": reg.snapshot(), "calls": calls}


def test_boundaries_count_the_iterations(served):
    snap, calls = served["snap"], served["calls"]
    steps = _sample(snap, "dl4j_decode_boundaries_total", model="phases",
                    executable="step")
    verifies = _sample(snap, "dl4j_decode_boundaries_total", model="phases",
                       executable="verify")
    # every iteration of the loop ends in one token or one verify boundary,
    # and a verify boundary with nobody ready falls through to the token step
    assert steps == calls.get("_step_boundary", 0)
    if served["mode"] == "verify":
        assert 0 < verifies <= calls["_speculative_boundary"]
    else:
        assert verifies == 0 and "_speculative_boundary" not in calls


def test_positions_add_up_to_what_the_requests_were_given(served):
    snap = served["snap"]
    by = {(exe, kind): _sample(snap, "dl4j_decode_positions_total",
                               model="phases", executable=exe, kind=kind)
          for exe in ("step", "prefill", "verify")
          for kind in ("prompt", "answer")}
    # the last answer token is never fed
    assert sum(by.values()) == sum(n + m - 1 for n, m in REQUESTS)
    assert by["step", "prompt"] + by["prefill", "prompt"] \
        + by["verify", "prompt"] == sum(n for n, _ in REQUESTS)
    assert by["prefill", "answer"] == 0
    if served["mode"] == "step":
        assert by["prefill", "prompt"] == 0 and by["verify", "answer"] == 0
    else:
        # a block takes up to four prompt tokens and leaves the last
        assert by["prefill", "prompt"] > 0
    if served["mode"] == "verify":
        assert by["verify", "answer"] > 0


def test_every_request_waited_in_the_queue_once(served):
    snap = served["snap"]
    assert _sample(snap, "dl4j_decode_queue_wait_seconds_count",
                   model="phases") == len(REQUESTS)
    assert _sample(snap, "dl4j_decode_queue_wait_seconds_sum",
                   model="phases") > 0


def test_five_phases_one_observation_a_boundary(served):
    snap = served["snap"]
    count = {p: _sample(snap, "dl4j_decode_boundary_seconds_count",
                        model="phases", phase=p) for p in DECODE_PHASES}
    boundaries = _sample(snap, "dl4j_decode_boundaries_total")
    assert boundaries > 0
    for p in ("build", "dispatch", "readback", "emit"):
        assert count[p] == boundaries, (p, count)
    # one admit an iteration. A plain engine's iteration dispatches one
    # boundary and delivers the one before it, or both where nothing is left
    # to dispatch after it; with a block executable an iteration may hold a
    # prefill boundary and then a token boundary
    if served["mode"] == "step":
        assert count["admit"] == boundaries
    else:
        assert 0 < count["admit"] < boundaries


def test_a_plain_engine_overlaps_and_a_serial_one_does_not(served):
    snap = served["snap"]
    steps = _sample(snap, "dl4j_decode_boundaries_total", model="phases",
                    executable="step")
    overlapped = _sample(snap, "dl4j_decode_overlapped_boundaries_total",
                         model="phases")
    if served["mode"] == "step":
        # four requests on two slots, submitted at once: the engine drains
        # the launch in flight only where both slots end on one boundary
        assert steps - len(REQUESTS) <= overlapped < steps
    else:
        assert overlapped == 0


def test_pool_fill_is_summed_once_a_boundary(served):
    snap = served["snap"]
    boundaries = _sample(snap, "dl4j_decode_boundaries_total")
    fill = _sample(snap, "dl4j_decode_kv_fill_sum", model="phases")
    # two slots of at most 3 of the pool's 8 pages each, and the last
    # boundary leaves the pool empty
    assert 0 < fill / boundaries < 0.75


def test_train_step_writes_its_two_spans_into_the_host_plane(tmp_path):
    import jax

    from benchmark.lib import program_spans, trace
    from deeplearning4j_tpu.models.bert import (BertConfig, BertTrainer,
                                                synthetic_mlm_batch)
    from deeplearning4j_tpu.parallel.mesh import MeshConfig

    cfg = BertConfig(vocab_size=200, hidden=32, num_layers=1, num_heads=2,
                     ffn=64, max_len=16)
    trainer = BertTrainer(cfg, MeshConfig(
        data=1, devices=jax.devices()[:1]).build(), lr=1e-4)
    tok, lab = synthetic_mlm_batch(cfg, 2, 16, seed=0)
    float(trainer.train_step(tok, lab))         # compile outside the trace
    telemetry.enable()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            loss = trainer.train_step(tok, lab)
        float(loss)
    host = trace.load(trace.find_xplane(str(tmp_path)))["host"]
    if not any(host.values()):
        pytest.skip("the CPU profiler wrote no host plane")
    names = [n for events in host.values() for n, _, _ in events]
    assert names.count("dl4j.train.gather") == 3
    assert names.count("dl4j.train.dispatch") == 3
    steps = program_spans.train_host_seconds(host, 0.0, float("inf"))
    assert len(steps) == 3 and all(s > 0 for s in steps)
