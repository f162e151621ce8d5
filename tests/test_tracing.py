"""End-to-end tracing + XLA cost attribution tests (ISSUE 10).

The tentpole contracts: a sampled HTTP predict yields ONE connected
span tree covering queue-wait / coalesce / replica-queue / execute; a
decode request yields per-token-boundary child spans; an ETL-worker
span parents to the training trace ACROSS the fork boundary; latency
histograms expose trace-id exemplars; `cost_analysis()` FLOPs agree
with the analytic formulas within 10%; and
``telemetry.disable()`` means ZERO tracer calls per step and per
request with bit-identical training math.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn import (
    DenseLayer, LossFunction, MultiLayerNetwork, NeuralNetConfiguration,
    OutputLayer)
from deeplearning4j_tpu.serving import (
    AdmissionController, BucketLadder, InferenceSession, ModelRegistry,
    ShedError)
from deeplearning4j_tpu.telemetry import costmodel, flight, prometheus, \
    tracing
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.telemetry.tracing import Tracer


def _mlp(seed=7, n_in=16, n_out=4):
    conf = (NeuralNetConfiguration.Builder().seed(seed).list()
            .layer(DenseLayer.Builder().nIn(n_in).nOut(8)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(n_out).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n=3, n_in=16, n_out=4, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(batch, n_in)).astype(np.float32),
             np.eye(n_out, dtype=np.float32)[
                 rng.integers(0, n_out, batch)])
            for _ in range(n)]


@pytest.fixture
def traced():
    """Fresh tracer + registry, sampling every trace; restores the
    process state (including the default 1-in-100 sampler) after."""
    reg = MetricsRegistry()
    prev_reg = telemetry.set_registry(reg)
    tr = Tracer()
    prev_tr = tracing.set_tracer(tr)
    telemetry.enable()
    tracing.configure(enabled=True, sample_rate=1.0)
    yield tr, reg
    tracing.set_tracer(prev_tr)
    telemetry.set_registry(prev_reg)
    tracing.configure(enabled=True, sample_rate=0.01)


def _scrape(reg):
    """{sample_name: value} including scrape-only (local) families —
    the cost gauges are excluded from snapshot()/aggregation by design
    (whether a host attributes depends on its measured step time)."""
    return prometheus.parse(prometheus.render(registry=reg,
                                              collect_system=False))


def _tree_connected(spans):
    """Every non-root span's parent is another span in the set; exactly
    one root."""
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] not in by_id]
    orphans = [s for s in spans
               if s["parent_id"] is not None and s["parent_id"] not in by_id]
    return len(roots) == 1 and not orphans, roots


# ---------------------------------------------------------------------------
# core: ids, traceparent, sampling, ring
# ---------------------------------------------------------------------------

class TestTracingCore:
    def test_traceparent_roundtrip(self, traced):
        span = tracing.start_trace("t")
        hdr = span.traceparent()
        tid, sid, sampled = tracing.parse_traceparent(hdr)
        assert (tid, sid, sampled) == (span.trace_id, span.span_id, True)

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-zz-11-01", "00-" + "0" * 32 + "-" +
        "1" * 16 + "-01", "ff-" + "a" * 32 + "-" + "b" * 16 + "-01"])
    def test_malformed_traceparent_rejected(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_upstream_unsampled_flag_wins(self, traced):
        hdr = "00-" + "a" * 32 + "-" + "b" * 16 + "-00"
        assert tracing.start_trace("t", traceparent=hdr) is None

    def test_upstream_sampled_joins_trace(self, traced):
        hdr = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
        span = tracing.start_trace("t", traceparent=hdr)
        assert span.trace_id == "a" * 32
        assert span.parent_id == "b" * 16

    def test_head_sampler_interval(self, traced):
        tracing.configure(sample_rate=0.25)
        kept = sum(tracing.start_trace("t") is not None
                   for _ in range(40))
        assert kept == 10   # deterministic 1-in-4 counter
        tracing.configure(sample_rate=0.0)
        assert tracing.start_trace("t") is None

    def test_ring_bounded(self, traced):
        tr, _ = traced
        tr.resize(8)
        for i in range(20):
            tr.emit(f"s{i}", "t" * 32, None, 0.0, 1.0)
        assert len(tr) == 8
        names = [s["name"] for s in tr.spans()]
        assert names == [f"s{i}" for i in range(12, 20)]

    def test_span_context_manager_sets_current(self, traced):
        assert tracing.current() is None
        with tracing.start_trace("outer") as outer:
            ctx = tracing.current()
            assert ctx.trace_id == outer.trace_id
            with tracing.span("inner") as inner:
                assert inner.parent_id == outer.span_id
        assert tracing.current() is None
        tr, _ = traced
        assert [s["name"] for s in tr.spans()] == ["inner", "outer"]

    def test_error_status_on_raise(self, traced):
        tr, _ = traced
        with pytest.raises(ValueError):
            with tracing.start_trace("boom"):
                raise ValueError("nope")
        rec = tr.spans()[-1]
        assert rec["status"] == "error"
        assert "ValueError" in rec["attrs"]["error"]

    def test_span_context_pickles(self, traced):
        import pickle

        ctx = tracing.SpanContext("a" * 32, "b" * 16)
        back = pickle.loads(pickle.dumps(ctx))
        assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)


# ---------------------------------------------------------------------------
# serving: the HTTP predict span tree (acceptance criterion)
# ---------------------------------------------------------------------------

class TestHttpPredictTrace:
    @pytest.fixture
    def server(self, traced):
        from deeplearning4j_tpu.ui.server import UIServer

        net = _mlp()
        session = InferenceSession(admission=AdmissionController())
        session.register("m", net, example_shape=(16,),
                         ladder=BucketLadder((1, 4)), warmup=True,
                         replicas=2)
        ui = UIServer.getInstance().serveModels(session)
        ui.start(port=0)
        yield f"http://127.0.0.1:{ui.port}", session
        session.close()
        ui.stop()
        UIServer._instance = None

    def _predict(self, url, headers=None):
        body = json.dumps({"instances": [[0.1] * 16]}).encode()
        req = urllib.request.Request(
            url + "/serving/v1/models/m:predict", data=body,
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        return urllib.request.urlopen(req)

    def test_sampled_predict_returns_connected_tree(self, server):
        url, _ = server
        resp = self._predict(url)
        hdr = resp.headers.get("traceparent")
        assert hdr, "sampled predict must return a traceparent header"
        tid = hdr.split("-")[1]
        raw = urllib.request.urlopen(
            url + f"/debug/traces?trace_id={tid}").read().decode()
        spans = [json.loads(line) for line in raw.splitlines() if line]
        connected, roots = _tree_connected(spans)
        assert connected, spans
        assert roots[0]["name"] == "http.predict"
        names = {s["name"] for s in spans}
        # the acceptance phases: queue-wait, coalesce, replica-queue,
        # execute — plus the handler root and the admission hop
        assert {"http.predict", "serving.admission",
                "serving.queue_wait", "serving.coalesce",
                "serving.replica_queue", "serving.execute"} <= names
        # phases nest inside the request window
        root = roots[0]
        for s in spans:
            if s is not root:
                assert s["start"] >= root["start"] - 1e-4
                assert s["end"] <= root["end"] + 1e-4

    def test_latency_histogram_exposes_exemplar(self, server):
        url, _ = server
        resp = self._predict(url)
        tid = resp.headers["traceparent"].split("-")[1]
        # explicit opt-in (?exemplars=1) carries the exemplar suffixes;
        # a default scrape — even one whose Accept advertises
        # OpenMetrics, as stock Prometheus does — stays bare 0.0.4
        text = urllib.request.urlopen(
            url + "/metrics?exemplars=1").read().decode()
        wait_lines = [line for line in text.splitlines()
                      if line.startswith("dl4j_serving_queue_wait_seconds"
                                         "_bucket")
                      and "trace_id=" in line]
        assert wait_lines, "queue-wait histogram must expose an exemplar"
        assert any(tid in line for line in wait_lines)
        # plain scrape stays bare 0.0.4 (and still parses) even when
        # the client's Accept header advertises OpenMetrics
        req = urllib.request.Request(
            url + "/metrics",
            headers={"Accept": "application/openmetrics-text"})
        plain = urllib.request.urlopen(req).read().decode()
        assert "trace_id=" not in plain
        assert "0.0.4" in urllib.request.urlopen(
            url + "/metrics").headers["Content-Type"]
        prometheus.parse(text)   # exemplar suffixes must not break parse

    def test_upstream_traceparent_honored(self, server):
        url, _ = server
        upstream = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        resp = self._predict(url, headers={"traceparent": upstream})
        assert resp.headers["traceparent"].split("-")[1] == "ab" * 16

    def test_unsampled_predict_no_header_no_spans(self, server, traced):
        tr, _ = traced
        url, _ = server
        tracing.configure(sample_rate=0.0)
        tr.clear()
        resp = self._predict(url)
        assert resp.headers.get("traceparent") is None
        assert len(tr) == 0

    def test_shed_flight_event_names_actor(self, server, traced):
        url, session = server
        rec = flight.get_recorder()
        rec.clear()
        session.admission.set_budget("m", 1, {"high": 1.0, "normal": 0.5,
                                              "batch": 0.5})
        # one standing high-priority ticket fills the whole budget, so
        # the next best-effort request is shed
        ticket = session.admission.admit("m", "high")
        try:
            with tracing.start_trace("client") as root:
                with pytest.raises(ShedError):
                    session.predict("m", np.zeros((1, 16), np.float32),
                                    priority="batch")
        finally:
            ticket.release()
        sheds = rec.events("shed")
        assert sheds, "shed decision must land in the flight recorder"
        ev = sheds[-1]
        assert ev["model"] == "m"
        assert ev["priority"] == "batch"
        assert ev["trace_id"] == root.trace_id


# ---------------------------------------------------------------------------
# decode: per-token-boundary child spans + wedge detection
# ---------------------------------------------------------------------------

class TestDecodeTrace:
    def test_boundary_spans(self, traced):
        from deeplearning4j_tpu.serving.decode import (
            DecodeEngine, TransformerDecodeModel)

        model = TransformerDecodeModel.init(
            vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=2, page=8, max_pages_per_slot=4)
        eng = DecodeEngine(model, name="d").warmup()
        try:
            root = tracing.start_trace("client.decode")
            with root:
                tokens = eng.decode([1, 2, 3], 5, timeout=30)
            assert len(tokens) == 5
            tr, _ = traced
            spans = [s for s in tr.spans(root.trace_id)
                     if s["span_id"] != root.span_id]
            names = [s["name"] for s in spans]
            # a 3-token prompt prefills over 2 boundaries (the third
            # prompt token's boundary generates), then 5 decode tokens
            assert names.count("decode.prefill") == 2
            assert names.count("decode.token") == 5
            assert names.count("decode.queue") == 1
            assert all(s["parent_id"] == root.span_id for s in spans)
        finally:
            eng.close()

    def test_boundary_span_cap_aggregates_tail(self, traced):
        # one long sampled generation must not evict every other trace
        # from the bounded ring: boundaries past the cap fold into one
        # aggregate decode.tokens span
        from deeplearning4j_tpu.serving.decode import (
            DecodeEngine, TransformerDecodeModel)

        model = TransformerDecodeModel.init(
            vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=1, page=8, max_pages_per_slot=4)
        eng = DecodeEngine(model, name="capped").warmup()
        eng.boundary_span_cap = 4
        try:
            root = tracing.start_trace("client")
            with root:
                tokens = eng.decode([1, 2], 10, timeout=30)
            assert len(tokens) == 10
            tr, _ = traced
            spans = [s for s in tr.spans(root.trace_id)
                     if s["span_id"] != root.span_id]
            boundary = [s for s in spans
                        if s["name"] in ("decode.prefill",
                                         "decode.token")]
            agg = [s for s in spans if s["name"] == "decode.tokens"]
            assert len(boundary) == 4
            # 1 prefill + 10 decode boundaries total, 4 emitted -> 7
            assert len(agg) == 1
            assert agg[0]["attrs"]["boundaries"] == 7
        finally:
            eng.close()

    def test_wedged_engine_reports_degraded(self, traced):
        import threading

        from deeplearning4j_tpu.serving.decode import DecodeEngine
        from deeplearning4j_tpu.telemetry import health

        release = threading.Event()

        class _BlockingModel:
            uses_pages = False
            page = None
            max_slots = 1

            def init_state(self):
                return []

            def reset_slot(self, state, slot):
                return state

            def step(self, state, tokens, pos, table):
                release.wait(10.0)
                return np.zeros(1, np.int32), state

        eng = DecodeEngine(_BlockingModel(), name="wedgy",
                           wedge_timeout=0.05)
        session = InferenceSession()
        session.register_decoder("wedgy", eng, warmup=False)
        try:
            eng.submit([1], 1)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                h = eng.health()
                if h["wedged"]:
                    break
                time.sleep(0.02)
            assert h["wedged"] and h["degraded"], h
            payload, status = health.healthz(session)
            assert status == 200            # degraded, not dead
            assert payload["status"] == "degraded"
            assert payload["serving"]["decoders"]["wedgy"]["wedged"]
        finally:
            release.set()
            session.close()

    def test_healthy_engine_not_degraded(self, traced):
        from deeplearning4j_tpu.serving.decode import (
            DecodeEngine, TransformerDecodeModel)
        from deeplearning4j_tpu.telemetry import health

        model = TransformerDecodeModel.init(
            vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=2, page=8, max_pages_per_slot=4)
        eng = DecodeEngine(model, name="ok").warmup()
        session = InferenceSession()
        session.register_decoder("ok", eng, warmup=False)
        try:
            eng.decode([1, 2], 3, timeout=30)
            payload, status = health.healthz(session)
            assert status == 200
            assert payload["status"] == "ok"
            assert not payload["serving"]["decoders"]["ok"]["wedged"]
        finally:
            session.close()


# ---------------------------------------------------------------------------
# replica incidents carry identity
# ---------------------------------------------------------------------------

class TestReplicaFlightIdentity:
    def test_steal_event_names_thief_victim_and_trace(self, traced):
        from deeplearning4j_tpu.serving.batcher import _Request
        from deeplearning4j_tpu.serving.replica import (
            ReplicaSet, _BatchTask)

        rec = flight.get_recorder()
        rec.clear()
        net = _mlp()
        registry = ModelRegistry()
        entry = registry.register("m", net, example_shape=(16,),
                                  ladder=BucketLadder((1, 4)),
                                  warmup=True)
        rset = ReplicaSet(entry, n_replicas=2, warmup=False)
        try:
            with tracing.start_trace("client") as root:
                req = _Request(np.zeros((1, 16), np.float32), None,
                               model="m", trace=tracing.current())
            rset._run_task(rset.replicas[0], _BatchTask([req], None),
                           stolen="r1")
            assert req.future.result(timeout=5) is not None
            steals = rec.events("steal")
            assert steals, "a stolen batch must record a steal event"
            ev = steals[-1]
            assert ev["model"] == "m"
            assert ev["replica"] == "r0"
            assert ev["victim"] == "r1"
            assert ev["trace_id"] == root.trace_id
        finally:
            rset.close()

    def test_dead_replica_degrades_healthz(self, traced):
        from deeplearning4j_tpu.telemetry import health

        net = _mlp()
        session = InferenceSession()
        session.register("m", net, example_shape=(16,),
                         ladder=BucketLadder((1, 4)), warmup=True,
                         replicas=2)
        try:
            session.predict("m", np.zeros((1, 16), np.float32))
            payload, status = health.healthz(session)
            assert payload["status"] == "ok"
            b = session._batchers[("m", 1)]
            b.executor.replicas[0].dead = True
            payload, status = health.healthz(session)
            assert status == 200
            assert payload["status"] == "degraded"
            row = payload["serving"]["replica_sets"]["m:v1"]
            assert row["dead"] == ["r0"] and row["live"] == 1
        finally:
            session.close()


# ---------------------------------------------------------------------------
# training: fit trace, ETL fork boundary, prefetch, checkpoints
# ---------------------------------------------------------------------------

class TestTrainingTrace:
    def test_fit_root_and_step_spans(self, traced):
        tr, reg = traced
        net = _mlp()
        net.fit(_batches(3), 2)
        spans = tr.spans()
        roots = [s for s in spans if s["name"] == "train.fit"]
        steps = [s for s in spans if s["name"] == "train.step"]
        assert len(roots) == 1
        assert len(steps) == 6
        assert all(s["trace_id"] == roots[0]["trace_id"] for s in steps)
        assert all(s["parent_id"] == roots[0]["span_id"] for s in steps)
        # step histogram carries the trace-id exemplar
        text = prometheus.render(registry=reg, exemplars=True,
                                 collect_system=False)
        assert any("dl4j_step_seconds_bucket" in line
                   and roots[0]["trace_id"] in line
                   for line in text.splitlines())

    def test_prefetch_producer_joins_trace(self, traced):
        from deeplearning4j_tpu.datasets import ListDataSetIterator

        tr, _ = traced
        from deeplearning4j_tpu.datasets.dataset import DataSet

        data = ListDataSetIterator(
            [DataSet(f, l) for f, l in _batches(4)], 8)
        net = _mlp()
        net.fit(data, 1)
        spans = tr.spans()
        prep = [s for s in spans if s["name"] == "prefetch.prepare"]
        roots = [s for s in spans if s["name"] == "train.fit"]
        assert roots
        if prep:   # auto-wrap engaged (default prefetch depth > 0)
            assert all(s["trace_id"] == roots[0]["trace_id"]
                       for s in prep)

    def test_etl_worker_spans_cross_fork(self, traced, tmp_path):
        from tests.test_datavec import _write_image_tree

        from deeplearning4j_tpu.datasets import (
            FileSplit, ParallelImageDataSetIterator)

        _write_image_tree(tmp_path, n_per_class=6)
        tr, _ = traced
        root = tracing.start_trace("train.fit")
        with root:
            it = ParallelImageDataSetIterator(
                FileSplit(str(tmp_path)), 8, 8, 3, batchSize=4,
                numWorkers=2)
            n = 0
            while it.hasNext():
                it.next()
                n += 1
            it.close()
        assert n == 3
        decode = [s for s in tr.spans(root.trace_id)
                  if s["name"] == "etl.decode"]
        # one span per decoded batch, produced in the WORKER PROCESSES
        # and materialized parent-side, parented to the training trace
        assert len(decode) == n
        assert all(s["parent_id"] == root.span_id for s in decode)
        assert {s["attrs"]["worker"] for s in decode} == {0, 1}

    def test_elastic_checkpoint_spans_join_trace(self, traced, tmp_path):
        from deeplearning4j_tpu.parallel.elastic import ElasticTrainer

        tr, _ = traced
        net = _mlp()
        trainer = ElasticTrainer(net, str(tmp_path),
                                 everyNIterations=2, asyncSave=True)
        trainer.fit(_batches(4), 2)
        trainer.close()
        spans = tr.spans()
        roots = [s for s in spans if s["name"] == "train.elastic"]
        assert len(roots) == 1
        tid = roots[0]["trace_id"]
        names = {s["name"] for s in spans if s["trace_id"] == tid}
        assert "train.fit" in names
        assert "ckpt.snapshot" in names
        assert "ckpt.write" in names       # the background-writer half
        connected, _ = _tree_connected(
            [s for s in spans if s["trace_id"] == tid])
        assert connected


# ---------------------------------------------------------------------------
# disabled contract: zero tracer calls, bit-identical math
# ---------------------------------------------------------------------------

class _CountingStubTracer:
    calls = 0

    def __getattr__(self, name):
        type(self).calls += 1
        raise AssertionError(f"tracer touched while disabled: {name}")


class _CountingStubRegistry:
    calls = 0

    def __getattr__(self, name):
        type(self).calls += 1
        raise AssertionError(f"registry touched while disabled: {name}")


class TestDisabledContract:
    def test_zero_tracer_calls_and_bit_identical(self, traced,
                                                 monkeypatch):
        import jax

        from deeplearning4j_tpu.models.bert import (
            BertConfig, BertTrainer, synthetic_mlm_batch)
        from deeplearning4j_tpu.parallel.mesh import MeshConfig
        from deeplearning4j_tpu.serving.decode import (
            TransformerDecodeModel)
        from deeplearning4j_tpu.telemetry import registry as registry_mod

        X, y = _batches(1)[0]
        tracing.configure(sample_rate=1.0)
        n1 = _mlp()
        n1.fit([(X, y), (X, y)], 2)
        p1 = np.asarray(n1.params())

        cfg = BertConfig(vocab_size=200, hidden=32, num_layers=1,
                         num_heads=2, ffn=64, max_len=16)
        tok, lab = synthetic_mlm_batch(cfg, 2, 16, seed=0)
        timers = []
        monkeypatch.setattr(
            registry_mod.Timer, "__enter__",
            lambda self: timers.append(self.name) or self)

        _CountingStubTracer.calls = _CountingStubRegistry.calls = 0
        telemetry.disable()
        prev = tracing.set_tracer(_CountingStubTracer())
        prev_reg = telemetry.set_registry(_CountingStubRegistry())
        try:
            n2 = _mlp()
            n2.fit([(X, y), (X, y)], 2)
            session = InferenceSession()
            session.register("m", n2, example_shape=(16,),
                             ladder=BucketLadder((1, 4)), warmup=True)
            session.predict("m", X)
            # a decode boundary and a BertTrainer step (ISSUE 26): no
            # phase Timer, no count, no span
            session.register_decoder("d", TransformerDecodeModel.init(
                vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64,
                max_slots=2, page=8, max_pages_per_slot=4))
            assert len(session.decoder("d").decode([1, 2, 3], 4,
                                                   timeout=60)) == 4
            session.close()
            trainer = BertTrainer(cfg, MeshConfig(
                data=1, devices=jax.devices()[:1]).build(), lr=1e-4)
            float(trainer.train_step(tok, lab))
        finally:
            tracing.set_tracer(prev)
            telemetry.set_registry(prev_reg)
            telemetry.enable()
        assert _CountingStubTracer.calls == 0
        assert _CountingStubRegistry.calls == 0
        assert timers == []
        np.testing.assert_array_equal(p1, np.asarray(n2.params()))

    def test_sampled_off_emits_nothing(self, traced):
        tr, _ = traced
        tracing.configure(sample_rate=0.0)
        net = _mlp()
        net.fit(_batches(2), 1)
        assert len(tr) == 0


# ---------------------------------------------------------------------------
# cost attribution (acceptance: within 10% of the analytic FLOPs)
# ---------------------------------------------------------------------------

@pytest.fixture
def cost_env(traced):
    costmodel.configure(min_step_seconds=0.0, peak_flops=1e12)
    yield traced
    costmodel.configure(min_step_seconds=0.02)
    costmodel.set_peak_flops(None)


class TestCostModel:
    def test_fit_loop_publishes_flops_and_mfu(self, cost_env):
        _, reg = cost_env
        net = _mlp()
        net.fit(_batches(3), 2)
        snap = _scrape(reg)
        flops = snap.get('dl4j_flops_per_step{executable="fit"}')
        mfu = snap.get('dl4j_mfu{executable="fit"}')
        assert flops and flops > 0
        assert mfu and 0 < mfu < 1
        # scrape-only: per-host attribution must not join the cross-host
        # identical-instrument-set aggregation
        assert 'dl4j_flops_per_step{executable="fit"}' not in \
            reg.snapshot()

    def test_sharded_loop_publishes_flops_and_mfu(self, cost_env):
        # the sharded loop records through the Timer span, not
        # record_step — its MFU refresh is a separate code path
        from deeplearning4j_tpu.parallel import ShardedTrainer

        _, reg = cost_env
        trainer = ShardedTrainer(_mlp())
        trainer.fit(_batches(3), 2)
        snap = _scrape(reg)
        assert snap.get('dl4j_flops_per_step{executable="sharded"}',
                        0) > 0
        assert 0 < snap.get('dl4j_mfu{executable="sharded"}', 0) < 1

    def test_bert_flops_within_10pct_of_analytic(self, cost_env):
        import jax
        import jax.numpy as jnp

        from benchmark.lib import arith
        from deeplearning4j_tpu.models.bert import (
            BertConfig, BertTrainer, mlm_gather, synthetic_mlm_batch)
        from deeplearning4j_tpu.parallel.mesh import MeshConfig

        _, reg = cost_env
        cfg = BertConfig(vocab_size=2000, hidden=128, num_layers=2,
                         num_heads=4, ffn=512, max_len=128)
        batch, seq = 4, 128
        mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
        trainer = BertTrainer(cfg, mesh, lr=1e-4)
        tok, lab = synthetic_mlm_batch(cfg, batch, seq, seed=0)
        n_masked = trainer._max_preds(seq)
        # the engine's own jit_step, lowered on what train_step hands it
        args = (trainer.params, trainer.opt, jnp.asarray(tok, jnp.int32),
                *mlm_gather(lab, max_preds=n_masked),
                jax.random.key(1, impl="rbg"), jnp.asarray(0, jnp.int32))
        flops = costmodel.step_cost("bert", trainer._build(), args,
                                    cache={})
        assert flops and flops > 0
        assert _scrape(reg).get(
            'dl4j_flops_per_step{executable="bert"}') == flops
        analytic = arith.bert_train_flops_per_step(
            cfg.hidden, cfg.ffn, cfg.num_layers, cfg.vocab_size, batch,
            seq, n_masked)
        assert abs(flops - analytic) / analytic < 0.10, (flops, analytic)

    @pytest.mark.slow
    def test_resnet50_flops_within_10pct_of_analytic(self, cost_env):
        import jax

        from deeplearning4j_tpu.models.zoo import ResNet50
        from deeplearning4j_tpu.telemetry import health as _health

        net = ResNet50(numClasses=1000).init()
        step = net._build_train_step(_health.INACTIVE)
        b = 1
        out = net.conf.outputs[0]
        args = (net._params, net._states, net._opt_states,
                net._prec_state,
                {"in": np.zeros((b, 3, 224, 224), np.float32)},
                {out: np.zeros((b, 1000), np.float32)},
                {out: np.ones((b,), np.float32)},
                jax.random.key(1), 0)
        flops = costmodel.step_cost("resnet50", step, args, cache={})
        # ResNet-50 forward = 4.1 GMACs per 224x224 image = 8.2e9 FLOPs
        # in the 2 x MAC convention of XLA's cost model and of the chip's
        # quoted peak; a training step is about 3 x forward
        analytic = 3 * 8.2e9 * b
        assert flops and abs(flops - analytic) / analytic < 0.10, (
            flops, analytic)

    def test_servable_warmup_publishes_executable_bytes(self, cost_env):
        _, reg = cost_env
        net = _mlp()
        session = InferenceSession()
        session.register("m", net, example_shape=(16,),
                         ladder=BucketLadder((1, 4)), warmup=True)
        try:
            snap = _scrape(reg)
            flops_keys = [k for k in snap
                          if k.startswith("dl4j_flops_per_step")
                          and "m:v1:" in k]
            byte_keys = [k for k in snap
                         if k.startswith("dl4j_executable_bytes")
                         and "m:v1:" in k]
            # one flops sample per warmed bucket shape (1x16 and 4x16)
            assert len(flops_keys) == 2, flops_keys
            assert any('kind="argument"' in k for k in byte_keys)
            assert all(snap[k] > 0 for k in flops_keys)
        finally:
            session.close()

    def test_throttle_skips_fast_steps(self, traced):
        _, reg = traced
        costmodel.configure(min_step_seconds=10.0)   # nothing qualifies
        try:
            net = _mlp()
            net.fit(_batches(3), 2)
            snap = _scrape(reg)
            assert 'dl4j_flops_per_step{executable="fit"}' not in snap
        finally:
            costmodel.configure(min_step_seconds=0.02)


class TestPeakTable:
    """The MFU denominator is keyed by the device_kind string a chip
    reports (ISSUE 21): a v5e says "TPU v5 lite"; an unknown TPU is an
    error, the CPU has no peak."""

    @staticmethod
    def _stub(monkeypatch, platform, kind):
        import types

        import jax

        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
        monkeypatch.delenv("DL4J_PEAK_FLOPS", raising=False)
        costmodel.set_peak_flops(None)

    def test_v5e_kind_resolves_to_its_published_peak(self, monkeypatch):
        self._stub(monkeypatch, "tpu", "TPU v5 lite")
        try:
            assert costmodel.peak_flops() == 197e12
        finally:
            costmodel.set_peak_flops(None)

    def test_unknown_tpu_kind_raises(self, monkeypatch):
        self._stub(monkeypatch, "tpu", "TPU v9 imaginary")
        with pytest.raises(LookupError, match="TPU v9 imaginary"):
            costmodel.peak_flops()
        # the override is the way out, not a silent None
        monkeypatch.setenv("DL4J_PEAK_FLOPS", "1e15")
        try:
            assert costmodel.peak_flops() == 1e15
        finally:
            costmodel.set_peak_flops(None)

    def test_cpu_has_no_peak_and_mfu_stays_unpublished(self, monkeypatch):
        self._stub(monkeypatch, "cpu", "cpu")
        try:
            assert costmodel.peak_flops() is None
            assert costmodel.publish_mfu("fit", 1e9, 0.01) is None
        finally:
            costmodel.set_peak_flops(None)


# ---------------------------------------------------------------------------
# /debug/traces route
# ---------------------------------------------------------------------------

class TestTraceExport:
    def test_export_jsonl_and_filter(self, traced):
        tr, _ = traced
        a = tracing.start_trace("a")
        with a:
            pass
        b = tracing.start_trace("b")
        with b:
            pass
        full = [json.loads(line)
                for line in tracing.export_jsonl().splitlines() if line]
        assert {s["name"] for s in full} == {"a", "b"}
        only_a = [json.loads(line)
                  for line in
                  tracing.export_jsonl(trace_id=a.trace_id).splitlines()
                  if line]
        assert [s["name"] for s in only_a] == ["a"]
