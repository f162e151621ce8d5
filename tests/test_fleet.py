"""ISSUE 15 tests: the fleet tier — multi-process router, rolling
canary updates with auto-rollback, and traffic capture.

Fast tier: routing + load spread over in-process workers, the 429
Retry-After / 504 byte-for-byte pass-through (satellite bugfix
verification), traceparent producing ONE connected trace across the
hop, breaker ejection + healthz degradation + re-admission, the
rollout state machine (promote, disagreement rollback, latency
rollback), capture determinism (save → replay → re-save
byte-identical), and the worker admin routes.

Slow tier (armed lock witness): a 3-subprocess-worker fleet where a
SIGKILL mid-soak loses ZERO accepted requests (retries absorb the
death), and a deliberately-regressed canary that auto-rolls back
fleet-wide with the decision visible as flight events and a
dl4j_fleet_rollout_state transition.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.fleet import (
    CaptureReplayIterator, FleetRouter, TrafficCapture, WorkerHandle)
from deeplearning4j_tpu.fleet.capture import load_capture
from deeplearning4j_tpu.fleet.rollout import (
    ROLLOUT_STATES, histogram_quantile)
from deeplearning4j_tpu.fleet.router import (
    TransportFailure, _http, _parse_gauge_sum, spawn_local_workers)
from deeplearning4j_tpu.fleet.worker import (
    LinearServable, WorkerAdmin, build_servable)
from deeplearning4j_tpu.serving import AdmissionController, InferenceSession
from deeplearning4j_tpu.telemetry import flight, tracing
from deeplearning4j_tpu.telemetry.registry import Histogram, log_buckets
from deeplearning4j_tpu.ui.server import UIServer

CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def _spec(scale=2.0, bias=0.0, delay_ms=0.0, shape=(3,), name="m",
          version=1):
    return {"name": name, "version": version, "kind": "linear",
            "scale": scale, "bias": bias, "delay_ms": delay_ms,
            "example_shape": list(shape), "ladder": [1, 4, 8]}


class _InprocWorker:
    """A full worker stack (UIServer + InferenceSession + admin) in
    this process — the fast-tier stand-in for a worker process."""

    def __init__(self, name, specs=(), admission=None):
        self.session = InferenceSession(max_latency=0.0,
                                        admission=admission)
        self.admin = WorkerAdmin(self.session)
        for s in specs:
            self.admin.register_spec(s["name"], s, s["version"])
        self.server = (UIServer().serveModels(self.session)
                       .serveFleetAdmin(self.admin).start(port=0))
        self.handle = WorkerHandle(
            name, f"http://127.0.0.1:{self.server.port}")

    def stop(self):
        self.server.stop()
        self.session.close()


class _Fleet:
    def __init__(self, n=2, specs=None, capture=None, admission=None,
                 **router_kw):
        specs = [_spec()] if specs is None else specs
        self.workers = [_InprocWorker(f"w{i}", specs,
                                      admission=admission)
                        for i in range(n)]
        router_kw.setdefault("poll_interval", 0.05)
        self.router = FleetRouter([w.handle for w in self.workers],
                                  capture=capture, **router_kw)
        self.router.start(port=0)
        self.url = f"http://127.0.0.1:{self.router.port}"
        # the rollout seam needs the poll thread to have discovered
        # the workers' model lists
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if all(w.handle.models for w in self.workers):
                break
            time.sleep(0.02)

    def predict(self, instances, model="m", headers=None, **extra):
        payload = {"instances": instances, **extra}
        return _http(f"{self.url}/serving/v1/models/{model}:predict",
                     body=json.dumps(payload).encode(),
                     headers=headers, timeout=30.0)

    def close(self):
        self.router.close()
        for w in self.workers:
            w.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _drive_until(fleet, ctl, timeout=30.0, instances=((1.0, 2.0, 3.0),)):
    """Send traffic until the rollout goes terminal."""
    deadline = time.monotonic() + timeout
    while not ctl.terminal() and time.monotonic() < deadline:
        fleet.predict([list(i) for i in instances])
        time.sleep(0.005)
    assert ctl.terminal(), \
        f"rollout stuck in {ctl.state} after {timeout}s: {ctl.describe()}"


# ---------------------------------------------------------------------------
# spec-built servables
# ---------------------------------------------------------------------------

class TestSpecServables:
    def test_linear_deterministic(self):
        sv = LinearServable((3,), scale=2.0, bias=0.5)
        x = np.array([[1, 2, 3]], np.float32)
        np.testing.assert_array_equal(sv.infer(x), x * 2 + 0.5)
        np.testing.assert_array_equal(sv.infer(x), sv.infer(x))

    def test_build_servable_kinds(self):
        sv = build_servable({"kind": "linear", "scale": 3.0,
                             "example_shape": [2]})
        assert isinstance(sv, LinearServable)
        assert sv.example_shape == (2,)
        with pytest.raises(ValueError, match="unknown model-spec"):
            build_servable({"kind": "nope"})
        with pytest.raises(ValueError):
            build_servable([1, 2])


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

class TestRouterRouting:
    def test_predict_routes_and_answers(self):
        with _Fleet(n=2) as f:
            status, headers, body = f.predict([[1.0, 2.0, 3.0]])
            assert status == 200
            out = json.loads(body)
            assert out["predictions"] == [[2.0, 4.0, 6.0]]
            assert out["version"] == 1
            assert "json" in headers.get("Content-Type", "")

    def test_models_merged_and_debug(self):
        with _Fleet(n=2) as f:
            _, _, body = _http(f.url + "/serving/v1/models")
            models = json.loads(body)["models"]
            assert [(m["name"], m["version"]) for m in models] == \
                [("m", 1)]
            _, _, body = _http(f.url + "/debug/fleet")
            dbg = json.loads(body)
            assert set(dbg["workers"]) == {"w0", "w1"}
            assert dbg["breaker"] == FleetRouter.BREAKER

    def test_healthz_ok_and_router_metrics(self):
        with _Fleet(n=2) as f:
            f.predict([[1.0, 2.0, 3.0]])
            status, _, body = _http(f.url + "/healthz")
            payload = json.loads(body)
            assert status == 200 and payload["status"] == "ok"
            assert payload["fleet"]["routable"] == 2
            _, _, text = _http(f.url + "/metrics")
            text = text.decode()
            assert "dl4j_fleet_requests_total" in text
            assert "dl4j_fleet_worker_up" in text

    def test_concurrent_load_spreads_over_workers(self):
        reg = telemetry.get_registry()
        hop = reg.histogram("dl4j_fleet_request_seconds",
                            labelnames=("worker",))
        before = {w: hop.labels(worker=w).count for w in ("w0", "w1")}
        with _Fleet(n=2, specs=[_spec(delay_ms=30.0)]) as f:
            errs = []

            def client():
                try:
                    status, _, _ = f.predict([[1.0, 2.0, 3.0]])
                    assert status == 200
                except Exception as e:   # surfaced below
                    errs.append(e)

            threads = [threading.Thread(target=client, daemon=True)
                       for _ in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            assert not errs
            counts = {w: hop.labels(worker=w).count - before[w]
                      for w in ("w0", "w1")}
            # 12 concurrent 30ms requests cannot all fit one worker
            # under least-inflight routing
            assert counts["w0"] > 0 and counts["w1"] > 0, counts

    def test_unknown_model_passes_through_404(self):
        with _Fleet(n=1) as f:
            status, _, body = f.predict([[1.0, 2.0, 3.0]],
                                        model="ghost")
            assert status == 404
            assert json.loads(body)["status"] == 404

    def test_parse_gauge_sum(self):
        text = ("# TYPE dl4j_serving_queue_depth gauge\n"
                'dl4j_serving_queue_depth{model="m"} 3\n'
                'dl4j_serving_queue_depth{model="n"} 2\n'
                'dl4j_serving_queue_depth_other{model="n"} 7\n'
                'dl4j_serving_replica_load{model="m",replica="r0"} -1\n')
        assert _parse_gauge_sum(text, "dl4j_serving_queue_depth") == 5.0
        # the -1 dead-replica sentinel is not load
        assert _parse_gauge_sum(text, "dl4j_serving_replica_load") == 0.0


# ---------------------------------------------------------------------------
# pass-through fidelity (the satellite bugfix verification)
# ---------------------------------------------------------------------------

class _StubWorkerHandler:
    """A raw worker that answers :predict with FIXED bytes — the
    byte-for-byte pass-through oracle."""

    BODY_429 = b'{"error": "shed by stub", "status": 429}'

    @classmethod
    def server(cls):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        class H(BaseHTTPRequestHandler):
            def _send(self, status, body, headers=()):
                self.send_response(status)
                for k, v in headers:
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self._send(200, b'{"status": "ok", "ready": true}')
                elif self.path == "/serving/v1/models":
                    self._send(200, b'{"models": []}')
                else:
                    self._send(200, b"")

            def do_POST(self):
                self.rfile.read(
                    int(self.headers.get("Content-Length") or 0))
                self._send(429, cls.BODY_429,
                           headers=[("Retry-After", "1.234"),
                                    ("Content-Type",
                                     "application/json")])

            def log_message(self, *a):
                pass

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        httpd.daemon_threads = True
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        return httpd, t


class TestPassthrough:
    def test_429_retry_after_byte_for_byte(self):
        """The worker's 429 body and Retry-After header cross the hop
        unmodified — and a 429 is an ANSWER: no retry, no breaker."""
        httpd, t = _StubWorkerHandler.server()
        router = FleetRouter(
            [WorkerHandle("stub",
                          f"http://127.0.0.1:{httpd.server_address[1]}")],
            poll_interval=0.05).start(port=0)
        try:
            status, headers, body = _http(
                f"http://127.0.0.1:{router.port}"
                f"/serving/v1/models/m:predict",
                body=b'{"instances": [[1]]}', timeout=10.0)
            assert status == 429
            assert body == _StubWorkerHandler.BODY_429
            assert headers.get("Retry-After") == "1.234"
            # an answered request is never a breaker strike
            assert router.workers[0].up
            assert router.workers[0].consec_failures == 0
        finally:
            router.close()
            httpd.shutdown()
            httpd.server_close()
            t.join(5.0)

    def test_504_body_byte_for_byte(self):
        """A deterministic worker 504 (tiny timeout against a slow
        model) produces identical bytes direct vs through the router."""
        with _Fleet(n=1, specs=[_spec(delay_ms=120.0)]) as f:
            payload = json.dumps({"instances": [[1.0, 2.0, 3.0]],
                                  "timeout_ms": 1}).encode()
            w = f.workers[0].handle
            s_direct, _, b_direct = _http(
                f"{w.url}/serving/v1/models/m:predict", body=payload,
                timeout=30.0)
            s_routed, _, b_routed = _http(
                f"{f.url}/serving/v1/models/m:predict", body=payload,
                timeout=30.0)
            assert s_direct == s_routed == 504
            assert b_routed == b_direct
            # a 504 is an answer too: no ejection
            assert f.router.workers[0].up

    def test_429_from_real_admission_control(self):
        """Occupy a budget-1 model's whole admission budget, then
        route a request: REAL admission control sheds it and the 429 +
        computed Retry-After cross the router hop."""
        adm = AdmissionController(default_budget=1)
        with _Fleet(n=1, admission=adm) as f:
            ticket = adm.admit("m")   # the budget is now full
            try:
                status, headers, body = f.predict([[1.0, 2.0, 3.0]])
            finally:
                ticket.release()
            assert status == 429
            assert float(headers["Retry-After"]) > 0
            assert json.loads(body)["status"] == 429
            # budget released: traffic flows again
            status, _, _ = f.predict([[1.0, 2.0, 3.0]])
            assert status == 200

    def test_traceparent_one_connected_trace(self):
        """An upstream sampled traceparent yields the router's
        fleet.predict span AND the worker's http.predict span under
        ONE trace id, and the response carries traceparent back."""
        trace_id = "ab" * 16
        parent = f"00-{trace_id}-{'cd' * 8}-01"
        with _Fleet(n=1) as f:
            status, headers, _ = f.predict(
                [[1.0, 2.0, 3.0]], headers={"traceparent": parent})
            assert status == 200
            resp_tp = headers.get("traceparent")
            assert resp_tp is not None and trace_id in resp_tp
            names = {s["name"] for s in
                     tracing.get_tracer().spans(trace_id)}
            assert {"fleet.predict", "http.predict"} <= names

    def test_unsampled_traceparent_stays_dark(self):
        parent = f"00-{'ef' * 16}-{'cd' * 8}-00"   # sampled flag OFF
        with _Fleet(n=1) as f:
            status, headers, _ = f.predict(
                [[1.0, 2.0, 3.0]], headers={"traceparent": parent})
            assert status == 200
            assert "traceparent" not in {k.lower() for k in headers}
            assert tracing.get_tracer().spans("ef" * 16) == []


# ---------------------------------------------------------------------------
# ejection / re-admission
# ---------------------------------------------------------------------------

class TestEjectionReadmission:
    def test_dead_worker_retried_ejected_then_degraded(self):
        with _Fleet(n=2, retry_budget=3) as f:
            f.workers[0].stop()   # connection refused from now on
            flight.get_recorder().clear()
            for _ in range(6):
                status, _, body = f.predict([[1.0, 2.0, 3.0]])
                assert status == 200   # retries absorb the death
                assert json.loads(body)["predictions"] == \
                    [[2.0, 4.0, 6.0]]
            dead = f.router.workers[0]
            assert not dead.up and dead.ejected_at is not None
            ejected = flight.get_recorder().events("worker_ejected")
            assert any(e["worker"] == "w0" for e in ejected)
            status, _, body = _http(f.url + "/healthz")
            payload = json.loads(body)
            assert status == 200            # degraded, NOT down
            assert payload["status"] == "degraded"
            assert payload["fleet"]["degraded"] is True
            snap = telemetry.get_registry().snapshot()
            assert snap.get('dl4j_fleet_worker_up{worker="w0"}') == 0.0
            assert snap.get("dl4j_fleet_retries_total", 0) >= 1.0

    def test_recovered_worker_readmitted(self):
        with _Fleet(n=2) as f:
            victim = f.workers[0]
            old_port = victim.server.port
            victim.stop()
            # route until the breaker ejects it
            deadline = time.monotonic() + 10.0
            while f.router.workers[0].up and \
                    time.monotonic() < deadline:
                f.predict([[1.0, 2.0, 3.0]])
            assert not f.router.workers[0].up
            # resurrect on the SAME port (the handle's URL is fixed)
            server = (UIServer().serveModels(victim.session)
                      .serveFleetAdmin(victim.admin))
            server.start(port=old_port)
            if server.port != old_port:   # someone stole the port
                server.stop()
                pytest.skip("port reused by another process")
            victim.server = server
            deadline = time.monotonic() + 10.0
            while not f.router.workers[0].up and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert f.router.workers[0].up, "never readmitted"
            events = flight.get_recorder().events("worker_readmitted")
            assert any(e["worker"] == "w0" for e in events)
            status, _, body = _http(f.url + "/healthz")
            assert json.loads(body)["status"] == "ok"


# ---------------------------------------------------------------------------
# rollouts
# ---------------------------------------------------------------------------

class TestRollout:
    def test_promote_pins_then_cuts_over(self):
        with _Fleet(n=3) as f:
            ctl = f.router.start_rollout(
                "m", {"kind": "linear", "scale": 2.0,
                      "example_shape": [3], "ladder": [1, 4]},
                # the latency verdict is test_latency_regression_rolls_back's
                # subject; here one slow mirror among 8 on a loaded host
                # must not turn the promotion into a rollback
                version=2, fraction=1.0, min_samples=8, p99_ratio=100.0)
            # while canarying, clients stay pinned to the incumbent
            status, _, body = f.predict([[1.0, 2.0, 3.0]])
            assert status == 200 and json.loads(body)["version"] == 1
            _drive_until(f, ctl)
            assert ctl.state == "complete"
            assert ctl.history == ["idle", "canary", "promoting",
                                   "complete"]
            # cutover: every worker now serves v2 by default
            for w in f.workers:
                assert w.session.registry.get("m").version == 2
            status, _, body = f.predict([[1.0, 2.0, 3.0]])
            assert json.loads(body)["version"] == 2
            snap = telemetry.get_registry().snapshot()
            assert snap["dl4j_fleet_rollout_state"] == \
                ROLLOUT_STATES["complete"]
            assert any(e["kind"] == "rollout_complete" for e in
                       flight.get_recorder().events())

    def test_disagreement_rolls_back(self):
        with _Fleet(n=3) as f:
            flight.get_recorder().clear()
            ctl = f.router.start_rollout(
                "m", {"kind": "linear", "scale": 3.0,   # WRONG answers
                      "example_shape": [3], "ladder": [1, 4]},
                version=2, fraction=1.0, min_samples=8)
            _drive_until(f, ctl)
            assert ctl.state == "rolled_back"
            assert "agreement" in ctl.decision["reason"]
            # vN restored on every worker; v2 gone everywhere
            for w in f.workers:
                entry = w.session.registry.get("m")
                assert entry.version == 1
            status, _, body = f.predict([[1.0, 2.0, 3.0]])
            out = json.loads(body)
            assert out["version"] == 1
            assert out["predictions"] == [[2.0, 4.0, 6.0]]
            events = flight.get_recorder().events("rollout_rollback")
            assert events and events[0]["restored"] == 1
            snap = telemetry.get_registry().snapshot()
            assert snap["dl4j_fleet_rollout_state"] == \
                ROLLOUT_STATES["rolled_back"]

    def test_latency_regression_rolls_back(self):
        with _Fleet(n=2) as f:
            ctl = f.router.start_rollout(
                "m", {"kind": "linear", "scale": 2.0,   # right answers,
                      "delay_ms": 150.0,                # 50x slower
                      "example_shape": [3], "ladder": [1, 4]},
                version=2, fraction=1.0, min_samples=6)
            _drive_until(f, ctl, timeout=60.0)
            assert ctl.state == "rolled_back"
            assert "p99" in ctl.decision["reason"]

    def test_promotion_with_down_worker_rolls_back(self):
        """Promotion pushes to EVERY worker: an unreachable one aborts
        into rollback instead of being skipped — a skipped worker
        readmitted later would serve vN beside a vN+1 fleet."""
        with _Fleet(n=3, retry_budget=3) as f:
            f.workers[2].stop()   # w2 goes dark
            deadline = time.monotonic() + 10.0
            while f.router.workers[2].up and \
                    time.monotonic() < deadline:
                f.predict([[1.0, 2.0, 3.0]])   # trip the breaker
            assert not f.router.workers[2].up
            ctl = f.router.start_rollout(
                "m", {"kind": "linear", "scale": 2.0,   # promote-worthy
                      "example_shape": [3], "ladder": [1, 4]},
                version=2, fraction=1.0, min_samples=6, p99_ratio=100.0)
            _drive_until(f, ctl)
            assert ctl.state == "rolled_back"
            assert "promotion push" in ctl.decision["reason"]
            assert "promoting" in ctl.history
            # v2 retracted from everything it reached
            for w in f.workers[:2]:
                assert w.session.registry.get("m").version == 1

    def test_rollout_guards(self):
        with _Fleet(n=2) as f:
            with pytest.raises(RuntimeError, match="not served"):
                f.router.start_rollout(
                    "ghost", {"kind": "linear", "example_shape": [3]},
                    version=2)
            ctl = f.router.start_rollout(
                "m", {"kind": "linear", "scale": 2.0,
                      "example_shape": [3], "ladder": [1, 4]},
                version=2, fraction=1.0, min_samples=4)
            with pytest.raises(RuntimeError, match="already active"):
                f.router.start_rollout(
                    "m", {"kind": "linear", "example_shape": [3]},
                    version=3)
            _drive_until(f, ctl)
            with pytest.raises(ValueError, match="exceed"):
                f.router.start_rollout(
                    "m", {"kind": "linear", "example_shape": [3]},
                    version=1)

    def test_histogram_quantile(self):
        h = Histogram("t", buckets=log_buckets(1e-3, 10, per_decade=4))
        assert histogram_quantile(h) == 0.0
        for _ in range(99):
            h.observe(0.002)
        h.observe(5.0)
        assert histogram_quantile(h, 0.5) < 0.01
        assert histogram_quantile(h, 0.999) >= 5.0


# ---------------------------------------------------------------------------
# traffic capture
# ---------------------------------------------------------------------------

class TestCapture:
    def test_capture_replay_bit_identical(self, tmp_path):
        cap = TrafficCapture(sample_interval=1, max_records=64)
        with _Fleet(n=2, capture=cap) as f:
            sent = []
            rng = np.random.default_rng(3)
            for _ in range(6):
                x = rng.normal(size=(2, 3)).astype(np.float32)
                sent.append(x)
                status, _, _ = f.predict(x.tolist())
                assert status == 200
        assert len(cap) == 6
        path = str(tmp_path / "traffic.jsonl")
        cap.save(path)
        # replay: features bit-identical to what clients sent, labels
        # = the fleet's answers (distillation targets)
        it = CaptureReplayIterator(path, batch_size=4)
        feats = np.concatenate([ds.features for ds in it])
        np.testing.assert_array_equal(feats, np.concatenate(sent))
        it2 = CaptureReplayIterator(path, batch_size=4)
        labels = np.concatenate([ds.labels for ds in it2])
        np.testing.assert_array_equal(labels,
                                      np.concatenate(sent) * 2.0)
        # iterating twice is bit-identical
        a = [ds.features for ds in CaptureReplayIterator(path)]
        b = [ds.features for ds in CaptureReplayIterator(path)]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        # and a re-save of the same ring is byte-identical
        path2 = str(tmp_path / "traffic2.jsonl")
        cap.save(path2)
        with open(path, "rb") as f1, open(path2, "rb") as f2:
            assert f1.read() == f2.read()

    def test_head_sampling_and_bounds(self, tmp_path):
        cap = TrafficCapture(sample_interval=3, max_records=4)
        for i in range(12):
            cap.maybe_record(
                "m", json.dumps({"instances": [[float(i)]]}).encode(),
                b'{"predictions": [[0.0]], "version": 1}')
        # 12 offered / every 3rd sampled = 4 records, ring-bounded at 4
        assert len(cap) == 4
        assert cap.describe()["sampled"] == 4
        # malformed bodies never raise, never record
        assert cap.maybe_record("m", b"not json", b"") is None
        path = str(tmp_path / "c.jsonl")
        cap.save(path)
        assert [r["instances"] for r in load_capture(path)] == \
            [[[0.0]], [[3.0]], [[6.0]], [[9.0]]]


# ---------------------------------------------------------------------------
# worker admin routes
# ---------------------------------------------------------------------------

class TestAdminRoutes:
    def test_register_unregister_roundtrip(self):
        w = _InprocWorker("w0", [_spec()])
        try:
            url = f"{w.handle.url}/serving/v1/models/m"
            status, _, body = _http(
                url + ":register",
                body=json.dumps({
                    "spec": {"kind": "linear", "scale": 5.0,
                             "example_shape": [3], "ladder": [1, 4]},
                    "version": 2}).encode())
            assert status == 200
            assert json.loads(body) == {"model": "m", "version": 2,
                                        "warmed": True}
            assert w.session.registry.get("m").version == 2
            status, _, body = _http(
                url + ":unregister",
                body=json.dumps({"version": 2}).encode())
            assert status == 200
            assert w.session.registry.get("m").version == 1
        finally:
            w.stop()

    def test_admin_error_mapping(self):
        w = _InprocWorker("w0", [_spec()])
        try:
            url = f"{w.handle.url}/serving/v1/models/m"
            status, _, _ = _http(url + ":register", body=b"not json")
            assert status == 400
            status, _, body = _http(
                url + ":register",
                body=json.dumps({"spec": {"kind": "nope"},
                                 "version": 2}).encode())
            assert status == 400
            assert "unknown model-spec" in json.loads(body)["error"]
            status, _, _ = _http(
                f"{w.handle.url}/serving/v1/models/ghost:unregister",
                body=b"{}")
            assert status == 404
            # an unknown VERSION of a known model is 404 too, not a
            # 500 (an automated rollback retrying on 5xx must treat
            # already-retracted as benign)
            status, _, body = _http(
                url + ":unregister",
                body=json.dumps({"version": 9}).encode())
            assert status == 404
            assert "m:9" in json.loads(body)["error"]
        finally:
            w.stop()

    def test_admin_404_without_attachment(self):
        session = InferenceSession(max_latency=0.0)
        server = UIServer().serveModels(session).start(port=0)
        try:
            status, _, body = _http(
                f"http://127.0.0.1:{server.port}"
                f"/serving/v1/models/m:register",
                body=json.dumps({"spec": {"kind": "linear"},
                                 "version": 1}).encode())
            assert status == 404
            assert "no fleet admin" in json.loads(body)["error"]
        finally:
            server.stop()
            session.close()


# ---------------------------------------------------------------------------
# slow tier: real worker processes under the armed lock witness
# ---------------------------------------------------------------------------

def _spawned_fleet(n=3, spec_models=None, **router_kw):
    spec = {"models": spec_models or [_spec()]}
    workers = spawn_local_workers(n, spec, extra_env=CPU_ENV)
    router_kw.setdefault("poll_interval", 0.1)
    router = FleetRouter(workers, owns_workers=True,
                         **router_kw).start(port=0)
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and \
            not all(w.models for w in router.workers):
        time.sleep(0.05)   # rollouts need the polled model lists
    return router, f"http://127.0.0.1:{router.port}"


@pytest.mark.slow
class TestFleetProcesses:
    def test_kill_one_worker_soak_loses_zero_requests(self):
        """ISSUE 15 acceptance: a 3-worker fleet under continuous
        client load, one worker SIGKILLed mid-soak — every accepted
        request completes (retries absorb the death), the death shows
        up as ejection + degradation, never as a client error."""
        router, url = _spawned_fleet(n=3, retry_budget=4)
        try:
            flight.get_recorder().clear()
            results = {"ok": 0}
            errors = []
            stop = threading.Event()
            body = json.dumps(
                {"instances": [[1.0, 2.0, 3.0]]}).encode()

            def client():
                while not stop.is_set():
                    try:
                        status, _, rb = _http(
                            url + "/serving/v1/models/m:predict",
                            body=body, timeout=30.0)
                        out = json.loads(rb)
                        if status != 200 or out["predictions"] != \
                                [[2.0, 4.0, 6.0]]:
                            errors.append((status, rb))
                        else:
                            results["ok"] += 1
                    except Exception as e:
                        errors.append(("transport", repr(e)))

            threads = [threading.Thread(target=client, daemon=True)
                       for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(1.5)                    # soak against 3 workers
            victim = router.workers[1]
            os.kill(victim.proc.pid, signal.SIGKILL)
            time.sleep(3.0)                    # soak through the death
            stop.set()
            for t in threads:
                t.join(30.0)
            assert not errors, errors[:5]
            assert results["ok"] > 50
            # the death was contained and observed
            assert not victim.up
            ejected = flight.get_recorder().events("worker_ejected")
            assert any(e["worker"] == victim.name for e in ejected)
            status, _, hb = _http(url + "/healthz")
            payload = json.loads(hb)
            assert status == 200
            assert payload["status"] == "degraded"
            snap = telemetry.get_registry().snapshot()
            assert snap.get("dl4j_fleet_retries_total", 0) >= 1.0
            # cross-process one-connected-trace check: a sampled
            # traceparent shows up in a SURVIVOR's span ring with the
            # router's span beside it in this process
            trace_id = "5a" * 16
            _http(url + "/serving/v1/models/m:predict", body=body,
                  headers={"traceparent":
                           f"00-{trace_id}-{'1b' * 8}-01"},
                  timeout=30.0)
            assert tracing.get_tracer().spans(trace_id)
            found = []
            for w in router.workers:
                if not w.up:
                    continue
                _, _, traces = _http(w.url + "/debug/traces",
                                     timeout=10.0)
                found.extend(
                    json.loads(line) for line in
                    traces.decode().splitlines()
                    if line and trace_id in line)
            assert any(s["trace_id"] == trace_id for s in found)
        finally:
            router.close()

    def test_regressed_canary_rolls_back_fleetwide(self):
        """ISSUE 15 acceptance: a deliberately-regressed vN+1 canary
        (wrong outputs) auto-rolls back; every worker process serves
        vN afterwards, the decision is a flight event, and the
        dl4j_fleet_rollout_state gauge walks idle→canary→rolled_back."""
        router, url = _spawned_fleet(n=3)
        try:
            flight.get_recorder().clear()
            ctl = router.start_rollout(
                "m", {"kind": "linear", "scale": 7.0,    # regressed
                      "example_shape": [3], "ladder": [1, 4]},
                version=2, fraction=1.0, min_samples=10)
            body = json.dumps(
                {"instances": [[1.0, 2.0, 3.0]]}).encode()
            deadline = time.monotonic() + 60.0
            while not ctl.terminal() and time.monotonic() < deadline:
                status, _, rb = _http(
                    url + "/serving/v1/models/m:predict", body=body,
                    timeout=30.0)
                # clients keep getting the incumbent THROUGHOUT
                assert status == 200
                assert json.loads(rb)["predictions"] == \
                    [[2.0, 4.0, 6.0]]
                time.sleep(0.005)
            assert ctl.state == "rolled_back", ctl.describe()
            assert ctl.history == ["idle", "canary", "rolled_back"]
            # vN restored in every WORKER PROCESS
            for w in router.workers:
                _, _, mb = _http(w.url + "/serving/v1/models",
                                 timeout=10.0)
                versions = [m["version"] for m in
                            json.loads(mb)["models"]
                            if m["name"] == "m"]
                assert versions == [1], (w.name, versions)
            events = flight.get_recorder().events("rollout_rollback")
            assert events and events[0]["restored"] == 1
            states = [e["state"] for e in
                      flight.get_recorder().events("rollout_state")]
            assert states == ["canary", "rolled_back"]
            snap = telemetry.get_registry().snapshot()
            assert snap["dl4j_fleet_rollout_state"] == \
                ROLLOUT_STATES["rolled_back"]
            assert snap.get(
                'dl4j_fleet_mirror_total{verdict="disagree"}', 0) >= 10
        finally:
            router.close()

    def test_worker_cli_spawn_and_terminate(self):
        """spawn_local_workers end to end: ports committed via the
        port file, /healthz ready, SIGTERM exits cleanly."""
        workers = spawn_local_workers(1, {"models": [_spec()]},
                                      extra_env=CPU_ENV)
        try:
            _, _, body = _http(workers[0].url + "/healthz")
            assert json.loads(body)["ready"] is True
            status, _, rb = _http(
                workers[0].url + "/serving/v1/models/m:predict",
                body=json.dumps(
                    {"instances": [[1.0, 2.0, 3.0]]}).encode())
            assert status == 200
            assert json.loads(rb)["predictions"] == [[2.0, 4.0, 6.0]]
        finally:
            for w in workers:
                w.proc.terminate()
            for w in workers:
                assert w.proc.wait(15) == 0
