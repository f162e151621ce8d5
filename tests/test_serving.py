"""Serving subsystem tests (ISSUE 2): bucket selection / padding
roundtrip (equal to unbatched output within the float32 tolerance of
`conftest.assert_rows_close`), AOT warmup with zero
steady-state recompiles, concurrent-client coalescing (>= 4x fewer
device dispatches than per-request calls), queue-full rejection,
per-request timeouts, graceful shutdown, and the HTTP predict route
end-to-end against a live UIServer."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn import (
    DenseLayer, LossFunction, MultiLayerNetwork, NeuralNetConfiguration,
    OutputLayer)
from deeplearning4j_tpu.serving import (
    BucketLadder, InferenceSession, ModelNotFound, ModelRegistry,
    QueueFullError, Servable, ServingShutdown, pad_batch, pad_rows, unpad)
from deeplearning4j_tpu.ui.server import UIServer


def _mlp(seed=1, n_in=6, n_out=3):
    conf = (NeuralNetConfiguration.Builder().seed(seed).list()
            .layer(DenseLayer.Builder().nIn(n_in).nOut(16)
                   .activation("tanh").build())
            .layer(OutputLayer.Builder().nOut(n_out).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    return MultiLayerNetwork(conf).init()


def _counter(name, **labels):
    fam = telemetry.get_registry().counter(
        name, labelnames=tuple(labels) if labels else ())
    return fam.labels(**labels) if labels else fam


class SlowServable(Servable):
    """Host-side stub: y = 2x after a fixed delay (no jax involved)."""

    def __init__(self, delay, example_shape=(2,)):
        super().__init__(example_shape)
        self.delay = delay
        self.calls = 0

    def warmup(self, ladder):
        return []

    def infer(self, x):
        self.calls += 1
        time.sleep(self.delay)
        return np.asarray(x) * 2.0


class TestBucketLadder:
    def test_covering_and_plan(self):
        lad = BucketLadder((1, 4, 8))
        assert [lad.covering(n) for n in (1, 2, 4, 5, 8)] == [1, 4, 4, 8, 8]
        assert lad.covering(9) is None
        assert lad.plan(3) == [4]
        assert lad.plan(8) == [8]
        assert lad.plan(21) == [8, 8, 8]
        assert lad.plan(17) == [8, 8, 1]

    def test_shapes_cross_product_with_seq_buckets(self):
        lad = BucketLadder((1, 2), seq_lengths=(16, 32))
        assert set(lad.shapes((5, 10))) == {
            (1, 5, 16), (1, 5, 32), (2, 5, 16), (2, 5, 32)}

    def test_pad_roundtrip(self):
        lad = BucketLadder((4, 8))
        x = np.arange(3 * 5, dtype=np.float32).reshape(3, 5)
        p, n, t = pad_batch(x, lad)
        assert p.shape == (4, 5) and n == 3 and t is None
        np.testing.assert_array_equal(p[:3], x)
        np.testing.assert_array_equal(p[3], x[-1])   # repeated last row
        np.testing.assert_array_equal(unpad(p, n, t), x)

    def test_pad_rows_rejects_overflow(self):
        with pytest.raises(ValueError):
            pad_rows(np.zeros((5, 2)), 4)


class TestServablePadding:
    def test_padded_results_match_unbatched_rows(self, assert_rows_close):
        """Padded-batch rows against unbatched rows: the bucket-8
        executable and the batch-3 `output()` are programs of different
        shapes, so within the float32 tolerance (ROADMAP D4)."""
        net = _mlp()
        rng = np.random.default_rng(0)
        X = rng.normal(size=(3, 6)).astype(np.float32)
        y_ref = net.output(X).toNumpy()           # unbatched, batch 3
        sess = InferenceSession()
        sess.register("m", net, example_shape=(6,),
                      ladder=BucketLadder((1, 8)), warmup=True)
        y_pad = sess.predict("m", X, batched=False)   # padded to bucket 8
        assert_rows_close(y_pad, y_ref)
        sess.close()

    def test_warmup_aot_compiles_and_steady_state_adds_none(self):
        net = _mlp(seed=2)
        sess = InferenceSession()
        entry = sess.register("m", net, example_shape=(6,),
                              ladder=BucketLadder((1, 4)))
        compiles = _counter("dl4j_compile_total")
        c0 = compiles.value
        sess.warmup("m")
        assert compiles.value > c0          # the ladder compiled HERE
        assert entry.warmed
        assert entry.servable.warmed_shapes == [(1, 6), (4, 6)]
        c1 = compiles.value
        x = np.zeros((3, 6), np.float32)
        for _ in range(5):
            sess.predict("m", x, batched=False)
            sess.predict("m", x[:1], batched=False)
        assert compiles.value == c1         # zero recompiles after warmup
        sess.close()

    def test_oversized_batch_chunks_through_ladder(self):
        net = _mlp(seed=3)
        sess = InferenceSession()
        sess.register("m", net, example_shape=(6,),
                      ladder=BucketLadder((1, 4)), warmup=True)
        X = np.random.default_rng(1).normal(size=(11, 6)).astype(np.float32)
        y = sess.predict("m", X, batched=False)   # plan: 4+4+4 buckets
        assert y.shape == (11, 3)
        np.testing.assert_array_equal(y, net.output(X).toNumpy())
        sess.close()


class TestOtherModelTypes:
    def test_computation_graph_servable(self, assert_rows_close):
        from deeplearning4j_tpu.nn import ComputationGraph

        conf = (NeuralNetConfiguration.Builder().seed(9).graphBuilder()
                .addInputs("in")
                .addLayer("d", DenseLayer.Builder().nIn(6).nOut(8)
                          .activation("tanh").build(), "in")
                .addLayer("out", OutputLayer.Builder().nIn(8).nOut(3)
                          .lossFunction(LossFunction.MCXENT).build(), "d")
                .setOutputs("out")
                .build())
        graph = ComputationGraph(conf).init()
        sess = InferenceSession()
        sess.register("g", graph, example_shape=(6,),
                      ladder=BucketLadder((1, 4)), warmup=True)
        X = np.random.default_rng(4).normal(size=(3, 6)).astype(np.float32)
        ref = graph.outputSingle(X).toNumpy()   # batch 3; served: bucket 4
        assert_rows_close(sess.predict("g", X, batched=False), ref)
        sess.close()

    def test_samediff_servable(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.autodiff.samediff import SameDiff

        sd = SameDiff.create()
        x = sd.placeHolder("x", jnp.float32, -1, 4)
        w = sd.var("w", np.random.default_rng(0).normal(
            size=(4, 2)).astype(np.float32))
        out = x.mmul(w)
        sess = InferenceSession()
        sess.register("sd", sd, example_shape=(4,),
                      ladder=BucketLadder((1, 4)),
                      input_name="x", output_name=out, warmup=True)
        X = np.random.default_rng(1).normal(size=(3, 4)).astype(np.float32)
        ref = sd.outputSingle({"x": X}, out).toNumpy()
        np.testing.assert_array_equal(
            sess.predict("sd", X, batched=False), ref)
        sess.close()


class TestRegistry:
    def test_versioning_and_describe(self):
        reg = ModelRegistry(ladder=BucketLadder((1, 2)))
        reg.register("m", _mlp(seed=1), version=1, example_shape=(6,))
        reg.register("m", _mlp(seed=2), version=2, example_shape=(6,))
        assert reg.get("m").version == 2          # newest wins
        assert reg.get("m", version=1).version == 1
        with pytest.raises(ModelNotFound):
            reg.get("nope")
        with pytest.raises(ModelNotFound):
            reg.get("m", version=9)
        rows = reg.describe()
        assert [(r["name"], r["version"]) for r in rows] == [
            ("m", 2), ("m", 1)]
        assert rows[0]["ladder"]["batch_sizes"] == [1, 2]
        reg.unregister("m", version=1)
        assert reg.get("m").version == 2


class TestDynamicBatcher:
    def test_concurrent_clients_coalesce(self):
        """Acceptance criterion: 32 concurrent single-example clients on
        a warmed ladder -> >= 4x fewer device dispatches than requests,
        zero recompiles, results bit-identical to unbatched output()."""
        net = _mlp(seed=4)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(4, 6)).astype(np.float32)
        y_ref = net.output(X).toNumpy()           # compiles (4, 6) HERE
        sess = InferenceSession(max_latency=0.05, queue_size=64)
        sess.register("coal", net, example_shape=(6,),
                      ladder=BucketLadder((1, 8, 32)), warmup=True)
        dispatches = _counter("dl4j_serving_dispatch_total", model="coal")
        compiles = _counter("dl4j_compile_total")
        d0, c0 = dispatches.value, compiles.value
        results = [None] * 32
        barrier = threading.Barrier(32)

        def client(i):
            barrier.wait()
            results[i] = sess.predict("coal", X[i % 4], timeout=10.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert dispatches.value - d0 <= 32 / 4    # >= 4x fewer dispatches
        assert compiles.value == c0               # zero recompiles
        for i in range(32):
            np.testing.assert_array_equal(results[i], y_ref[i % 4])
        ok = _counter("dl4j_serving_requests_total", model="coal",
                      outcome="ok")
        assert ok.value >= 32
        sess.close()

    def test_queue_full_rejection(self):
        sess = InferenceSession(max_latency=0.0, queue_size=2)
        sess.register("slow", SlowServable(delay=0.3),
                      ladder=BucketLadder((1,)))
        x = np.zeros((1, 2), np.float32)
        sess.predict_async("slow", x)     # worker takes this one
        time.sleep(0.05)                  # let the worker start executing
        sess.predict_async("slow", x)     # queued
        sess.predict_async("slow", x)     # queued (queue now full)
        with pytest.raises(QueueFullError):
            sess.predict_async("slow", x)
        rejected = _counter("dl4j_serving_requests_total", model="slow",
                            outcome="rejected")
        assert rejected.value >= 1
        sess.close()

    def test_per_request_timeout(self):
        sess = InferenceSession(max_latency=0.0, queue_size=8)
        sess.register("slow2", SlowServable(delay=0.4),
                      ladder=BucketLadder((1,)))
        x = np.zeros((1, 2), np.float32)
        sess.predict_async("slow2", x, timeout=5.0)   # occupies the worker
        time.sleep(0.05)
        f = sess.predict_async("slow2", x, timeout=0.05)
        with pytest.raises(TimeoutError):
            f.result(timeout=5.0)         # expired while queued
        # ISSUE 8 satellite: queued expiry is its own outcome, distinct
        # from a deadline passing mid-execute (timeout_execute)
        timeouts = _counter("dl4j_serving_requests_total", model="slow2",
                            outcome="timeout_queued")
        assert timeouts.value >= 1
        sess.close()

    def test_shutdown_fails_queued_requests(self):
        sess = InferenceSession(max_latency=0.0, queue_size=8)
        sess.register("slow3", SlowServable(delay=0.5),
                      ladder=BucketLadder((1,)))
        x = np.zeros((1, 2), np.float32)
        sess.predict_async("slow3", x)
        time.sleep(0.05)
        queued = [sess.predict_async("slow3", x) for _ in range(3)]
        sess.close()
        failed = 0
        for f in queued:
            try:
                f.result(timeout=5.0)
            except ServingShutdown:
                failed += 1
        assert failed >= 2   # at most one was already being collected
        with pytest.raises(RuntimeError):
            sess.predict("slow3", x)


class TestSequenceBatching:
    def test_mixed_length_sequences_coalesce_and_unpad(self):
        """Concurrent sequence requests with different trailing lengths
        pad to the covering seq bucket before coalescing, and each
        result slices back to its own real length."""
        from deeplearning4j_tpu.serving import FnServable

        sv = FnServable(lambda x: x * 2.0, example_shape=(2, 8))
        sess = InferenceSession(max_latency=0.05)
        sess.register("seq", sv,
                      ladder=BucketLadder((1, 4), seq_lengths=(8,)),
                      warmup=True)
        rng = np.random.default_rng(8)
        a = rng.normal(size=(1, 2, 5)).astype(np.float32)
        b = rng.normal(size=(1, 2, 7)).astype(np.float32)
        fa = sess.predict_async("seq", a)
        fb = sess.predict_async("seq", b)
        ya, yb = fa.result(timeout=10), fb.result(timeout=10)
        assert ya.shape == (1, 2, 5) and yb.shape == (1, 2, 7)
        np.testing.assert_array_equal(ya, a * 2.0)
        np.testing.assert_array_equal(yb, b * 2.0)
        sess.close()


class TestVersionPinning:
    def test_predict_serves_the_pinned_version(self, assert_rows_close):
        net1, net2 = _mlp(seed=11), _mlp(seed=12)
        sess = InferenceSession(max_latency=0.001)
        for v, net in ((1, net1), (2, net2)):
            sess.register("vp", net, version=v, example_shape=(6,),
                          ladder=BucketLadder((1, 4)), warmup=True)
        X = np.random.default_rng(9).normal(size=(3, 6)).astype(np.float32)
        # bucket 4 against a batch-3 output(): tolerance (D4); the two
        # versions' outputs differ in the first decimal
        y1, y2 = net1.output(X).toNumpy(), net2.output(X).toNumpy()
        assert np.abs(y1 - y2).max() > 1e-3
        assert_rows_close(sess.predict("vp", X, version=1), y1)
        assert_rows_close(sess.predict("vp", X), y2)
        assert set(sess.stats()) == {"vp:v1", "vp:v2"}
        sess.close()


class TestHttpServing:
    def _serve(self, sess):
        ui = UIServer().serveModels(sess)
        ui.start(port=0)
        return ui, f"http://127.0.0.1:{ui.port}"

    def _post(self, url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req).read())

    def test_predict_and_models_routes_end_to_end(self):
        net = _mlp(seed=5)
        sess = InferenceSession(max_latency=0.001)
        sess.register("http", net, example_shape=(6,),
                      ladder=BucketLadder((1, 4)), warmup=True)
        ui, base = self._serve(sess)
        try:
            X = np.random.default_rng(2).normal(size=(3, 6)).astype(
                np.float32)
            out = self._post(f"{base}/serving/v1/models/http:predict",
                             {"instances": X.tolist()})
            assert out["model"] == "http" and out["version"] == 1
            np.testing.assert_allclose(
                np.asarray(out["predictions"], np.float32),
                net.output(X).toNumpy(), rtol=1e-5, atol=1e-6)
            models = json.loads(urllib.request.urlopen(
                f"{base}/serving/v1/models").read())["models"]
            assert models[0]["name"] == "http" and models[0]["warmed"]
            assert models[0]["ladder"]["batch_sizes"] == [1, 4]
        finally:
            ui.stop()
            sess.close()

    def test_http_error_mapping(self):
        sess = InferenceSession()
        sess.register("m", _mlp(seed=6), example_shape=(6,),
                      ladder=BucketLadder((1,)))
        ui, base = self._serve(sess)
        try:
            for path, payload, code in [
                ("/serving/v1/models/nope:predict", {"instances": [[0.0]]},
                 404),
                ("/serving/v1/models/m:predict", {"wrong": 1}, 400),
                ("/serving/v1/models/m:predict",
                 {"instances": [[0.0, 0.0]]}, 400),   # wrong example shape
            ]:
                with pytest.raises(urllib.error.HTTPError) as e:
                    self._post(f"{base}{path}", payload)
                assert e.value.code == code
                body = json.loads(e.value.read())
                assert body["status"] == code and body["error"]
            # malformed JSON body
            req = urllib.request.Request(
                f"{base}/serving/v1/models/m:predict", data=b"{nope")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req)
            assert e.value.code == 400
        finally:
            ui.stop()
            sess.close()

    def test_no_session_attached_404(self):
        ui = UIServer().start(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ui.port}/serving/v1/models")
            assert e.value.code == 404
        finally:
            ui.stop()


@pytest.mark.slow
class TestServingSoak:
    def test_sustained_concurrent_load(self):
        """Multi-threaded soak: 8 clients x 50 requests of mixed batch
        sizes; every request succeeds, results match unbatched output,
        zero recompiles after warmup."""
        net = _mlp(seed=7)
        rng = np.random.default_rng(3)
        X = rng.normal(size=(64, 6)).astype(np.float32)
        refs = net.output(X).toNumpy()            # compiles (64, 6)
        sess = InferenceSession(max_latency=0.002, queue_size=512)
        sess.register("soak", net, example_shape=(6,),
                      ladder=BucketLadder((1, 2, 4, 8, 16, 32)),
                      warmup=True)
        compiles = _counter("dl4j_compile_total")
        c0 = compiles.value
        errors = []

        def client(seed):
            r = np.random.default_rng(seed)
            for _ in range(50):
                n = int(r.integers(1, 9))
                i = int(r.integers(0, 64 - n))
                y = sess.predict("soak", X[i:i + n], timeout=30.0)
                if not np.array_equal(y, refs[i:i + n]):
                    errors.append((seed, i, n))

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert compiles.value == c0
        dispatches = _counter("dl4j_serving_dispatch_total", model="soak")
        assert dispatches.value < 8 * 50          # coalescing happened
        sess.close()
