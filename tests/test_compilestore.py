"""Persistent executable store tests (ISSUE 13): content-addressed
entries with atomic commits and corrupt/stale rejection, LRU eviction,
the serving warm-registration zero-compile smoke (ledger-asserted via
the new cache_hit cause), StoredJit train-step resolution with
bit-identical math, Supervisor kill-and-resume over a warm store,
the donation-safety clone for deserialized executables, the rewarm /
cache_hit cause split, and the /debug/compiles store section."""

import json
import os
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import compilestore, telemetry
from deeplearning4j_tpu.compilestore import (
    ExecutableStore, StoreReject, entry_key)
from deeplearning4j_tpu.telemetry import compile_ledger


@pytest.fixture
def store(tmp_path):
    """Fresh store + fresh ledger + enabled telemetry, all restored
    after (the store is process-global state like the ledger)."""
    st = compilestore.configure(root=str(tmp_path / "xc"))
    led = compile_ledger.CompileLedger()
    prev = compile_ledger.set_ledger(led)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    compile_ledger.configure(enabled=True)
    compile_ledger.consume_backend_compiles()
    yield st
    compilestore.configure(enabled=False)
    compile_ledger.set_ledger(prev)
    (telemetry.enable if was_enabled else telemetry.disable)()


def _mlp(seed=1, nin=4):
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Adam(1e-2)).list()
            .layer(DenseLayer.Builder().nIn(nin).nOut(8)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(2).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=8, nin=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, nin)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return X, y


def _flat(net):
    return np.asarray(net.params().toNumpy())


def _compiles():
    return float(telemetry.get_registry()
                 .counter("dl4j_compile_total").value)


def _sig(shapes=((4, 8),), policy=""):
    return compile_ledger.Signature(
        args=tuple((tuple(s), "float32") for s in shapes),
        donation=(), policy=policy, sharding="")


# ---------------------------------------------------------------------------
# the disk store: entries, rejection, eviction
# ---------------------------------------------------------------------------

class TestExecutableStore:
    def test_put_get_roundtrip(self, store):
        key = entry_key(_sig(), "prog")
        path = store.put(key, b"payload-bytes", site="s",
                         fingerprint="abc")
        assert path.endswith(".xc") and os.path.exists(path)
        header, payload = store.get(key)
        assert payload == b"payload-bytes"
        assert header["site"] == "s"
        assert header["hlo_fingerprint"] == "abc"
        assert store.stats["puts"] == 1 and store.stats["hits"] == 1

    def test_miss_returns_none(self, store):
        assert store.get("0" * 64) is None
        assert store.stats["misses"] == 1

    def test_truncated_entry_rejected_and_removed(self, store):
        key = entry_key(_sig(), "prog")
        path = store.put(key, b"x" * 1000)
        with open(path, "rb") as f:
            raw = f.read()
        with open(path, "wb") as f:
            f.write(raw[:-17])   # torn tail
        with pytest.raises(StoreReject):
            store.get(key)
        assert not os.path.exists(path)   # removed: next get is a miss
        assert store.get(key) is None
        assert store.stats["rejects"] == 1

    def test_bitflip_rejected_by_payload_hash(self, store):
        key = entry_key(_sig(), "prog")
        path = store.put(key, b"y" * 512)
        with open(path, "rb") as f:
            raw = bytearray(f.read())
        raw[-7] ^= 0x40
        with open(path, "wb") as f:
            f.write(bytes(raw))
        with pytest.raises(StoreReject):
            store.get(key)

    def test_wrong_machine_identity_rejected(self, store):
        key = entry_key(_sig(), "prog")
        path = store.put(key, b"z")
        # rewrite the header with a foreign jax version, keeping the
        # payload hash valid — only the machine check can catch it
        with open(path, "rb") as f:
            raw = f.read()
        hlen = int.from_bytes(raw[8:12], "big")
        header = json.loads(raw[12:12 + hlen])
        header["machine"] = dict(header["machine"], jax="0.0.1")
        head = json.dumps(header, sort_keys=True).encode()
        with open(path, "wb") as f:
            f.write(raw[:8] + len(head).to_bytes(4, "big") + head
                    + raw[12 + hlen:])
        with pytest.raises(StoreReject):
            store.get(key)

    def test_lru_eviction_keeps_newest(self, store):
        keys = [entry_key(_sig(((i, 4),)), "prog") for i in range(6)]
        for i, k in enumerate(keys):
            store.put(k, bytes(1000))
            os.utime(store._store_path(k), (i, i))   # deterministic age
        entry_bytes = os.path.getsize(store._store_path(keys[0]))
        store.max_bytes = 3 * entry_bytes + 10
        store._evict()
        alive = [k for k in keys
                 if os.path.exists(store._store_path(k))]
        assert alive == keys[-3:]
        assert store.stats["evictions"] == 3

    def test_key_covers_signature_program_and_machine(self, store):
        a = entry_key(_sig(((4, 8),)), "prog")
        assert a == entry_key(_sig(((4, 8),)), "prog")   # deterministic
        assert a != entry_key(_sig(((8, 8),)), "prog")
        assert a != entry_key(_sig(((4, 8),), policy="bf16"), "prog")
        assert a != entry_key(_sig(((4, 8),)), "prog2")

    def test_describe_and_contents(self, store):
        store.put(entry_key(_sig(), "p"), b"abc", site="fit")
        d = compilestore.describe()
        assert d["enabled"] and d["entries"] == 1
        assert d["bytes_on_disk"] > 0
        rows = store.contents()
        assert rows[0]["site"] == "fit"


# ---------------------------------------------------------------------------
# resolve(): the AOT seam
# ---------------------------------------------------------------------------

class TestResolve:
    def test_miss_compiles_and_stores_then_hits(self, store):
        fn = jax.jit(lambda x: x * 2 + 1)
        x = jnp.ones((4,))
        sig = _sig(((4,),))
        exe, info = compilestore.resolve(
            "s", lambda: fn.lower(x), sig, program="p")
        assert info["store"] == "miss" and info["mode"] == "compile"
        assert store.entry_count() == 1
        # a fresh jitted fn (fresh jit cache): the entry is served
        fn2 = jax.jit(lambda x: x * 2 + 1)
        c0 = _compiles()
        exe2, info2 = compilestore.resolve(
            "s", lambda: fn2.lower(x), sig, program="p")
        assert info2["store"] == "hit" and info2["mode"] == "deserialize"
        assert _compiles() == c0               # zero XLA compiles
        assert np.array_equal(np.asarray(exe2(x)), np.asarray(exe(x)))

    def test_reject_recompiles_and_overwrites(self, store):
        fn = jax.jit(lambda x: x - 3)
        x = jnp.ones((4,))
        sig = _sig(((4,),))
        _, info = compilestore.resolve("s", lambda: fn.lower(x), sig,
                                       program="p")
        path = store._store_path(info["key"])
        with open(path, "wb") as f:   # dl4jlint: disable=atomic-commit
            f.write(b"garbage")
        exe, info2 = compilestore.resolve(
            "s", lambda: fn.lower(x), sig, program="p")
        assert info2["store"] == "reject" and info2["mode"] == "compile"
        assert float(exe(x)[0]) == -2.0
        # overwritten: the NEXT resolve hits
        _, info3 = compilestore.resolve(
            "s", lambda: jax.jit(lambda x: x - 3).lower(x), sig,
            program="p")
        assert info3["store"] == "hit"

    def test_compile_seconds_histogram_by_mode(self, store):
        fn = jax.jit(lambda x: x + 7)
        x = jnp.ones((3,))
        sig = _sig(((3,),))
        compilestore.resolve("s", lambda: fn.lower(x), sig, program="q")
        compilestore.resolve("s", lambda: jax.jit(lambda x: x + 7)
                             .lower(x), sig, program="q")
        fam = telemetry.get_registry().histogram(
            "dl4j_compile_seconds", labelnames=("mode",))
        modes = {dict(k).get("mode"): h.count for k, h in fam.children()}
        assert modes.get("compile", 0) >= 1
        assert modes.get("deserialize", 0) >= 1


# ---------------------------------------------------------------------------
# serving: warm registration performs ZERO compiles (the tier-1 smoke)
# ---------------------------------------------------------------------------

class TestServingWarmRegistration:
    def test_warm_registration_zero_compiles_ledger_asserted(
            self, store):
        from deeplearning4j_tpu.serving import (
            BucketLadder, InferenceSession)

        X, _ = _data(8)
        net1 = _mlp(seed=3)
        net2 = _mlp(seed=3)   # same conf => same program digest
        net2.setParams(net1.params().toNumpy())
        session = InferenceSession()
        try:
            session.register("cold", net1, example_shape=(4,),
                             ladder=BucketLadder((1, 8)), warmup=True)
            led = compile_ledger.get_ledger()
            assert led.causes("cold:v1") == {"first_compile": 1,
                                             "new_bucket": 1}
            c0 = _compiles()
            session.register("warm", net2, example_shape=(4,),
                             ladder=BucketLadder((1, 8)), warmup=True)
            # THE acceptance assertion: ledger-counted, not timed
            assert _compiles() == c0
            assert led.causes("warm:v1") == {"cache_hit": 2}
            recs = led.describe("warm:v1")
            assert all(r["mode"] == "deserialize" and
                       r["store"] == "hit" for r in recs)
            # the deserialized ladder serves bit-identically
            y1 = session.predict("cold", X)
            y2 = session.predict("warm", X)
            assert np.array_equal(np.asarray(y1), np.asarray(y2))
        finally:
            session.close()

    def test_reregister_same_spec_is_cache_hit_not_rewarm(self, store):
        # ISSUE 13 satellite: the old `rewarm` cause conflated a real
        # recompile with what is now a store hit; entries-per-
        # registration stays exact (ladder size each time)
        from deeplearning4j_tpu.serving import (
            BucketLadder, InferenceSession)

        net = _mlp(seed=4)
        session = InferenceSession()
        try:
            session.register("m", net, example_shape=(4,),
                             ladder=BucketLadder((1, 8)), warmup=True)
            session.register("m", net, example_shape=(4,),
                             ladder=BucketLadder((1, 8)), warmup=True)
            led = compile_ledger.get_ledger()
            causes = led.causes("m:v1")
            assert causes == {"first_compile": 1, "new_bucket": 1,
                              "cache_hit": 2}
            assert "rewarm" not in causes
            assert len(led.describe("m:v1")) == 4   # 2 registrations x 2
        finally:
            session.close()

    def test_debug_compiles_store_section(self, store):
        from deeplearning4j_tpu.serving import (
            BucketLadder, InferenceSession)
        from deeplearning4j_tpu.ui.server import UIServer
        import urllib.request

        net = _mlp(seed=5)
        session = InferenceSession()
        ui = UIServer.getInstance().start(port=0)
        try:
            session.register("dbg", net, example_shape=(4,),
                             ladder=BucketLadder((1,)), warmup=True)
            payload = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{ui.port}/debug/compiles").read())
            sec = payload["store"]
            assert sec["enabled"] is True
            assert sec["entries"] >= 1 and sec["bytes_on_disk"] > 0
            assert {"hits", "misses", "rejects", "puts",
                    "evictions"} <= set(sec)
        finally:
            ui.stop()
            session.close()


# ---------------------------------------------------------------------------
# train steps: StoredJit through fit, bit-identical math
# ---------------------------------------------------------------------------

class TestStoredTrainStep:
    def test_warm_fit_zero_step_compiles_and_bit_identical(self, store):
        X, y = _data(8)
        cold = _mlp(seed=7)
        cold.fit([(X, y)], 2)
        led = compile_ledger.get_ledger()
        assert led.causes("fit") == {"first_compile": 1}
        warm = _mlp(seed=7)   # fresh net, same conf: the restart shape
        warm.fit([(X, y)], 2)
        assert led.causes("fit") == {"first_compile": 1, "cache_hit": 1}
        rec = [r for r in led.describe("fit")
               if r["cause"] == "cache_hit"][0]
        assert rec["mode"] == "deserialize" and rec["kind"] == "step"
        assert np.array_equal(_flat(cold), _flat(warm))

    def test_store_on_equals_store_off_bit_for_bit(self, tmp_path):
        X, y = _data(8)
        prev_led = compile_ledger.set_ledger(
            compile_ledger.CompileLedger())
        telemetry.enable()
        try:
            compilestore.configure(enabled=False)
            off = _mlp(seed=9)
            off.fit([(X, y)], 3)
            compilestore.configure(root=str(tmp_path / "xc2"))
            on_cold = _mlp(seed=9)
            on_cold.fit([(X, y)], 3)     # compiled via StoredJit
            on_warm = _mlp(seed=9)
            on_warm.fit([(X, y)], 3)     # deserialized via StoredJit
            assert np.array_equal(_flat(off), _flat(on_cold))
            assert np.array_equal(_flat(off), _flat(on_warm))
        finally:
            compilestore.configure(enabled=False)
            compile_ledger.set_ledger(prev_led)

    def test_deserialized_step_safe_with_host_borrowed_params(
            self, store):
        """Donation-safety regression: setParams leaves numpy VIEWS of
        one flat host array in net._params; jax CPU zero-copies them,
        and donating borrowed buffers through a deserialize_and_load
        executable corrupted the shared backing store (segfault on the
        second step) until StoredJit's first-call owned-clone."""
        X, y = _data(8)
        n1 = _mlp(seed=11)
        n1.fit([(X, y)], 1)              # cold: compiles + stores
        ref = _mlp(seed=11)
        ref.setParams(n1.params().toNumpy())
        n2 = _mlp(seed=11)
        n2.setParams(n1.params().toNumpy())   # numpy views installed
        # ref runs store-OFF (plain jit), n2 runs store-ON (hit)
        compilestore.configure(enabled=False)
        try:
            ref.fit([(X, y)], 3)
        finally:
            compilestore.configure(root=store.root)
        n2.fit([(X, y)], 3)              # 3 chained donated steps
        assert compile_ledger.get_ledger().causes("fit").get(
            "cache_hit", 0) >= 1
        assert np.array_equal(_flat(ref), _flat(n2))

    def test_graph_site_warm_fit_cache_hit(self, store):
        from deeplearning4j_tpu.nn import (
            ComputationGraph, DenseLayer, LossFunction,
            NeuralNetConfiguration, OutputLayer)

        def build():
            conf = (NeuralNetConfiguration.Builder().seed(17)
                    .graphBuilder().addInputs("in")
                    .addLayer("h", DenseLayer.Builder().nIn(4).nOut(8)
                              .activation("relu").build(), "in")
                    .addLayer("out", OutputLayer.Builder().nIn(8)
                              .nOut(2).activation("softmax")
                              .lossFunction(LossFunction.MCXENT)
                              .build(), "h")
                    .setOutputs("out").build())
            return ComputationGraph(conf).init()

        X, y = _data(8)
        g1 = build()
        g1.fit([(X, y)], 2)
        g2 = build()
        g2.fit([(X, y)], 2)
        led = compile_ledger.get_ledger()
        assert led.causes("graph") == {"first_compile": 1,
                                       "cache_hit": 1}
        assert np.array_equal(
            np.asarray(g1.params().toNumpy()),
            np.asarray(g2.params().toNumpy()))

    def test_sharded_site_warm_fit_cache_hit(self, store):
        from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

        X, y = _data(8)
        n1 = _mlp(seed=19)
        ShardedTrainer(n1).fit([(X, y)], 2)
        n2 = _mlp(seed=19)
        ShardedTrainer(n2).fit([(X, y)], 2)
        led = compile_ledger.get_ledger()
        assert led.causes("sharded") == {"first_compile": 1,
                                         "cache_hit": 1}
        assert np.array_equal(_flat(n1), _flat(n2))

    def test_bucket_growth_resolves_second_signature(self, store):
        X, y = _data(4)
        X2, y2 = _data(16)
        net = _mlp(seed=13)
        net.fit([(X, y)], 1)
        net.fit([(X2, y2)], 1)   # bigger bucket: second executable
        assert store.entry_count() >= 3   # 2 steps + owned-clone(s)
        warm = _mlp(seed=13)
        warm.fit([(X, y)], 1)
        warm.fit([(X2, y2)], 1)
        causes = compile_ledger.get_ledger().causes("fit")
        assert causes.get("cache_hit", 0) == 2


# ---------------------------------------------------------------------------
# supervisor: kill-and-resume over a warm store
# ---------------------------------------------------------------------------

class TestSupervisorWarmResume:
    def _run(self, tmp_path, store):
        from deeplearning4j_tpu.resilience import (
            FaultPlan, Supervisor, SupervisorConfig)

        X, y = _data(16)
        data = [(X[i:i + 4], y[i:i + 4]) for i in range(0, 16, 4)]
        from deeplearning4j_tpu.parallel.elastic import ElasticTrainer

        ref = _mlp(seed=21)
        ElasticTrainer(ref, str(tmp_path / "ref"),
                       everyNIterations=1000).fit(data, 4)
        plan = FaultPlan().preempt_at(7)
        sup = Supervisor(
            lambda: _mlp(seed=21), str(tmp_path / "sup"),
            config=SupervisorConfig(max_restarts=2, backoff_base=0.0),
            faults=plan, everyNIterations=3)
        net = sup.run(data, epochs=4)
        return ref, sup, net

    def test_resume_zero_step_compiles_and_bit_identical(
            self, tmp_path, store):
        ref, sup, net = self._run(tmp_path, store)
        assert sup.restarts == 1 and sup.reasons == ["preemption"]
        causes = compile_ledger.get_ledger().causes("fit")
        # ref run compiled once (+ stored); the supervisor's first
        # attempt AND the post-kill resume both deserialize: the
        # ledger shows no recompile cause anywhere at the fit site —
        # this is the "zero XLA compiles on resume" assertion
        assert causes == {"first_compile": 1, "cache_hit": 2}
        assert net._iteration == ref._iteration == 16
        assert np.array_equal(_flat(ref), _flat(net))

    def test_corrupt_entry_degrades_to_compile_and_overwrite(
            self, tmp_path, store):
        from deeplearning4j_tpu.resilience import (
            FaultPlan, Supervisor, SupervisorConfig)

        X, y = _data(16)
        data = [(X[i:i + 4], y[i:i + 4]) for i in range(0, 16, 4)]
        cold = _mlp(seed=23)
        cold.fit(data, 1)        # populate the store
        # corrupt EVERY entry (step + clone): resume must reject,
        # recompile, overwrite — and still finish correctly
        for row in store.contents():
            path = store._store_path(row["key"])
            with open(path, "rb") as f:
                raw = f.read()
            with open(path, "wb") as f:
                f.write(raw[: len(raw) // 2])
        plan = FaultPlan().preempt_at(7)
        sup = Supervisor(
            lambda: _mlp(seed=23), str(tmp_path / "sup2"),
            config=SupervisorConfig(max_restarts=2, backoff_base=0.0),
            faults=plan, everyNIterations=3)
        net = sup.run(data, epochs=4)
        assert net._iteration == 16
        causes = compile_ledger.get_ledger().causes("fit")
        assert causes.get("cache_reject", 0) >= 1
        assert store.stats["rejects"] >= 1
        # overwritten: one more fresh net now hits
        c0 = _compiles()
        again = _mlp(seed=23)
        again.fit(data, 1)
        assert _compiles() == c0

    def test_warm_store_tightens_watchdog_grace(self, store):
        from deeplearning4j_tpu.resilience import supervisor as sup_mod
        from deeplearning4j_tpu.resilience.supervisor import (
            SupervisorConfig, Watchdog)

        cfg = SupervisorConfig(stall_timeout=2.0)
        assert not compilestore.is_warm()
        assert sup_mod.resume_grace(cfg) is None   # cold: Watchdog 30s
        assert Watchdog(2.0, warmup_grace=None).warmup_grace == 30.0
        # a shared store holding only OTHER jobs' serving ladders must
        # not promise a train-step hit (review finding): no tightening
        store.put(entry_key(_sig(((9, 9),)), "q"), b"x",
                  site="model:v1")
        assert compilestore.is_warm()     # store-global: has entries
        assert not compilestore.is_warm(
            sites=sup_mod.TRAIN_STEP_SITES)
        assert sup_mod.resume_grace(cfg) is None
        store.put(entry_key(_sig(), "p"), b"x", site="fit")
        assert compilestore.is_warm(sites=sup_mod.TRAIN_STEP_SITES)
        assert sup_mod.resume_grace(cfg) == 5.0    # floor
        cfg2 = SupervisorConfig(stall_timeout=60.0)
        assert sup_mod.resume_grace(cfg2) == 60.0
        cfg3 = SupervisorConfig(stall_timeout=2.0, stall_warmup=11.0)
        assert sup_mod.resume_grace(cfg3) == 11.0  # explicit wins


# ---------------------------------------------------------------------------
# disabled / default-off contracts
# ---------------------------------------------------------------------------

class TestOffByDefault:
    def test_unconfigured_process_is_off(self):
        # the suite must not inherit a store from the environment
        assert os.environ.get(compilestore.ENV_ROOT) is None
        compilestore.configure(enabled=False)
        assert not compilestore.enabled()
        assert compilestore.describe() == {"enabled": False}
        assert not compilestore.is_warm()

    def test_train_step_is_plain_jit_when_off(self):
        compilestore.configure(enabled=False)
        net = _mlp(seed=31)
        net._refresh_train_step()
        assert not isinstance(net._train_step, compilestore.StoredJit)

    def test_train_step_wrapped_when_on(self, store):
        net = _mlp(seed=31)
        net._refresh_train_step()
        assert isinstance(net._train_step, compilestore.StoredJit)


# ---------------------------------------------------------------------------
# the whole matrix, cross-process (slow): tools/coldstart.py
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestColdstartCrossProcess:
    def test_coldstart_report_acceptance(self, tmp_path):
        tools = pathlib.Path(__file__).resolve().parent.parent / "tools"
        sys.path.insert(0, str(tools))
        try:
            import coldstart
        finally:
            sys.path.remove(str(tools))
        report = coldstart.run_report(
            store_dir=str(tmp_path / "store"))
        s, r = report["serving"], report["resume"]
        # zero XLA compiles warm, ledger-asserted in the CHILD process
        assert s["warm"]["compiles"] == 0
        assert set(s["warm"]["causes"]) == {"cache_hit"}
        assert set(r["warm"]["fit_causes"]) == {"cache_hit"}
        # acceptance: warm registration >= 5x faster than cold
        assert s["speedup"] >= 5.0, report
        # resume params bit-identical to the cold-resumed run
        assert r["warm"]["params_sha"] == r["cold"]["params_sha"]
        assert report["store_contents"]


# ---------------------------------------------------------------------------
# decode engines through the store (ISSUE 20 satellite): warm engine
# construction deserializes every executable — zero XLA compiles
# ---------------------------------------------------------------------------

class TestDecodeWarmStore:
    def test_warm_decode_engine_zero_compiles(self, store):
        from deeplearning4j_tpu.serving import InferenceSession
        from deeplearning4j_tpu.serving.decode import (
            TransformerDecodeModel)

        def _model():
            # fixed seed => identical params => identical tokens; same
            # geometry => same store program for every decode lane
            return TransformerDecodeModel.init(
                vocab=16, hidden=8, n_layers=1, n_heads=2,
                max_len=32, seed=0, max_slots=2, page=4,
                max_pages_per_slot=8)

        session = InferenceSession()
        try:
            before = _compiles()
            session.register_decoder("cold", _model(), warmup=True)
            # the cold path really compiles — the zero-delta below is
            # a store hit, not a dead counter
            assert _compiles() > before
            base = session.decode("cold", [1, 2, 3],
                                  max_new_tokens=4)
            c0 = _compiles()
            led = compile_ledger.get_ledger()
            n_recs = len(led.describe("decode:step"))
            session.register_decoder("warm", _model(), warmup=True)
            # THE acceptance assertion: warm engine construction
            # resolves from the store, ledger-counted not timed
            assert _compiles() == c0
            fresh = led.describe("decode:step")[n_recs:]
            assert fresh
            assert all(r["mode"] == "deserialize" and
                       r["store"] == "hit" for r in fresh)
            # and the deserialized engine decodes identically
            assert session.decode("warm", [1, 2, 3],
                                  max_new_tokens=4) == base
            assert _compiles() == c0
        finally:
            session.close()
