"""ISSUE 14: device-memory observability — the HBM ownership ledger,
OOM forensics, and admission-time capacity planning.

Covers the acceptance criteria:
- claim lifecycle across every shipped registrar (train fit/graph/
  sharded, prefetch staging, checkpoint snapshot clones, serving
  executables + replica placed-args, decode KV pools incl. the
  speculative draft lane);
- census: claims reconciled against live device usage with the
  unattributed residual below threshold on the CPU backend;
- a forced allocation failure at each instrumented seam yields a
  typed DeviceOomError plus a flight ``oom`` event naming site,
  requested bytes, and the top claims — both fault-injected
  (resilience/faults.py InjectedOom) and via a REAL oversized
  allocation;
- an oversized serving registration / KV pool is rejected by the
  planner with a structured CapacityError BEFORE any XLA compile
  (compile-ledger-asserted);
- telemetry.disable(): zero registry AND zero ledger calls per step,
  bit-identical params.
"""

import gc
import json
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import flight, memledger
from deeplearning4j_tpu.telemetry.memledger import (
    CapacityError, DeviceOomError, MemLedger)
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
from deeplearning4j_tpu.resilience.faults import FaultPlan, InjectedOom


@pytest.fixture
def fresh_ledger():
    """Fresh registry + fresh ledger + clean flight ring; telemetry
    enabled. Restores everything after."""
    reg = MetricsRegistry()
    prev_reg = telemetry.set_registry(reg)
    prev_led = memledger.set_ledger(MemLedger())
    memledger.configure(budget_bytes=None, min_headroom_bytes=None)
    telemetry.enable()
    flight.get_recorder().clear()
    yield reg
    telemetry.set_registry(prev_reg)
    memledger.set_ledger(prev_led)
    memledger.configure(budget_bytes=None, min_headroom_bytes=None)
    telemetry.enable()


def _tiny_net(seed=1, n_in=4, hidden=8, n_out=2):
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)

    conf = (NeuralNetConfiguration.Builder().seed(seed).list()
            .layer(DenseLayer.Builder().nIn(n_in).nOut(hidden)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(n_out)
                   .activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    return MultiLayerNetwork(conf).init()


def _tiny_data(n=16, seed=0, n_in=4, n_out=2):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.randint(0, n_out, n)]
    return X, y


def _oom_events(site=None):
    evts = flight.get_recorder().events("oom")
    if site is not None:
        evts = [e for e in evts if e["site"] == site]
    return evts


# ---------------------------------------------------------------------------
# ledger core
# ---------------------------------------------------------------------------

class TestLedgerCore:
    def test_claim_update_release_totals(self, fresh_ledger):
        led = memledger.get_memledger()
        c = memledger.claim("train", "t1", nbytes=100, device="cpu:0")
        assert led.total("train") == 100
        c.update(nbytes=250)
        assert led.total("train") == 250
        c2 = memledger.claim("train", "t2", nbytes=50, device="cpu:0")
        assert led.total("train") == 300
        c.release()
        assert led.total("train") == 50
        assert c.released and led.get("train", "t1") is None
        c2.release()
        assert led.total() == 0

    def test_reclaim_same_key_restates(self, fresh_ledger):
        led = memledger.get_memledger()
        memledger.claim("kv_cache", "e:target", nbytes=100)
        memledger.claim("kv_cache", "e:target", nbytes=400)
        assert led.total("kv_cache") == 400
        assert len(led.claims("kv_cache")) == 1

    def test_release_prefix(self, fresh_ledger):
        memledger.claim("executable", "m:v1:1x4", nbytes=10)
        memledger.claim("executable", "m:v1:8x4", nbytes=20)
        memledger.claim("executable", "m:v2:1x4", nbytes=30)
        n = memledger.release_prefix("executable", "m:v1:")
        assert n == 2
        led = memledger.get_memledger()
        assert led.total("executable") == 30

    def test_tree_bytes(self, fresh_ledger):
        import jax

        tree = {"a": np.zeros((4, 4), np.float32),
                "b": [np.zeros((2,), np.float64), "not-an-array"],
                "c": jax.ShapeDtypeStruct((8,), np.float32)}
        assert memledger.tree_bytes(tree) == 64 + 16 + 32

    def test_claim_none_when_disabled(self, fresh_ledger):
        telemetry.disable()
        try:
            assert memledger.claim("train", "x", nbytes=1) is None
        finally:
            telemetry.enable()

    def test_census_arithmetic_and_gauges(self, fresh_ledger):
        memledger.claim("train", "x", nbytes=128)
        snap = memledger.census()
        dev = memledger._device_label()
        row = snap["devices"][dev]
        assert row["claimed"]["train"] == 128
        assert row["unattributed"] == max(0, row["in_use"]
                                          - row["claimed_bytes"])
        memledger.refresh_metrics()
        reg_snap = fresh_ledger.snapshot()
        # local families are scrape-only: read via render, not snapshot
        from deeplearning4j_tpu.telemetry import prometheus

        text = prometheus.render(fresh_ledger, collect_system=False)
        assert "dl4j_device_memory_claimed_bytes" in text
        assert 'category="train"' in text
        assert 'category="unattributed"' in text
        assert not any("dl4j_device_memory_claimed_bytes" in k
                       for k in reg_snap)   # excluded from aggregation


class _Owner:
    """Something a claim can be keyed to: weakly referable."""


class TestAnOwnersFinalizer:
    """A collection can begin between any two instructions, inside the
    ledger's own critical sections too, and `MemLedger._lock` is not
    re-entrant: the finalizer of `claim_for_owner` queues its key and
    takes no lock (a tier-1 worker once waited for itself for ever in
    `claims()`), and the ledger drops the claim at its next call."""

    def test_it_does_not_wait_for_the_ledgers_lock(self, fresh_ledger):
        import threading

        led = memledger.get_memledger()
        owners = [_Owner()]
        memledger.claim_for_owner(owners[0], "train", "fit", nbytes=64)
        # whoever holds the lock (the finalizer's own thread, when the
        # collection began under it): the finalizer must come back
        dropper = threading.Thread(target=owners.pop, daemon=True)
        with led._lock:
            dropper.start()
            dropper.join(timeout=10)
            waits = dropper.is_alive()
        dropper.join(timeout=10)
        assert not waits
        assert led.claims("train") == []

    def test_a_collection_under_the_lock_comes_back(self, fresh_ledger):
        import threading

        led = memledger.get_memledger()
        owners = [_Owner()]
        memledger.claim_for_owner(owners[0], "train", "fit", nbytes=64)

        def collect_under_the_lock():
            with led._lock:         # as `claims()` holds it
                owners.pop()        # the finalizer runs here, in the
                gc.collect()        # thread that holds the lock

        # in a thread of its own, so that a finalizer that waits fails
        # this test and does not stop the run
        holder = threading.Thread(target=collect_under_the_lock,
                                  daemon=True)
        holder.start()
        holder.join(timeout=10)
        assert not holder.is_alive()
        assert led.total("train") == 0

    @pytest.mark.parametrize("read", [
        lambda led, name: led.claims("train"),
        lambda led, name: led.get("train", name),
        lambda led, name: led.total("train"),
        lambda led, name: led.top(),
        lambda led, name: led.release_prefix("train", "fit")],
        ids=["claims", "get", "total", "top", "release_prefix"])
    def test_every_read_sees_the_claim_gone(self, fresh_ledger, read):
        led = memledger.get_memledger()
        owner = _Owner()
        name = memledger.claim_for_owner(owner, "train", "fit",
                                         nbytes=64).name
        assert read(led, name)
        del owner
        gc.collect()
        assert not read(led, name)

    def test_the_scrape_publishes_the_total_without_it(self, fresh_ledger):
        from deeplearning4j_tpu.telemetry import prometheus

        dev = memledger._device_label()
        owner = _Owner()
        memledger.claim_for_owner(owner, "train", "fit", nbytes=64)
        memledger.claim("train", "kept", nbytes=32, device=dev)
        del owner
        gc.collect()
        memledger.refresh_metrics()
        text = prometheus.render(fresh_ledger, collect_system=False)
        line = [ln for ln in text.splitlines()
                if ln.startswith("dl4j_device_memory_claimed_bytes")
                and 'category="train"' in ln]
        assert len(line) == 1 and float(line[0].split()[-1]) == 32


# ---------------------------------------------------------------------------
# registrars: train loops
# ---------------------------------------------------------------------------

class TestTrainRegistrars:
    def test_fit_claims_train_memory(self, fresh_ledger):
        net = _tiny_net()
        X, y = _tiny_data()
        net.fit([(X, y)], 2)
        claims = [c for c in memledger.get_memledger().claims("train")
                  if c.name.startswith("fit#")]
        assert len(claims) == 1
        expected = memledger.tree_bytes(
            {"p": net._params, "s": net._states, "o": net._opt_states,
             "prec": net._prec_state})
        assert claims[0].bytes == expected > 0

    def test_two_nets_hold_two_claims(self, fresh_ledger):
        # per-owner keys: a second net fitting through the same loop
        # label must not re-state (and so mis-attribute) the first's
        X, y = _tiny_data()
        net_a, net_b = _tiny_net(41), _tiny_net(42)
        net_a.fit([(X, y)], 1)
        net_b.fit([(X, y)], 1)
        led = memledger.get_memledger()
        claims = [c for c in led.claims("train")
                  if c.name.startswith("fit#")]
        assert len(claims) == 2
        per_net = memledger.tree_bytes(
            {"p": net_a._params, "s": net_a._states,
             "o": net_a._opt_states, "prec": net_a._prec_state})
        assert led.total("train") == 2 * per_net
        # ... and the claim dies with its net (weakref finalizer)
        del net_b
        gc.collect()
        claims = [c for c in led.claims("train")
                  if c.name.startswith("fit#")]
        assert len(claims) == 1

    def test_graph_fit_claims(self, fresh_ledger):
        from deeplearning4j_tpu.nn import (
            ComputationGraph, DenseLayer, LossFunction,
            NeuralNetConfiguration, OutputLayer)

        conf = (NeuralNetConfiguration.Builder().seed(13)
                .graphBuilder()
                .addInputs("in")
                .addLayer("d", DenseLayer.Builder().nIn(4).nOut(8)
                          .activation("relu").build(), "in")
                .addLayer("out", OutputLayer.Builder().nIn(8).nOut(2)
                          .activation("softmax")
                          .lossFunction(LossFunction.MCXENT).build(),
                          "d")
                .setOutputs("out")
                .build())
        net = ComputationGraph(conf).init()
        X, y = _tiny_data()
        net.fit([(X, y)], 2)
        claims = [c for c in memledger.get_memledger().claims("train")
                  if c.name.startswith("graph#")]
        assert len(claims) == 1 and claims[0].bytes > 0

    def test_sharded_fit_claims(self, fresh_ledger):
        from deeplearning4j_tpu.datasets import DataSet
        from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

        net = _tiny_net(12)
        X, y = _tiny_data()
        ShardedTrainer(net).fit([DataSet(X, y)], epochs=2)
        claims = [c for c in memledger.get_memledger().claims("train")
                  if c.name.startswith("sharded#")]
        assert len(claims) == 1 and claims[0].bytes > 0


# ---------------------------------------------------------------------------
# registrars: prefetch + checkpoint
# ---------------------------------------------------------------------------

class TestPrefetchRegistrar:
    def test_staged_claim_lifecycle(self, fresh_ledger):
        from deeplearning4j_tpu.datasets import ListDataSetIterator
        from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher

        rng = np.random.default_rng(0)
        data = [(rng.normal(size=(4, 3)).astype(np.float32),
                 np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)])
                for _ in range(6)]
        pf = DevicePrefetcher(ListDataSetIterator(data, 4), depth=2,
                              loop="memtest")
        assert pf.hasNext()
        led = memledger.get_memledger()
        deadline = time.time() + 5.0
        while led.get("prefetch", "memtest") is None \
                and time.time() < deadline:
            time.sleep(0.01)
        c = led.get("prefetch", "memtest")
        assert c is not None
        # capacity claim: depth + 1 staged batches' device bytes
        per_batch = 4 * 3 * 4 + 4 * 2 * 4
        assert c.bytes == per_batch * (2 + 1)
        while pf.hasNext():
            pf.next()
        pf.close()
        assert led.get("prefetch", "memtest") is None

    def test_released_on_reset_restated_next_epoch(self, fresh_ledger):
        from deeplearning4j_tpu.datasets import ListDataSetIterator
        from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher

        rng = np.random.default_rng(1)
        data = [(rng.normal(size=(2, 3)).astype(np.float32),
                 np.eye(2, dtype=np.float32)[rng.integers(0, 2, 2)])
                for _ in range(3)]
        pf = DevicePrefetcher(ListDataSetIterator(data, 2), depth=1,
                              loop="memtest2")
        pf.next()
        pf.reset()
        led = memledger.get_memledger()
        assert led.get("prefetch", "memtest2") is None
        pf.next()    # producer restarted: claim restated
        deadline = time.time() + 5.0
        while led.get("prefetch", "memtest2") is None \
                and time.time() < deadline:
            time.sleep(0.01)
        assert led.get("prefetch", "memtest2") is not None
        pf.close()


class TestCheckpointRegistrar:
    def test_snapshot_claim_released_after_write(self, fresh_ledger,
                                                 tmp_path):
        from deeplearning4j_tpu.resilience.async_ckpt import (
            AsyncCheckpointer)

        net = _tiny_net(3)
        X, y = _tiny_data()
        net.fit([(X, y)], 1)
        ck = AsyncCheckpointer(str(tmp_path), keepLast=2)
        led = memledger.get_memledger()
        snap = ck.snapshot(net, 7)
        c = led.claims("checkpoint")
        assert len(c) == 1 and c[0].bytes > 0 and "7" in c[0].name
        ck.submit(snap)
        assert ck.drain(10.0)
        ck.close()
        assert led.claims("checkpoint") == []


# ---------------------------------------------------------------------------
# registrars: serving executables, replica args, decode KV pools
# ---------------------------------------------------------------------------

class TestServingRegistrars:
    def test_executable_claims_with_breakdown(self, fresh_ledger):
        from deeplearning4j_tpu.serving import ModelRegistry

        net = _tiny_net(5)
        reg = ModelRegistry()
        reg.register("memsvc", net, example_shape=(4,), ladder=[1, 4],
                     warmup=True)
        led = memledger.get_memledger()
        claims = led.claims("executable")
        assert {c.name for c in claims} == {"memsvc:v1:1x4",
                                            "memsvc:v1:4x4"}
        for c in claims:
            # memory_analysis breakdown rides in the claim meta
            assert set(c.meta) >= {"argument", "output", "temp", "code"}
            assert c.bytes == (c.meta["temp"] + c.meta["output"]
                               + c.meta["code"])
        reg.unregister("memsvc")
        assert led.claims("executable") == []

    def test_reregister_same_version_releases_replaced_claims(
            self, fresh_ledger):
        from deeplearning4j_tpu.serving import ModelRegistry

        reg = ModelRegistry()
        reg.register("roll", _tiny_net(16), example_shape=(4,),
                     ladder=[1, 4, 16], warmup=True)
        led = memledger.get_memledger()
        assert len(led.claims("executable")) == 3
        # rolling same-version replace with a SMALLER ladder: the
        # dropped bucket's claim must not linger
        reg.register("roll", _tiny_net(17), example_shape=(4,),
                     ladder=[1, 4], warmup=True)
        names = {c.name for c in led.claims("executable")}
        assert names == {"roll:v1:1x4", "roll:v1:4x4"}

    def test_replica_args_claims_lifecycle(self, fresh_ledger):
        from deeplearning4j_tpu.serving import InferenceSession

        net = _tiny_net(6)
        session = InferenceSession()
        session.register("memrep", net, example_shape=(4,),
                         ladder=[1, 2], replicas=2, warmup=True)
        y = session.predict("memrep", np.zeros((1, 4), np.float32))
        assert y.shape == (1, 2)
        led = memledger.get_memledger()
        claims = led.claims("replica_args")
        assert len(claims) == 2          # one pinned arg copy per replica
        assert all(c.bytes > 0 for c in claims)
        session.close()
        assert led.claims("replica_args") == []


class TestDecodeRegistrars:
    def _paged_model(self, hidden=16, **kw):
        from deeplearning4j_tpu.serving.decode import (
            TransformerDecodeModel)

        kw.setdefault("vocab", 32)
        kw.setdefault("n_layers", 1)
        kw.setdefault("n_heads", 2)
        kw.setdefault("max_len", 64)
        kw.setdefault("max_slots", 2)
        kw.setdefault("page", 8)
        kw.setdefault("max_pages_per_slot", 4)
        return TransformerDecodeModel.init(hidden=hidden, **kw)

    def test_kv_claims_and_health_bytes_both_lanes(self, fresh_ledger):
        from deeplearning4j_tpu.serving.decode import DecodeEngine
        from deeplearning4j_tpu.serving.speculative import (
            SpeculativeConfig)

        target = self._paged_model(hidden=16)
        draft = self._paged_model(hidden=8)
        engine = DecodeEngine(
            target, name="memdec",
            speculative=SpeculativeConfig(draft=draft, k=2))
        led = memledger.get_memledger()
        by_name = {c.name: c for c in led.claims("kv_cache")}
        assert by_name["memdec:target"].bytes == \
            memledger.tree_bytes(engine._state) > 0
        assert by_name["memdec:draft"].bytes == \
            engine._spec.pool_bytes > 0
        # the satellite: KV pool BYTES (not just occupancy) in
        # health(), both lanes
        h = engine.health()
        assert h["kv_pages"]["pool_bytes"] == by_name[
            "memdec:target"].bytes
        assert h["kv_pages"]["used_bytes"] == 0
        assert h["speculative"]["kv_pages"]["pool_bytes"] == by_name[
            "memdec:draft"].bytes
        engine.close()
        assert led.claims("kv_cache") == []

    def test_failed_engine_init_leaks_no_claim(self, fresh_ledger):
        # claims register LAST in __init__: a draft-geometry
        # validation raise must not leave a target claim for an
        # engine that never existed
        from deeplearning4j_tpu.serving.decode import (
            DecodeEngine, DecodeError)
        from deeplearning4j_tpu.serving.speculative import (
            SpeculativeConfig)

        target = self._paged_model(hidden=16)
        bad_draft = self._paged_model(hidden=8, page=4)  # page mismatch
        with pytest.raises(DecodeError):
            DecodeEngine(target, name="leaky",
                         speculative=SpeculativeConfig(draft=bad_draft))
        assert memledger.get_memledger().claims("kv_cache") == []

    def test_health_used_bytes_track_reservation(self, fresh_ledger):
        from deeplearning4j_tpu.serving.decode import DecodeEngine

        engine = DecodeEngine(self._paged_model(), name="memdec2")
        engine.warmup()
        req = engine.submit([1, 2, 3], max_new_tokens=4)
        req.result(timeout=30)
        # while idle again, used returns to 0; probe mid-flight signal
        # via a fresh request held by tiny pool accounting instead:
        h = engine.health()
        assert h["kv_pages"]["pool_bytes"] > 0
        assert h["kv_pages"]["used_bytes"] == (
            h["kv_pages"]["pool_bytes"]
            // (engine.model.n_pages + 1)) * (
                engine.model.n_pages - h["kv_pages"]["free"])
        engine.close()


# ---------------------------------------------------------------------------
# census: residual attribution quality on the CPU backend
# ---------------------------------------------------------------------------

class TestCensusResidual:
    def test_residual_below_threshold_for_claimed_workload(
            self, fresh_ledger):
        """The attribution-accuracy check the ISSUE asks for: on the
        CPU backend (live-array census), the in-use DELTA from a
        claimed training workload is claimed to within 40% — i.e. the
        unattributed residual the ledger would report for this
        workload stays below threshold."""
        dev = memledger._device_label()
        gc.collect()
        before = memledger.census()["devices"][dev]["in_use"]
        net = _tiny_net(9, n_in=128, hidden=256, n_out=8)
        X, y = _tiny_data(32, n_in=128, n_out=8)
        net.fit([(X, y)], 1)
        gc.collect()
        row = memledger.census()["devices"][dev]
        led = memledger.get_memledger()
        claimed = led.total(device=dev)
        delta_in_use = row["in_use"] - before
        assert claimed > 0 and delta_in_use > 0
        residual = delta_in_use - claimed
        assert residual <= 0.4 * delta_in_use, (
            f"unattributed residual {residual} of {delta_in_use} "
            f"delta bytes (claimed {claimed})")


# ---------------------------------------------------------------------------
# OOM forensics at every instrumented seam
# ---------------------------------------------------------------------------

class TestOomForensics:
    def test_fit_seam_fault_injected(self, fresh_ledger):
        net = _tiny_net(2)
        X, y = _tiny_data()
        net.fit([(X, y)], 1)    # warm + establish claims

        def boom(*a, **k):
            raise InjectedOom(nbytes=123456789, where="fit step")

        net._train_step = boom
        with pytest.raises(DeviceOomError) as ei:
            net.fit([(X, y)], 1)
        err = ei.value
        assert err.site == "train.fit"
        assert err.requested_bytes == 123456789
        assert any(c["category"] == "train" for c in err.claims)
        evts = _oom_events("train.fit")
        assert len(evts) == 1
        assert evts[0]["requested_bytes"] == 123456789
        assert evts[0]["claims"]
        assert isinstance(err.__cause__, InjectedOom)

    def test_fit_seam_non_oom_passes_through(self, fresh_ledger):
        net = _tiny_net(2)
        X, y = _tiny_data()
        net.fit([(X, y)], 1)

        def boom(*a, **k):
            raise ValueError("not an oom")

        net._train_step = boom
        with pytest.raises(ValueError, match="not an oom"):
            net.fit([(X, y)], 1)
        assert _oom_events() == []

    def test_sharded_seam(self, fresh_ledger):
        from deeplearning4j_tpu.datasets import DataSet
        from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

        net = _tiny_net(4)
        X, y = _tiny_data()
        tr = ShardedTrainer(net)
        tr.fit([DataSet(X, y)], epochs=1)

        def boom(*a, **k):
            raise InjectedOom(nbytes=777, where="sharded step")

        tr._step_fn = boom
        with pytest.raises(DeviceOomError) as ei:
            tr.fit([DataSet(X, y)], epochs=1)
        assert ei.value.site == "train.sharded"
        assert _oom_events("train.sharded")

    def test_prefetch_seam_fault_injected_via_plan(self, fresh_ledger):
        from deeplearning4j_tpu.datasets import ListDataSetIterator
        from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher

        rng = np.random.default_rng(0)
        data = [(rng.normal(size=(2, 3)).astype(np.float32),
                 np.eye(2, dtype=np.float32)[rng.integers(0, 2, 2)])
                for _ in range(4)]
        plan = FaultPlan().oom_at(batch=1, nbytes=4096)
        pf = DevicePrefetcher(
            plan.wrap_data(ListDataSetIterator(data, 2)), depth=2)
        with pytest.raises(DeviceOomError) as ei:
            while pf.hasNext():
                pf.next()
        assert ei.value.site == "prefetch.device_put"
        assert ei.value.requested_bytes == 4096
        assert plan.fired("oom") == [("oom", 1)]
        assert _oom_events("prefetch.device_put")
        pf.close()

    def test_prefetch_seam_real_oversized_allocation(self, fresh_ledger):
        """A REAL device allocation failure (no fault injection): the
        producer's prepare asks XLA for ~256 TiB and the consumer's
        next() surfaces the typed error with the parsed byte count."""
        from deeplearning4j_tpu.datasets import ListDataSetIterator
        from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher

        data = [(np.zeros((2, 3), np.float32),
                 np.zeros((2, 2), np.float32))]

        def hungry_prepare(ds):
            import jax.numpy as jnp

            huge = jnp.zeros((1 << 46,), jnp.float32)  # 256 TiB
            huge.block_until_ready()
            return ds

        pf = DevicePrefetcher(ListDataSetIterator(data, 2), depth=1,
                              prepare=hungry_prepare)
        with pytest.raises(DeviceOomError) as ei:
            while pf.hasNext():
                pf.next()
        assert ei.value.site == "prefetch.device_put"
        assert ei.value.requested_bytes == (1 << 46) * 4
        evts = _oom_events("prefetch.device_put")
        assert evts and evts[-1]["requested_bytes"] == (1 << 46) * 4
        pf.close()

    def test_run_batch_seam(self, fresh_ledger):
        from deeplearning4j_tpu.serving import InferenceSession

        net = _tiny_net(7)
        session = InferenceSession()
        entry = session.register("memoom", net, example_shape=(4,),
                                 ladder=[2], warmup=True)

        def boom(x):
            raise InjectedOom(nbytes=2048, where="serving dispatch")

        entry.servable.infer = boom
        with pytest.raises(DeviceOomError) as ei:
            session.predict("memoom", np.zeros((2, 4), np.float32))
        assert ei.value.site == "serving.run_batch"
        evts = _oom_events("serving.run_batch")
        assert evts and evts[0]["model"] == "memoom"
        session.close()

    def test_decode_boundary_seam(self, fresh_ledger):
        from deeplearning4j_tpu.serving.decode import (
            DecodeEngine, TransformerDecodeModel)

        model = TransformerDecodeModel.init(
            vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=2, page=8, max_pages_per_slot=4)
        engine = DecodeEngine(model, name="oomdec")
        engine.warmup()

        def boom(*a, **k):
            raise InjectedOom(nbytes=9999, where="decode step")

        model.step = boom
        req = engine.submit([1, 2], max_new_tokens=3)
        with pytest.raises(DeviceOomError) as ei:
            req.result(timeout=30)
        assert ei.value.site == "decode:oomdec:step"
        assert _oom_events("decode:oomdec:step")
        engine.close()

    def test_snapshot_seam(self, fresh_ledger, tmp_path, monkeypatch):
        from deeplearning4j_tpu.resilience import async_ckpt
        from deeplearning4j_tpu.resilience.async_ckpt import (
            AsyncCheckpointer)

        net = _tiny_net(8)
        X, y = _tiny_data()
        net.fit([(X, y)], 1)
        ck = AsyncCheckpointer(str(tmp_path))

        def boom(tree):
            raise InjectedOom(nbytes=555, where="snapshot clone")

        monkeypatch.setattr(async_ckpt, "_clone_to_device", boom)
        with pytest.raises(DeviceOomError) as ei:
            ck.snapshot(net, 3)
        assert ei.value.site == "ckpt.snapshot"
        assert _oom_events("ckpt.snapshot")
        # no claim leaked for the failed snapshot
        assert memledger.get_memledger().claims("checkpoint") == []
        ck.close()


# ---------------------------------------------------------------------------
# admission-time capacity planning
# ---------------------------------------------------------------------------

class TestCapacityPlanner:
    def test_oversized_registration_rejected_before_any_compile(
            self, fresh_ledger):
        from deeplearning4j_tpu.serving import ModelRegistry
        from deeplearning4j_tpu.telemetry import compile_ledger

        net = _tiny_net(11)
        compiles_before = fresh_ledger.snapshot().get(
            "dl4j_compile_total", 0.0)
        ledger_sites_before = {
            r["site"] for r in compile_ledger.get_ledger().describe()}
        memledger.configure(budget_bytes=50_000)
        try:
            with pytest.raises(CapacityError) as ei:
                ModelRegistry().register(
                    "toolarge", net, example_shape=(4,),
                    ladder=[8192], warmup=True)
        finally:
            memledger.configure(budget_bytes=None)
        err = ei.value
        assert err.site == "serving:toolarge:v1"
        assert err.need_bytes > 50_000
        assert err.headroom_bytes is not None
        assert "buckets" in err.detail
        # LEDGER-ASSERTED: the rejection happened before any XLA
        # compile — no new compile-ledger site, compile counter flat
        sites_after = {
            r["site"] for r in compile_ledger.get_ledger().describe()}
        assert "toolarge:v1" not in sites_after - ledger_sites_before
        assert fresh_ledger.snapshot().get(
            "dl4j_compile_total", 0.0) == compiles_before
        # and the decision is flight-recorded
        plans = flight.get_recorder().events("capacity_plan")
        assert plans and plans[-1]["fits"] is False

    def test_oversized_kv_pool_rejected_before_allocation(
            self, fresh_ledger):
        from deeplearning4j_tpu.serving.decode import (
            DecodeEngine, TransformerDecodeModel)
        from deeplearning4j_tpu.telemetry import compile_ledger

        model = TransformerDecodeModel.init(
            vocab=32, hidden=64, n_layers=4, n_heads=2, max_len=4096,
            max_slots=8, page=16, max_pages_per_slot=256, n_pages=2048)
        sites_before = {
            r["site"] for r in compile_ledger.get_ledger().describe()}
        memledger.configure(budget_bytes=100_000)
        try:
            with pytest.raises(CapacityError) as ei:
                DecodeEngine(model, name="toolargekv")
        finally:
            memledger.configure(budget_bytes=None)
        assert ei.value.site == "decode:toolargekv:kv"
        assert ei.value.detail["lane"] == "target"
        sites_after = {
            r["site"] for r in compile_ledger.get_ledger().describe()}
        assert not any("toolargekv" in s
                       for s in sites_after - sites_before)
        # nothing claimed for the rejected pool
        assert memledger.get_memledger().claims("kv_cache") == []

    def test_rejected_registration_rolled_back(self, fresh_ledger):
        # a planner-rejected registration must NOT stay live in the
        # registry: the next predict would lazily compile and hit the
        # very OOM the planner refused
        from deeplearning4j_tpu.serving import ModelRegistry
        from deeplearning4j_tpu.serving.registry import ModelNotFound

        net = _tiny_net(18)
        reg = ModelRegistry()
        memledger.configure(budget_bytes=50_000)
        try:
            with pytest.raises(CapacityError):
                reg.register("ghost", net, example_shape=(4,),
                             ladder=[8192], warmup=True)
        finally:
            memledger.configure(budget_bytes=None)
        with pytest.raises(ModelNotFound):
            reg.get("ghost")
        # a same-version rolling update that gets rejected restores
        # the previous (still-warmed) entry
        reg.register("keep", net, example_shape=(4,), ladder=[1],
                     warmup=True)
        memledger.configure(budget_bytes=50_000)
        try:
            with pytest.raises(CapacityError):
                reg.register("keep", _tiny_net(19), example_shape=(4,),
                             ladder=[8192], warmup=True)
        finally:
            memledger.configure(budget_bytes=None)
        assert reg.get("keep").servable.warmed_shapes == [(1, 4)]

    def test_planner_skipped_when_capacity_unknown(self, fresh_ledger):
        # no memory_stats, no budget: the whole estimate is skipped —
        # no capacity_plan flight event, registration just proceeds
        from deeplearning4j_tpu.serving import ModelRegistry

        assert not memledger.capacity_known()
        flight.get_recorder().clear()
        ModelRegistry().register("cheap", _tiny_net(20),
                                 example_shape=(4,), ladder=[1],
                                 warmup=True)
        assert flight.get_recorder().events("capacity_plan") == []

    def test_unknown_headroom_admits(self, fresh_ledger):
        # CPU reports no memory_stats and no budget is configured:
        # the planner refuses to guess and admits
        plan = memledger.plan_capacity("probe", 1 << 40)
        assert plan["fits"] and plan["headroom_bytes"] is None

    def test_fitting_registration_admitted_with_budget(
            self, fresh_ledger):
        from deeplearning4j_tpu.serving import ModelRegistry

        net = _tiny_net(15)
        memledger.configure(budget_bytes=1 << 30)
        try:
            entry = ModelRegistry().register(
                "fits", net, example_shape=(4,), ladder=[1, 4],
                warmup=True)
        finally:
            memledger.configure(budget_bytes=None)
        assert entry.warmed


# ---------------------------------------------------------------------------
# /debug/memory + /healthz
# ---------------------------------------------------------------------------

class TestRoutesAndHealthz:
    def test_healthz_memory_section_and_degraded_floor(
            self, fresh_ledger):
        from deeplearning4j_tpu.telemetry import health

        net = _tiny_net(21)
        X, y = _tiny_data()
        net.fit([(X, y)], 1)    # first claim registers the provider
        payload, status = health.healthz()
        assert status == 200
        assert "memory" in payload
        sec = payload["memory"]
        assert sec["claimed_bytes"] > 0
        assert not sec.get("degraded")
        # drop headroom below the floor: degraded, STILL 200
        dev = memledger._device_label()
        in_use = memledger.census()["devices"][dev]["in_use"]
        memledger.configure(budget_bytes=in_use + 1000,
                            min_headroom_bytes=1 << 20)
        try:
            payload, status = health.healthz()
        finally:
            memledger.configure(budget_bytes=None,
                                min_headroom_bytes=None)
        assert status == 200
        assert payload["status"] == "degraded"
        assert payload["memory"]["degraded"]
        assert "headroom" in payload["memory"]["detail"]

    def test_debug_memory_route(self, fresh_ledger):
        from deeplearning4j_tpu.ui.server import UIServer

        net = _tiny_net(22)
        X, y = _tiny_data()
        net.fit([(X, y)], 1)
        ui = UIServer()
        ui.start(port=0)
        try:
            base = f"http://127.0.0.1:{ui.port}"
            body = json.loads(urllib.request.urlopen(
                f"{base}/debug/memory", timeout=10).read())
            assert any(c["category"] == "train" for c in body["claims"])
            dev = memledger._device_label()
            assert body["devices"][dev]["claimed"]["train"] > 0
            assert "unattributed" in body["devices"][dev]
            assert "headroom_bytes" in body and "budget_bytes" in body
            # the claimed-bytes gauges render at /metrics scrape time
            text = urllib.request.urlopen(
                f"{base}/metrics", timeout=10).read().decode()
            assert "dl4j_device_memory_claimed_bytes" in text
            assert 'category="unattributed"' in text
        finally:
            ui.stop()

    def test_decoders_healthz_reports_pool_bytes(self, fresh_ledger):
        from deeplearning4j_tpu.serving import InferenceSession
        from deeplearning4j_tpu.serving.decode import (
            TransformerDecodeModel)
        from deeplearning4j_tpu.telemetry import health

        session = InferenceSession()
        model = TransformerDecodeModel.init(
            vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=2, page=8, max_pages_per_slot=4)
        session.register_decoder("hzdec", model)
        payload, status = health.healthz(serving=session)
        assert status == 200
        kv = payload["serving"]["decoders"]["hzdec"]["kv_pages"]
        assert kv["pool_bytes"] > 0 and "used_bytes" in kv
        session.close()


# ---------------------------------------------------------------------------
# disabled contract: zero calls + bit identity
# ---------------------------------------------------------------------------

class _CountingStubLedger:
    calls = 0

    def __getattr__(self, name):
        _CountingStubLedger.calls += 1
        raise AssertionError(f"memledger.{name} touched while disabled")


class TestDisabledContract:
    def test_zero_registry_and_ledger_calls_when_disabled(self):
        class CountingStub:
            calls = 0

            def __getattr__(self, name):
                CountingStub.calls += 1
                raise AssertionError(
                    f"registry.{name} touched while disabled")

        net = _tiny_net(30)
        X, y = _tiny_data()
        prev_reg = telemetry.set_registry(CountingStub())
        _CountingStubLedger.calls = 0
        prev_led = memledger.set_ledger(_CountingStubLedger())
        telemetry.disable()
        try:
            net.fit([(X, y)], 3)
            assert CountingStub.calls == 0
            assert _CountingStubLedger.calls == 0
        finally:
            telemetry.set_registry(prev_reg)
            memledger.set_ledger(prev_led)
            telemetry.enable()

    def test_params_bit_identical_disabled_vs_enabled(
            self, fresh_ledger):
        import jax

        X, y = _tiny_data()
        net_on = _tiny_net(31)
        net_off = _tiny_net(31)
        net_on.fit([(X, y)], 3)
        telemetry.disable()
        try:
            net_off.fit([(X, y)], 3)
        finally:
            telemetry.enable()
        for a, b in zip(jax.tree_util.tree_leaves(net_on._params),
                        jax.tree_util.tree_leaves(net_off._params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
