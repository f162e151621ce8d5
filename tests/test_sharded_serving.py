"""Mesh-sharded serving tests (ISSUE 19): GSPMD servables through the
standard registry/ladder/warmup path, equal (per row, within the float32
tolerance of `conftest.assert_rows_close`) to the unsharded
single-device reference; the mesh-sharded paged KV cache with
prefix caching and speculative decoding riding unchanged on top;
capacity planning upgraded from admitting to PLACING (per-device
headroom, per-device breakdown in CapacityError.detail); compile-ledger
invariants under sharding (sharding in the abstract signature, a forced
mesh-shape change classifies as ``sharding_change``, zero steady-state
records); /healthz sharded section + /debug/memory per-device claims;
and the ``"sharded"`` fleet worker kind behind the router with a canary
rollout (slow tier, real processes under the armed lock witness).

The suite runs on the conftest-forced 8-virtual-device CPU platform."""

import gc
import json
import time

import numpy as np
import pytest
import jax

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.parallel.mesh import MeshConfig
from deeplearning4j_tpu.serving import (
    BucketLadder, FnServable, InferenceSession, ShardedServable,
    ShardedTransformerDecodeModel, TransformerDecodeModel,
    column_parallel_mlp, sharded_mlp_servable)
from deeplearning4j_tpu.serving.sharded import (
    STORE_REJECT_SHARDED, mesh_device_labels)
from deeplearning4j_tpu.telemetry import compile_ledger, flight, memledger
from deeplearning4j_tpu.telemetry.memledger import CapacityError


def _mesh(model=4, data=1):
    n = model * data
    return MeshConfig(data=data, model=model,
                      devices=jax.devices()[:n]).build()


def _counter(name):
    return telemetry.get_registry().counter(name)


@pytest.fixture
def budget():
    """Set a per-device budget for the capacity tests, restore the
    unconfigured (capacity-unknown) default after. ``relative=True``
    adds the max per-device live-array bytes at call time, so a test
    that needs ~n bytes of real HEADROOM is immune to whatever arrays
    earlier suite tests left alive on the default device (an absolute
    budget stays right for too-small-everywhere tests — pollution only
    shrinks headroom further)."""
    def set_bytes(n, relative=False):
        if relative:
            # collect first: exception tracebacks (pytest.raises) hold
            # earlier tests' device arrays in reference cycles — alive
            # at measure time, freed before the planner looks, which
            # would inflate the budget into admitting everything
            gc.collect()
            usage = memledger._device_usage()
            n += max((row["in_use"] for row in usage.values()),
                     default=0)
        memledger.configure(budget_bytes=n)
    yield set_bytes
    memledger.configure(budget_bytes=None)


# ---------------------------------------------------------------------------
# sharded serving against the unsharded single-device reference
# ---------------------------------------------------------------------------

class TestShardedPredict:
    def test_predict_matches_reference_per_row(self, assert_rows_close):
        """ISSUE 19 acceptance: :predict on the mesh equals, row for row,
        the single-device reference within the float32 tolerance (another
        placement: ROADMAP D4) — and steady state adds exactly zero
        compiles after warmup."""
        mesh = _mesh(model=4)
        fn, ref_fn, params, specs = column_parallel_mlp(
            mesh, (16, 64, 8), seed=3)
        sv = ShardedServable(fn, params, (16,), mesh, param_specs=specs)
        ref = FnServable(lambda x: ref_fn(params, x), (16,))
        sess = InferenceSession()
        try:
            sess.register("big", sv, ladder=BucketLadder([1, 4, 8]),
                          warmup=True)
            sess.register("ref", ref, ladder=BucketLadder([1, 4, 8]),
                          warmup=True)
            compiles = _counter("dl4j_compile_total")
            c0 = compiles.value
            x = np.random.RandomState(0).randn(6, 16).astype(np.float32)
            ys = sess.predict("big", x, batched=False)
            yr = sess.predict("ref", x, batched=False)
            assert_rows_close(ys, yr)
            # steady state: more traffic, zero new executables
            for _ in range(4):
                sess.predict("big", x[:3], batched=False)
                sess.predict("big", x[:1], batched=False)
            assert compiles.value == c0
        finally:
            sess.close()

    def test_batch_sharded_inputs_still_match_reference(
            self, assert_rows_close):
        """batch_axis="data" shards bucket inputs over the data axis
        when the bucket divides it; rows still match the reference
        within the float32 tolerance (ROADMAP D4)."""
        mesh = _mesh(model=2, data=2)
        fn, ref_fn, params, specs = column_parallel_mlp(
            mesh, (8, 32, 4), seed=5)
        sv = ShardedServable(fn, params, (8,), mesh, param_specs=specs,
                             batch_axis="data")
        sess = InferenceSession()
        try:
            sess.register("b", sv, ladder=BucketLadder([2, 4]),
                          warmup=True)
            x = np.random.RandomState(1).randn(4, 8).astype(np.float32)
            y_ref = np.asarray(jax.jit(ref_fn)(params, x))
            ys = sess.predict("b", x, batched=False)
            assert_rows_close(ys, y_ref)
        finally:
            sess.close()

    def test_healthz_sharded_section_and_per_device_memory(self):
        """Satellite: /healthz gains a ``sharded`` entry per sharded
        servable (mesh shape, device set, per-device bytes) and
        /debug/memory attributes the sharded-array claims per device."""
        from deeplearning4j_tpu.telemetry.health import healthz

        mesh = _mesh(model=4)
        sv = sharded_mlp_servable(mesh, (8, 32, 4), seed=2)
        sess = InferenceSession()
        try:
            sess.register("m", sv, ladder=BucketLadder([1]),
                          warmup=True)
            payload, status = healthz(serving=sess)
            assert status == 200
            row = payload["serving"]["sharded"]["m:v1"]
            assert row["mesh"] == {"model": 4}
            assert row["devices"] == mesh_device_labels(mesh)
            per_dev = row["params_per_device_bytes"]
            assert sorted(per_dev) == mesh_device_labels(mesh)
            assert all(b > 0 for b in per_dev.values())
            # /debug/memory: one replica_args claim per mesh device,
            # flagged sharded, carrying that device's label
            claims = [c for c in memledger.describe()["claims"]
                      if c["category"] == "replica_args"
                      and c["name"].startswith("m:v1@")]
            assert {c["device"] for c in claims} == set(
                mesh_device_labels(mesh))
            assert all(c["meta"]["sharded"] for c in claims)
        finally:
            sess.close()
            sv.release_memory_claims()

    def test_compile_store_scoped_out_with_reject_cause(self, tmp_path):
        """PR-13 seam: sharded executables never consult the persistent
        store — the skip is an explicit ledgered reject plus a
        ``compile_store_reject`` flight event, not a silent miss."""
        from deeplearning4j_tpu import compilestore

        mesh = _mesh(model=2)
        sv = sharded_mlp_servable(mesh, (8, 16, 4), seed=9)
        sv.cost_label = "scoped:v1"
        compilestore.configure(root=str(tmp_path))
        flight.get_recorder().clear()
        try:
            assert compilestore.enabled()
            sv.warmup(BucketLadder([2]))
            recs = compile_ledger.get_ledger().describe(site="scoped:v1")
            assert recs and all(r.get("store") == "reject" for r in recs)
            evts = flight.get_recorder().events("compile_store_reject")
            assert any(e["site"] == "scoped:v1"
                       and e["reason"] == STORE_REJECT_SHARDED
                       for e in evts)
        finally:
            compilestore.configure(enabled=False)
            sv.release_memory_claims()


# ---------------------------------------------------------------------------
# compile-ledger invariants under sharding
# ---------------------------------------------------------------------------

class TestShardedLedger:
    def test_ladder_entries_carry_mesh_sharding_signature(self):
        mesh = _mesh(model=4)
        sv = sharded_mlp_servable(mesh, (8, 16, 4), seed=1)
        sv.cost_label = "sig:v1"
        sv.warmup(BucketLadder([1, 2, 4]))
        try:
            recs = compile_ledger.get_ledger().describe(site="sig:v1")
            assert len(recs) == 3          # one per ladder bucket
            assert all(r["signature"]["sharding"]
                       .startswith("mesh(model=4)") for r in recs)
            causes = compile_ledger.get_ledger().causes(site="sig:v1")
            assert causes.get("first_compile") == 1
            assert causes.get("new_bucket") == 2
        finally:
            sv.release_memory_claims()

    def test_forced_mesh_shape_change_classifies_sharding_change(self):
        """Re-registering the same (name, version) on a different mesh
        shape recompiles with cause ``sharding_change`` — the signature
        diff names exactly the mesh string (single-bucket ladder, so no
        shape diff can shadow it)."""
        sess = InferenceSession()
        try:
            sess.register("resh", sharded_mlp_servable(
                _mesh(model=4), (8, 16, 4), seed=1),
                ladder=BucketLadder([4]), warmup=True)
            sess.register("resh", sharded_mlp_servable(
                _mesh(model=2), (8, 16, 4), seed=1),
                ladder=BucketLadder([4]), warmup=True)
            causes = compile_ledger.get_ledger().causes(site="resh:v1")
            assert causes.get("sharding_change") == 1
            recs = compile_ledger.get_ledger().describe(site="resh:v1")
            last = recs[0]   # describe() is newest first
            assert last["cause"] == "sharding_change"
            assert any("mesh(model=4)" in c and "mesh(model=2)" in c
                       for c in last["changed"])
        finally:
            sess.close()

    def test_steady_state_adds_zero_ledger_records(self):
        mesh = _mesh(model=4)
        sv = sharded_mlp_servable(mesh, (8, 16, 4), seed=4)
        sess = InferenceSession()
        try:
            sess.register("flat", sv, ladder=BucketLadder([1, 4]),
                          warmup=True)
            n0 = len(compile_ledger.get_ledger().describe(
                site="flat:v1"))
            compiles = _counter("dl4j_compile_total")
            c0 = compiles.value
            x = np.zeros((3, 8), np.float32)
            for _ in range(5):
                sess.predict("flat", x, batched=False)
                sess.predict("flat", x[:1], batched=False)
            assert len(compile_ledger.get_ledger().describe(
                site="flat:v1")) == n0
            assert compiles.value == c0
        finally:
            sess.close()


# ---------------------------------------------------------------------------
# placement: per-device capacity planning
# ---------------------------------------------------------------------------

# ~34 MB of params: over a 20 MB per-device budget in total, ~8.5 MB
# per device sharded 4 ways — the ISSUE 19 "bigger than one chip" shape.
# ~134 MB of params against a 64 MB budget: the margins dwarf both the
# live bytes earlier suite tests leave behind and their cross-device
# attribution skew (a sharded array's census lands on an arbitrary
# device of its set), so the placement verdicts stay deterministic
# under any test ordering.
_BIG_SIZES = (256, 65536, 256)
_BUDGET = 64 * 1024 * 1024


class TestShardedPlacement:
    def test_over_budget_model_rejected_unsharded_placed_sharded(
            self, budget):
        """ISSUE 19 acceptance: a model whose footprint exceeds one
        device's budget raises a typed CapacityError when forced onto
        one device, and registers + serves when sharded — the placement
        decision recorded as a ``capacity_plan`` flight event."""
        budget(_BUDGET, relative=True)   # ~64 MB of real headroom
        mesh = _mesh(model=4)
        fn, ref_fn, params, specs = column_parallel_mlp(
            mesh, _BIG_SIZES, seed=7)
        assert memledger.tree_bytes(params) > _BUDGET
        sess = InferenceSession()
        try:
            compiles = _counter("dl4j_compile_total")
            c0 = compiles.value
            # forced onto ONE device (a single-device mesh charges the
            # full footprint to that device): typed rejection
            one = MeshConfig(data=1, model=1,
                             devices=jax.devices()[:1]).build()
            with pytest.raises(CapacityError) as ei:
                sess.register(
                    "ref", ShardedServable(fn, params,
                                           (_BIG_SIZES[0],), one),
                    ladder=BucketLadder([1]), warmup=True)
            assert ei.value.site == "serving:ref:v1"
            assert ei.value.detail["per_device"]
            assert compiles.value == c0   # rejected BEFORE any compile
            flight.get_recorder().clear()
            sv = ShardedServable(fn, params, (_BIG_SIZES[0],), mesh,
                                 param_specs=specs)
            sess.register("big", sv, ladder=BucketLadder([1]),
                          warmup=True)
            plans = [e for e in
                     flight.get_recorder().events("capacity_plan")
                     if e["site"] == "serving:big:v1"]
            assert plans and plans[0]["sharded"] is True
            assert plans[0]["fits"] is True
            assert plans[0]["devices"] == 4
            x = np.random.RandomState(2).randn(
                1, _BIG_SIZES[0]).astype(np.float32)
            y = sess.predict("big", x, batched=False)
            np.testing.assert_array_equal(
                np.asarray(y),
                np.asarray(jax.jit(ref_fn)(params, x)))
        finally:
            sess.close()

    def test_sharded_too_big_rejects_with_per_device_breakdown(
            self, budget):
        """Satellite: the rejection names the tightest device and
        carries the full shard layout in ``detail["per_device"]``."""
        budget(4 * 1024 * 1024)   # < the ~33.6 MB per-device share
        mesh = _mesh(model=4)
        sv = sharded_mlp_servable(mesh, _BIG_SIZES, seed=7)
        sess = InferenceSession()
        try:
            with pytest.raises(CapacityError) as ei:
                sess.register("big", sv, ladder=BucketLadder([1]),
                              warmup=True)
            per_dev = ei.value.detail["per_device"]
            assert sorted(per_dev) == mesh_device_labels(mesh)
            assert all(not row["fits"] for row in per_dev.values())
            assert all(row["share_bytes"] > 4 * 1024 * 1024
                       for row in per_dev.values())
            assert ei.value.detail["mesh"] == {"model": 4}
            # the rejected entry never went live
            with pytest.raises(Exception):
                sess.predict("big", np.zeros((1, _BIG_SIZES[0]),
                                             np.float32))
        finally:
            sess.close()

    def test_decode_pool_placed_per_device_with_split_claims(
            self, budget):
        """The sharded KV pool is planned as a placement and its
        memledger claim is split per device; the same pool forced onto
        a single device is a typed CapacityError."""
        # pool = 2 * L2 * (n_pages+1) * page16 * H2 * D8 * 4B: 32767
        # pages (+1 scratch = 32768, divides the 4-way mesh) = 128 MB
        # total, 32 MB per device — the margins (128 vs 64 budget, 32
        # vs 64) dwarf both the live bytes earlier suite tests leave
        # behind and their cross-device attribution skew (a sharded
        # array's census lands on an arbitrary device of its set)
        head = 64 * 1024 * 1024
        budget(head, relative=True)
        mesh = _mesh(model=4)
        kw = dict(vocab=32, hidden=16, n_layers=2, n_heads=2,
                  max_len=64, seed=1)
        pool_kw = dict(max_slots=4, page=16, max_pages_per_slot=8,
                       n_pages=32767)
        ref = TransformerDecodeModel.init(**kw, **pool_kw)
        sm = ShardedTransformerDecodeModel(ref.params, 2, mesh,
                                           **pool_kw)
        total = sum(sm.pool_device_bytes().values())
        assert total > head
        sess = InferenceSession()
        try:
            with pytest.raises(CapacityError) as ei:
                sess.register_decoder("one", ref)
            assert ei.value.site == "decode:one:kv"
            flight.get_recorder().clear()
            engine = sess.register_decoder("sh", sm)
            plans = [e for e in
                     flight.get_recorder().events("capacity_plan")
                     if e["site"] == "decode:sh:kv"]
            assert plans and plans[0]["sharded"] is True
            assert plans[0]["fits"] is True
            claims = [c for c in memledger.describe()["claims"]
                      if c["category"] == "kv_cache"
                      and c["name"].startswith("sh:target@")]
            assert {c["device"] for c in claims} == set(
                mesh_device_labels(mesh))
            share = sm.pool_device_bytes()
            for c in claims:
                assert c["bytes"] == share[c["device"]]
            engine.close()
            left = [c for c in memledger.describe()["claims"]
                    if c["name"].startswith("sh:target@")]
            assert not left   # released with the engine
        finally:
            sess.close()


# ---------------------------------------------------------------------------
# the mesh-sharded paged KV cache
# ---------------------------------------------------------------------------

def _decode_models(mesh, **pool_kw):
    kw = dict(vocab=32, hidden=16, n_layers=2, n_heads=2, max_len=64,
              seed=1)
    pool = dict(max_slots=4, page=4, max_pages_per_slot=8)
    pool.update(pool_kw)
    ref = TransformerDecodeModel.init(**kw, **pool)
    sharded = ShardedTransformerDecodeModel(ref.params, 2, mesh, **pool)
    return ref, sharded


class TestShardedDecode:
    def test_decode_bit_identical_token_streams(self):
        """ISSUE 19 acceptance: :decode over the page-sharded pool
        emits the identical token stream — the online-softmax page
        accumulation order is sequential either way."""
        mesh = _mesh(model=4)
        ref, sharded = _decode_models(mesh)
        assert (sharded.n_pages + 1) % sharded.pool_shards == 0
        sess = InferenceSession()
        try:
            sess.register_decoder("dref", ref)
            sess.register_decoder("dsh", sharded)
            for prompt in ([3, 7, 1, 9], [5], [2, 4, 6, 8, 10, 12]):
                a = sess.decode("dref", prompt, 12)
                b = sess.decode("dsh", prompt, 12)
                assert list(a) == list(b)
        finally:
            sess.close()

    def test_decode_steady_state_zero_recompiles(self):
        mesh = _mesh(model=4)
        _, sharded = _decode_models(mesh)
        sess = InferenceSession()
        try:
            sess.register_decoder("d", sharded)
            sess.decode("d", [3, 7, 1], 8)      # compiles here
            compiles = _counter("dl4j_compile_total")
            c0 = compiles.value
            for prompt in ([1, 2], [9, 8, 7, 6], [5]):
                sess.decode("d", prompt, 8)
            assert compiles.value == c0
        finally:
            sess.close()

    def test_prefix_cache_and_speculative_ride_on_sharded_pool(self):
        """ISSUE 12's layers never see device layout (the host-side
        page table hands out page NUMBERS): prefix caching and
        speculative decoding work unchanged over the sharded pool, and
        the stream still matches the unsharded reference."""
        mesh = _mesh(model=4)
        ref, sharded = _decode_models(mesh)
        draft = TransformerDecodeModel.init(
            vocab=32, hidden=8, n_layers=1, n_heads=1, max_len=64,
            seed=2, max_slots=4, page=4, max_pages_per_slot=8,
            n_pages=sharded.n_pages)
        sess = InferenceSession()
        try:
            sess.register_decoder("dref", ref)
            engine = sess.register_decoder(
                "dsh", sharded, prefix_cache=True, speculative=draft)
            prompt = [3, 7, 1, 9, 11, 2]
            want = list(sess.decode("dref", prompt, 10))
            assert list(sess.decode("dsh", prompt, 10)) == want
            assert list(sess.decode("dsh", prompt, 10)) == want
            h = engine.health()
            assert h["prefix_cache"]["hits"] >= 1
            assert h["speculative"]["boundaries"] > 0
            assert h["sharded"]["pool_shards"] == 4
            assert h["kv_pages"]["per_device_bytes"] == \
                sharded.pool_device_bytes()
        finally:
            sess.close()

    def test_decoder_sharded_health_via_session(self):
        mesh = _mesh(model=2)
        _, sharded = _decode_models(mesh)
        sess = InferenceSession()
        try:
            sess.register_decoder("d", sharded)
            details = sess.health_details()
            row = details["sharded"]["decode:d"]
            assert row["mesh"] == {"model": 2}
            assert row["pool_shards"] == 2
            assert sorted(row["kv_pool_per_device_bytes"]) == \
                mesh_device_labels(mesh)
        finally:
            sess.close()


# ---------------------------------------------------------------------------
# slow tier: the "sharded" fleet worker kind behind the router
# ---------------------------------------------------------------------------

def _http(url, body=None, timeout=30.0, headers=None):
    import urllib.request

    req = urllib.request.Request(url, data=body,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except Exception as e:
        if hasattr(e, "code"):
            return e.code, dict(e.headers), e.read()
        raise


_SHARDED_SPEC = {"kind": "sharded", "model_parallel": 4,
                 "sizes": [8, 32, 4], "seed": 7, "ladder": [1, 4]}


@pytest.mark.slow
class TestShardedFleet:
    def test_sharded_worker_group_serves_and_canary_rolls_back(self):
        """ISSUE 19 acceptance: a "sharded" worker group (4-way mesh
        per worker process) serves behind the FleetRouter — predictions
        match the locally-computed column-parallel reference — and a
        deliberately-regressed sharded canary (different seed) is
        judged and rolled back fleet-wide, with v1 restored in every
        worker process."""
        from deeplearning4j_tpu.fleet.router import (
            FleetRouter, spawn_local_workers)

        spec = {"host_devices": 4,
                "models": [{"name": "m", "version": 1, **_SHARDED_SPEC}]}
        workers = spawn_local_workers(
            2, spec, extra_env={"JAX_PLATFORMS": "cpu"})
        router = FleetRouter(workers, owns_workers=True,
                             poll_interval=0.1).start(port=0)
        url = f"http://127.0.0.1:{router.port}"
        try:
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline and \
                    not all(w.models for w in router.workers):
                time.sleep(0.05)
            # the local reference: same spec -> same params (seeded
            # numpy init is process-independent)
            mesh = _mesh(model=4)
            _, ref_fn, params, _ = column_parallel_mlp(
                mesh, (8, 32, 4), seed=7)
            x = np.random.RandomState(3).randn(2, 8).astype(np.float32)
            want = np.asarray(jax.jit(ref_fn)(params, x))
            status, _, rb = _http(
                url + "/serving/v1/models/m:predict",
                body=json.dumps({"instances": x.tolist()}).encode())
            assert status == 200
            got = np.asarray(json.loads(rb)["predictions"],
                             np.float32)
            # JSON round-trips floats via shortest-repr: exact
            np.testing.assert_array_equal(got, want)
            # sharded placement is visible in every worker's /healthz
            for w in router.workers:
                _, _, hb = _http(w.url + "/healthz", timeout=10.0)
                sharded = json.loads(hb)["serving"]["sharded"]
                assert sharded["m:v1"]["mesh"] == {"model": 4}
            # regressed canary: same shape, different seed -> mirrored
            # traffic disagrees -> judged -> rolled back everywhere
            ctl = router.start_rollout(
                "m", {**_SHARDED_SPEC, "seed": 99}, version=2,
                fraction=1.0, min_samples=10)
            body = json.dumps({"instances": x.tolist()}).encode()
            deadline = time.monotonic() + 90.0
            while not ctl.terminal() and time.monotonic() < deadline:
                status, _, rb = _http(
                    url + "/serving/v1/models/m:predict", body=body)
                assert status == 200   # incumbent serves throughout
                time.sleep(0.005)
            assert ctl.state == "rolled_back", ctl.describe()
            for w in router.workers:
                _, _, mb = _http(w.url + "/serving/v1/models",
                                 timeout=10.0)
                versions = [m["version"] for m in
                            json.loads(mb)["models"]
                            if m["name"] == "m"]
                assert versions == [1], (w.name, versions)
        finally:
            router.close()
