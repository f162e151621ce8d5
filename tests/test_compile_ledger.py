"""Compile-side observability tests (ISSUE 11): the executable ledger,
recompile forensics (cause taxonomy + exact-changed-field diffs), the
serving-warmup ledger invariant, the /debug/compiles + /debug/hlo
routes, the /healthz compile section, the HLO audit parser, and the
disabled-mode zero-call contract."""

import json
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.telemetry import compile_ledger, hlo_audit
from deeplearning4j_tpu.telemetry.compile_ledger import (
    Signature, classify, signature_of)


@pytest.fixture
def ledger():
    """Fresh process ledger + enabled telemetry, restored after."""
    led = compile_ledger.CompileLedger()
    prev = compile_ledger.set_ledger(led)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    compile_ledger.configure(enabled=True)
    compile_ledger.consume_backend_compiles()   # drop earlier strays
    yield led
    compile_ledger.set_ledger(prev)
    (telemetry.enable if was_enabled else telemetry.disable)()


def _mlp(seed=1, nin=4, precision=None):
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)

    b = NeuralNetConfiguration.Builder().seed(seed)
    if precision is not None:
        b = b.precision(precision)
    conf = (b.list()
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


def _sig(args, **kw):
    return signature_of(args, **kw)


# ---------------------------------------------------------------------------
# forensic classification
# ---------------------------------------------------------------------------

class TestClassify:
    def test_first_compile(self):
        cause, changed = classify(None, _sig((np.zeros((4, 2)),)))
        assert cause == "first_compile" and changed == []

    def test_shape_change_names_dim_and_field(self):
        a = _sig((np.zeros((8, 4), np.float32),))
        b = _sig((np.zeros((16, 4), np.float32),))
        cause, changed = classify(a, b)
        assert cause == "shape_change(dim=0)"
        assert changed == ["args[0].shape: [8, 4] -> [16, 4]"]
        cause, _ = classify(a, _sig((np.zeros((8, 6), np.float32),)))
        assert cause == "shape_change(dim=1)"

    def test_dtype_change_wins_over_shape(self):
        a = _sig((np.zeros((8, 4), np.float32),))
        b = _sig((np.zeros((16, 4), np.float64),))
        cause, changed = classify(a, b)
        assert cause == "dtype_change"
        assert "args[0].dtype: float32 -> float64" in changed
        assert "args[0].shape: [8, 4] -> [16, 4]" in changed

    def test_donation_change(self):
        x = (np.zeros((4,)),)
        cause, changed = classify(_sig(x, donation=(0, 1, 2)),
                                  _sig(x, donation=(0,)))
        assert cause == "donation_change"
        assert changed == ["donation: [0, 1, 2] -> [0]"]

    def test_policy_change_wins_over_dtype(self):
        a = _sig((np.zeros((4,), np.float32),), policy="float32/h10")
        b = _sig((np.zeros((4,), np.float16),), policy="bf16_mixed/h10")
        cause, changed = classify(a, b)
        assert cause == "policy_change"
        assert any(c.startswith("policy:") for c in changed)

    def test_sharding_change(self):
        x = (np.zeros((4,)),)
        cause, changed = classify(_sig(x, sharding="cpu:0"),
                                  _sig(x, sharding="cpu:1"))
        assert cause == "sharding_change"
        assert changed == ["sharding: 'cpu:0' -> 'cpu:1'"]

    def test_new_bucket_only_when_bucketed_and_leading_dim(self):
        a = _sig((np.zeros((1, 4)),))
        b = _sig((np.zeros((8, 4)),))
        assert classify(a, b, bucketed=True)[0] == "new_bucket"
        assert classify(a, b, bucketed=False)[0] == "shape_change(dim=0)"
        c = _sig((np.zeros((8, 6)),))
        assert classify(a, c, bucketed=True)[0] == "shape_change(dim=0)"

    def test_identical_signature_is_rewarm(self):
        a = _sig((np.zeros((4,)),), policy="p")
        assert classify(a, a)[0] == "rewarm"


# ---------------------------------------------------------------------------
# note_step: the fit-loop seam, driven directly with a jitted function
# ---------------------------------------------------------------------------

class TestNoteStep:
    def test_compile_miss_records_and_steady_state_returns_none(
            self, ledger):
        @jax.jit
        def f(x):
            return jnp.dot(x, x.T)

        x = jnp.ones((4, 8))
        f(x).block_until_ready()   # backend compile -> pending event
        rec = compile_ledger.note_step("site", f, (x,), policy="p")
        assert rec is not None
        assert rec["cause"] == "first_compile"
        assert rec["compile_seconds"] > 0
        assert rec["hlo_fingerprint"]
        assert rec["flops"] > 0
        # keys ride in /debug/hlo/<key> URLs: no '#' (a client-side
        # fragment) allowed
        assert rec["key"] == "site:1"
        # steady state: no pending compile -> no ledger touch
        f(x).block_until_ready()
        assert compile_ledger.note_step("site", f, (x,),
                                        policy="p") is None

    def test_batch_and_dtype_recompiles_name_the_field(self, ledger):
        @jax.jit
        def f(x):
            return x * 2.0

        f(jnp.ones((4, 3))).block_until_ready()
        compile_ledger.note_step("s", f, (jnp.ones((4, 3)),))
        f(jnp.ones((8, 3))).block_until_ready()
        rec = compile_ledger.note_step("s", f, (jnp.ones((8, 3)),))
        assert rec["cause"] == "shape_change(dim=0)"
        assert rec["changed"] == ["args[0].shape: [4, 3] -> [8, 3]"]
        x16 = jnp.ones((8, 3), jnp.bfloat16)
        f(x16).block_until_ready()
        rec = compile_ledger.note_step("s", f, (x16,))
        assert rec["cause"] == "dtype_change"
        assert rec["changed"] == ["args[0].dtype: float32 -> bfloat16"]
        assert ledger.causes("s") == {
            "first_compile": 1, "shape_change(dim=0)": 1,
            "dtype_change": 1}

    def test_stray_compile_with_seen_signature_is_dropped(self, ledger):
        @jax.jit
        def f(x):
            return x + 1

        @jax.jit
        def other(x):
            return x - 1

        x = jnp.ones((4,))
        f(x).block_until_ready()
        compile_ledger.note_step("s", f, (x,))
        # an unrelated executable compiles mid-loop (e.g. a listener's
        # inference fn): the step signature is already ledgered, so no
        # bogus record appears at the site
        other(x).block_until_ready()
        assert compile_ledger.note_step("s", f, (x,)) is None
        assert len(ledger.describe("s")) == 1

    def test_rebuilt_fn_same_signature_is_rewarm(self, ledger):
        # two distinct step-function builds (jax.jit of the SAME
        # function object shares one cache, so the rebuilt fn must be a
        # distinct callable — exactly what _build_train_step produces)
        f1 = jax.jit(lambda x: x * 3)
        f2 = jax.jit(lambda x: x * 3)
        x = jnp.ones((4,))
        f1(x).block_until_ready()
        compile_ledger.note_step("s", f1, (x,))
        f2(x).block_until_ready()   # rebuilt step fn: fresh jit cache
        rec = compile_ledger.note_step("s", f2, (x,))
        assert rec["cause"] == "rewarm"

    def test_lazy_audit_for_step_records(self, ledger):
        @jax.jit
        def f(x):
            return jnp.dot(x, x.T) + 1.0

        x = jnp.ones((4, 8))
        f(x).block_until_ready()
        rec = compile_ledger.note_step("s", f, (x,))
        audit = ledger.audit(rec["key"])
        assert audit["ops"] > 0
        assert "fusions" in audit and "unfused_dots" in audit
        assert ledger.audit("nope#1") is None


# ---------------------------------------------------------------------------
# training-loop integration: fit/graph/sharded sites
# ---------------------------------------------------------------------------

class TestTrainSites:
    def test_fit_first_compile_then_bucket_growth(self, ledger):
        net = _mlp()
        X, y = _data(8)
        net.fit([(X, y)], 2)
        recs = ledger.describe("fit")
        assert len(recs) == 1
        assert recs[0]["cause"] == "first_compile"
        assert recs[0]["compile_seconds"] > 0
        assert recs[0]["hlo_fingerprint"]
        assert recs[0]["signature"]["donation"] == [0, 1, 2]
        # a bigger batch grows the fit bucket -> forced recompile named
        # down to the changed dim
        X2, y2 = _data(16)
        net.fit([(X2, y2)], 1)
        recs = ledger.describe("fit")
        assert len(recs) == 2
        assert recs[0]["cause"] == "shape_change(dim=0)"
        assert any("shape: [8, 4] -> [16, 4]" in c
                   for c in recs[0]["changed"])
        # steady state at the grown bucket: no new records
        net.fit([(X2, y2)], 3)
        assert len(ledger.describe("fit")) == 2

    def test_policy_change_cause_at_fit_site(self, ledger):
        X, y = _data(8)
        _mlp(precision=None).fit([(X, y)], 1)
        _mlp(precision="bf16_mixed").fit([(X, y)], 1)
        recs = ledger.describe("fit")
        assert recs[0]["cause"] == "policy_change"
        assert any(c.startswith("policy: 'float32/h10'")
                   for c in recs[0]["changed"])

    def test_graph_and_sharded_sites(self, ledger):
        from deeplearning4j_tpu.datasets import DataSet
        from deeplearning4j_tpu.nn import (
            ComputationGraph, DenseLayer, LossFunction,
            NeuralNetConfiguration, OutputLayer)
        from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

        X, y = _data(8)
        gconf = (NeuralNetConfiguration.Builder().seed(3)
                 .graphBuilder()
                 .addInputs("in")
                 .addLayer("d", DenseLayer.Builder().nIn(4).nOut(8)
                           .activation("relu").build(), "in")
                 .addLayer("out", OutputLayer.Builder().nIn(8).nOut(2)
                           .activation("softmax")
                           .lossFunction(LossFunction.MCXENT).build(),
                           "d")
                 .setOutputs("out").build())
        ComputationGraph(gconf).init().fit([(X, y)], 1)
        assert ledger.causes("graph") == {"first_compile": 1}

        ShardedTrainer(_mlp(seed=5)).fit([DataSet(X, y)], epochs=2)
        assert ledger.causes("sharded") == {"first_compile": 1}

    def test_metric_and_flight_emission(self, ledger):
        from deeplearning4j_tpu.telemetry import MetricsRegistry, flight

        reg = MetricsRegistry()
        prev = telemetry.set_registry(reg)
        try:
            net = _mlp(seed=9)
            X, y = _data(8)
            net.fit([(X, y)], 1)
        finally:
            telemetry.set_registry(prev)
        snap = reg.collect()
        fam = {f.name: f for f in snap}["dl4j_compile_cause_total"]
        children = dict(fam.children())
        assert children[(("site", "fit"),
                         ("cause", "first_compile"))].value == 1
        evts = [e for e in flight.get_recorder().events("compile_ledger")
                if e["site"] == "fit"]
        assert evts and evts[-1]["cause"] in ("first_compile",
                                              "shape_change(dim=0)")

    def test_compile_lower_span_in_trace_tree(self, ledger):
        from deeplearning4j_tpu.telemetry import tracing

        tracer = tracing.Tracer()
        prev_tr = tracing.set_tracer(tracer)
        tracing.configure(sample_rate=1.0)
        try:
            net = _mlp(seed=11)
            X, y = _data(8)
            net.fit([(X, y)], 1)
        finally:
            tracing.set_tracer(prev_tr)
            tracing.configure(sample_rate=0.01)
        spans = [s for s in tracer.spans()
                 if s["name"] == "compile.lower"]
        assert spans
        assert spans[0]["attrs"]["site"] == "fit"
        assert spans[0]["attrs"]["cause"] == "first_compile"
        roots = [s for s in tracer.spans() if s["name"] == "train.fit"]
        assert spans[0]["trace_id"] == roots[0]["trace_id"]


# ---------------------------------------------------------------------------
# serving warmup: the ledger-backed zero-steady-state-recompile claim
# ---------------------------------------------------------------------------

class TestServingWarmup:
    def test_ledger_entries_equal_ladder_size(self, ledger):
        from deeplearning4j_tpu.serving import (
            BucketLadder, InferenceSession)

        net = _mlp(seed=21)
        X, _ = _data(8)
        session = InferenceSession()
        try:
            session.register("m", net, example_shape=(4,),
                             ladder=BucketLadder((1, 8)), warmup=True)
            recs = ledger.describe("m:v1")
            assert len(recs) == 2            # == bucket-ladder size
            assert ledger.causes("m:v1") == {"first_compile": 1,
                                             "new_bucket": 1}
            assert all(r["kind"] == "aot" and
                       r["compile_seconds"] is not None and
                       r["hlo_fingerprint"] for r in recs)
            # AOT records carry the eager audit
            audit = ledger.audit(recs[0]["key"])
            assert audit["fusions"] >= 0 and "collectives" in audit
            # steady-state predicts add ZERO ledger records (PR 8's
            # claim, now ledger-backed)
            for _ in range(4):
                session.predict("m", X[0])
            assert len(ledger.describe("m:v1")) == 2
            # re-registering the SAME spec re-warms: ladder-size new
            # records, all rewarm, zero new_bucket causes
            session.register("m", net, example_shape=(4,),
                             ladder=BucketLadder((1, 8)), warmup=True)
            causes = ledger.causes("m:v1")
            assert causes == {"first_compile": 1, "new_bucket": 1,
                              "rewarm": 2}
        finally:
            session.close()


# ---------------------------------------------------------------------------
# HTTP surface: /debug/compiles, /debug/hlo/<key>, /healthz compile
# ---------------------------------------------------------------------------

class TestRoutes:
    def test_debug_compiles_and_hlo(self, ledger):
        from deeplearning4j_tpu.serving import (
            BucketLadder, InferenceSession)
        from deeplearning4j_tpu.ui.server import UIServer

        net = _mlp(seed=31)
        X, y = _data(8)
        net.fit([(X, y)], 1)
        session = InferenceSession()
        session.register("routes", net, example_shape=(4,),
                         ladder=BucketLadder((1, 4)), warmup=True)
        ui = UIServer.getInstance().start(port=0)
        base = f"http://127.0.0.1:{ui.port}"
        try:
            payload = json.loads(urllib.request.urlopen(
                base + "/debug/compiles").read())
            recs = payload["records"]
            # the ISSUE 13 executable-store section rides beside the
            # records (disabled by default in this process)
            assert "enabled" in payload["store"]
            sites = {r["site"] for r in recs}
            assert {"fit", "routes:v1"} <= sites
            for r in recs:
                assert {"key", "site", "cause", "compile_seconds",
                        "hlo_fingerprint", "signature"} <= set(r)
            # ?site= filter
            only = json.loads(urllib.request.urlopen(
                base + "/debug/compiles?site=routes:v1").read())["records"]
            assert {r["site"] for r in only} == {"routes:v1"}
            # per-executable audit, AOT (eager) and step (lazy)
            for site in ("routes:v1", "fit"):
                key = [r for r in recs if r["site"] == site][0]["key"]
                audit = json.loads(urllib.request.urlopen(
                    base + "/debug/hlo/"
                    + urllib.parse.quote(key)).read())
                assert "fusions" in audit and "remat" in audit, site
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/debug/hlo/absent%231")
            assert ei.value.code == 404
        finally:
            ui.stop()
            session.close()

    def test_healthz_compile_section(self, ledger):
        from deeplearning4j_tpu.telemetry import health

        payload, status = health.healthz()
        assert "compile" not in payload
        with compile_ledger.warmup_scope("m:v1", 4) as progress:
            progress.step()
            payload, status = health.healthz()
            assert status == 200                   # degraded, not 503
            assert payload["status"] == "degraded"
            sec = payload["compile"]
            assert sec["warmup"]["m:v1"] == {
                "done": 1, "total": 4, "fraction": 0.25}
            assert "m:v1" in sec["compiling"]
        payload, _ = health.healthz()
        assert "compile" not in payload


# ---------------------------------------------------------------------------
# disabled contract: zero ledger calls per step, bit-identical params
# ---------------------------------------------------------------------------

class _CountingStubLedger:
    calls = 0

    def __getattr__(self, name):
        _CountingStubLedger.calls += 1
        raise AssertionError(f"ledger.{name} touched while disabled")


class TestDisabledContract:
    def test_zero_ledger_calls_and_bit_identical(self):
        X, y = _data(8)
        telemetry.enable()
        n1 = _mlp(seed=41)
        n1.fit([(X, y), (X, y)], 2)
        p1 = np.asarray(n1.params())

        _CountingStubLedger.calls = 0
        prev = compile_ledger.set_ledger(_CountingStubLedger())
        telemetry.disable()
        try:
            n2 = _mlp(seed=41)
            n2.fit([(X, y), (X, y)], 2)

            from deeplearning4j_tpu.serving import (
                BucketLadder, InferenceSession)

            session = InferenceSession()
            session.register("dm", n2, example_shape=(4,),
                             ladder=BucketLadder((1, 4)), warmup=True)
            session.predict("dm", X)
            session.close()
        finally:
            compile_ledger.set_ledger(prev)
            telemetry.enable()
        assert _CountingStubLedger.calls == 0
        np.testing.assert_array_equal(p1, np.asarray(n2.params()))

    def test_ledger_flag_alone_disables(self, ledger):
        compile_ledger.configure(enabled=False)
        try:
            net = _mlp(seed=43)
            X, y = _data(8)
            net.fit([(X, y)], 1)
            assert len(ledger) == 0
        finally:
            compile_ledger.configure(enabled=True)


# ---------------------------------------------------------------------------
# the HLO audit parser
# ---------------------------------------------------------------------------

_SYNTH_HLO = """\
HloModule synth, is_scheduled=true

%fused_computation (param_0: f32[64,64]) -> f32[64,64] {
  %param_0 = f32[64,64]{1,0} parameter(0)
  %dot.1.remat = f32[64,64]{1,0} dot(%param_0, %param_0)
  ROOT %add.1 = f32[64,64]{1,0} add(%dot.1.remat, %param_0)
}

ENTRY %main (a: f32[64,64], b: bf16[32,128]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  %b = bf16[32,128]{1,0} parameter(1)
  %fusion.1 = f32[64,64]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation
  %dot.2 = f32[64,64]{1,0} dot(%a, %fusion.1)
  %conv = f32[1,8,8,4]{3,2,1,0} convolution(%a, %a), dim_labels=b01f_01io->b01f
  %ar = f32[64,64]{1,0} all-reduce(%dot.2), replica_groups={}
  %ag = bf16[64,128]{1,0} all-gather(%b), dimensions={0}
  %ob = f32[64,64]{1,0} opt-barrier(%ar)
  ROOT %out = f32[64,64]{1,0} add(%ob, %fusion.1)
}
"""


class TestHloAuditParser:
    def test_synthetic_module_counts(self):
        audit = hlo_audit.audit_text(_SYNTH_HLO)
        assert audit["fusions"] == 1
        assert audit["fused_computations"] == 1
        assert audit["unfused_dots"] == 1      # dot.2 (entry)
        assert audit["fused_dots"] == 1        # dot.1.remat (in fusion)
        assert audit["unfused_convolutions"] == 1
        assert audit["collectives"]["all-reduce"] == 1
        assert audit["collectives"]["all-gather"] == 1
        assert audit["collectives"]["total"] == 2
        assert audit["remat"]["opt_barriers"] == 1
        assert audit["remat"]["remat_ops"] == 1
        # largest buffer: bf16[64,128] = 16384 < f32[64,64] = 16384;
        # top entries are all 16 KiB here
        assert audit["largest_buffers"][0]["bytes"] == 16384
        assert audit["opcode_histogram"]["parameter"] == 3

    def test_audit_compiled_real_executable(self):
        @jax.jit
        def f(x, w):
            return jax.nn.relu(jnp.dot(x, w)).sum()

        compiled = f.lower(jnp.ones((8, 16)), jnp.ones((16, 4))).compile()
        audit = hlo_audit.audit_compiled(compiled)
        assert audit["ops"] > 0
        assert audit["hlo_fingerprint"]
        assert audit["module_bytes"] > 0
        assert audit["flops"] > 0
        assert (audit["unfused_dots"] + audit["fused_dots"]
                + audit["fusions"]) >= 1

    def test_parser_is_total_on_garbage(self):
        audit = hlo_audit.audit_text("not hlo at all\n%%% = }{")
        assert audit["ops"] == 0 and audit["fusions"] == 0

    def test_root_instructions_are_counted(self):
        """Regression: a computation's ROOT line is an instruction too
        — a small module's only dot is often the entry root, and a
        fusion's root IS the fused op."""
        audit = hlo_audit.audit_text(
            "ENTRY %m (a: f32[2,2]) -> f32[2,2] {\n"
            "  ROOT %dot.1 = f32[2,2]{1,0} dot(%a, %a)\n"
            "}\n")
        assert audit["ops"] == 1
        assert audit["unfused_dots"] == 1
        # the synthetic module's ROOT adds are in the histogram
        full = hlo_audit.audit_text(_SYNTH_HLO)
        assert full["opcode_histogram"]["add"] == 2


# ---------------------------------------------------------------------------
# route-drift rule (ISSUE 11 satellite) — fixture-level; the live-repo
# pass runs in test_dl4jlint.py's full-project gate
# ---------------------------------------------------------------------------

class TestRouteDriftRule:
    def _lint(self, tmp_path, source, **config):
        from deeplearning4j_tpu.analysis.runner import analyze

        f = tmp_path / "server.py"
        f.write_text(source)
        return analyze([str(f)], root=str(tmp_path), config=config)

    SRC = (
        "class H:\n"
        "    def do_GET(self):\n"
        "        if self.path == '/debug/widget':\n"
        "            pass\n"
        "        elif self.path.startswith('/serving/v9/'):\n"
        "            pass\n"
        "        elif self.path == '/metrics':\n"
        "            pass\n"
    )

    def test_undocumented_routes_flagged(self, tmp_path):
        report = self._lint(tmp_path, self.SRC, docs_text="",
                            serving_docs_text="")
        msgs = [f.message for f in report.new
                if f.rule == "route-drift"]
        assert len(msgs) == 2
        assert any("/debug/widget" in m for m in msgs)
        assert any("/serving/v9/" in m for m in msgs)

    def test_documented_in_either_doc_passes(self, tmp_path):
        report = self._lint(
            tmp_path, self.SRC,
            docs_text="GET /debug/widget returns widgets",
            serving_docs_text="POST /serving/v9/models ...")
        assert not [f for f in report.new if f.rule == "route-drift"]

    def test_non_path_literals_ignored(self, tmp_path):
        src = "ROUTES = ['/debug/notdispatched']\n"
        report = self._lint(tmp_path, src, docs_text="")
        assert not [f for f in report.new if f.rule == "route-drift"]
