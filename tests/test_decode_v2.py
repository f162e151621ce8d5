"""ISSUE 12 tests: decode engine v2 — chunked prefill, refcounted
prefix caching, and speculative decoding over the paged KV cache.

The acceptance bars, verbatim from the issue: chunked-prefill output
for a >=512-token prompt bit-identical to the offline single-request
decode loop with the TTFT boundary count dropping >=8x at chunk=64
and the compile ledger showing exactly the warmup executable set
across a mixed soak; a second request sharing a >=256-token prefix
prefilling only its suffix (page adoption asserted via
dl4j_serving_prefix_hits_total and the per-request boundary count)
with output bit-identical to a cold run and no page leaks; and
speculative greedy output identical to target-only decode with
accepted-tokens/boundary > 1 on the test model pair plus clean
fallback when acceptance collapses. Plus the PagedKVCache refcount
satellites: adoption/copy-on-write/release leak assertions,
exhaustion under shared prefixes, scratch-page isolation, and the
PR-8 head-of-line wedge fix (admission reclaims refcount==1 idle
cached pages).
"""

import os
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.serving import (
    DecodeEngine, InferenceSession, PagedKVCache, PrefixCache,
    RnnDecodeModel, SpeculativeConfig, TransformerDecodeModel)
from deeplearning4j_tpu.serving.decode import (
    DecodeError, _DecodeRequest, _pool_bytes_estimate)
from deeplearning4j_tpu.telemetry import compile_ledger

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib.program_spans import sample_sum  # noqa: E402


def _counter(name, **labels):
    fam = telemetry.get_registry().counter(
        name, labelnames=tuple(labels) if labels else ())
    return fam.labels(**labels) if labels else fam


def _xf(seed=5, **kw):
    d = dict(vocab=40, hidden=16, n_layers=1, n_heads=2, max_len=576,
             max_slots=2, page=32, max_pages_per_slot=18, seed=seed)
    d.update(kw)
    return TransformerDecodeModel.init(**d)


def offline_decode(model, prompt, max_new):
    """The offline single-request decode loop: one token per step
    through the model's own step executable — the bit-identity
    reference for every engine configuration."""
    state = model.init_state()
    if getattr(model, "uses_pages", False):
        kv = PagedKVCache(model.n_pages, model.page,
                          model.max_pages_per_slot, model.max_slots)
        kv.reserve(0, len(prompt) + max_new)
        table = np.ascontiguousarray(kv.table)
    else:
        table = np.zeros((model.max_slots, 1), np.int32)
    S = model.max_slots
    toks, out = list(prompt), []
    for i in range(len(prompt) + max_new):
        # FRESH arrays per step: jax may zero-copy-alias numpy inputs
        # on CPU while the dispatch is still in flight, so mutating a
        # reused buffer here races with the previous step's read
        t = np.zeros((S,), np.int32)
        p = np.zeros((S,), np.int32)
        t[0], p[0] = toks[i], i
        nxt, state = model.step(state, t, p, table)
        if i >= len(prompt) - 1:
            tok = int(np.asarray(nxt)[0])
            out.append(tok)
            toks.append(tok)
        if len(out) >= max_new:
            break
    return out


def _char_rnn(vocab=11):
    from deeplearning4j_tpu.nn import (
        InputType, LossFunction, LSTM, MultiLayerNetwork,
        NeuralNetConfiguration, RnnOutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    conf = (NeuralNetConfiguration.Builder().seed(4)
            .updater(Adam(1e-3)).list()
            .layer(LSTM.Builder().nOut(12).build())
            .layer(RnnOutputLayer.Builder().nOut(vocab)
                   .activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .setInputType(InputType.recurrent(vocab)).build())
    return MultiLayerNetwork(conf).init()


class TestPagedKVRefcount:
    def test_adopt_release_and_leak_free(self):
        kv = PagedKVCache(n_pages=6, page=4, max_pages_per_slot=6,
                          max_slots=2)
        pages = kv.reserve(0, 16)               # 4 pages, ref 1 each
        assert all(kv.refcount(p) == 1 for p in pages)
        kv.retain(pages[0])                     # the cache's reference
        kv.retain(pages[1])
        kv.release(0)
        # cache-held pages survive the slot release; the rest free
        assert kv.free_pages == 4
        assert kv.refcount(pages[0]) == 1
        assert kv.refcount(pages[2]) == 0
        # adoption: a second slot shares the cached pages (no copy)
        adopted = kv.reserve(1, 12, adopted=pages[:2])   # 3 pages
        assert adopted[:2] == pages[:2]
        assert kv.refcount(pages[0]) == 2
        assert (kv.table[1, :3] == adopted).all()
        kv.release(1)
        assert kv.refcount(pages[0]) == 1       # back to cache-only
        kv.decref(pages[0])
        kv.decref(pages[1])
        assert kv.free_pages == 6               # pool fully free again
        assert kv.refcount(pages[0]) == 0

    def test_copy_on_write_line_adoption_never_covers_last_token(self):
        """match() stops at full pages of prompt[:-1]: the adopter
        always writes on its OWN pages (the divergence/partial page is
        re-prefilled fresh, never shared)."""
        kv = PagedKVCache(n_pages=8, page=4, max_pages_per_slot=8,
                          max_slots=2)
        cache = PrefixCache(page=4)
        prompt = list(range(12))                # exactly 3 full pages
        pages = kv.reserve(0, 16)
        cache.publish(kv, prompt, pages[:3])
        # same prompt again: only 2 pages adoptable (12-1)//4 == 2
        hit, keys = cache.match(prompt)
        assert len(hit) == 2 and hit == pages[:2]
        # longer prompt sharing the prefix adopts all 3 full pages
        hit2, _ = cache.match(prompt + [99, 98])
        assert hit2 == pages[:3]
        # diverging mid-page: only the full matching pages adopt
        hit3, _ = cache.match(prompt[:6] + [77] * 6)
        assert hit3 == pages[:1]

    def test_exhaustion_and_reserve_validation(self):
        kv = PagedKVCache(n_pages=4, page=8, max_pages_per_slot=3,
                          max_slots=2)
        kv.reserve(0, 17)                       # 3 pages
        with pytest.raises(DecodeError):
            kv.reserve(1, 24)                   # needs 3, only 1 free
        # adoption shrinks the fresh need below exhaustion
        pages = kv.owned(0)
        kv.retain(pages[0])
        kv.retain(pages[1])
        kv.release(0)
        kv.reserve(1, 17, adopted=pages[:2])    # 1 fresh of 2 free
        kv.release(1)
        with pytest.raises(DecodeError):
            kv.reserve(0, 8, adopted=[pages[0], pages[1]])  # > need

    def test_scratch_page_isolation(self):
        kv = PagedKVCache(n_pages=3, page=4, max_pages_per_slot=3,
                          max_slots=1)
        assert 0 not in kv.reserve(0, 12)
        with pytest.raises(DecodeError):
            kv.retain(0)
        kv.release(0)
        with pytest.raises(DecodeError):
            kv.reserve(0, 12, adopted=[0])
        cache = PrefixCache(page=4)
        # a scratch page in a publish row is skipped, never cached
        cache.publish(kv, list(range(4)), [0])
        assert len(cache) == 0


class TestChunkedPrefill:
    def test_512_prompt_bit_identity_and_boundary_drop(self):
        """The acceptance bar: a 512-token prompt through chunk=64
        prefill emits exactly the offline decode loop's tokens, and
        the TTFT boundary count drops >=8x (here 64x: 512 -> 8)."""
        model = _xf(seed=7)
        prompt = list(np.random.default_rng(3).integers(
            0, 40, size=512))
        ref = offline_decode(model, prompt, 8)
        eng = DecodeEngine(_xf(seed=7), name="c512", chunk=64).warmup()
        req = eng.submit(prompt, 8)
        assert req.result(timeout=300.0) == ref
        # 8 boundaries: 7 full chunks + the tail, each retiring
        # chunk + 1 tokens (the token step rides every boundary)
        assert req.ttft_boundaries <= 8
        assert 512 / req.ttft_boundaries >= 8
        eng.close()

    def test_plain_engine_boundary_count_is_prompt_length(self):
        """The baseline the >=8x is measured against: one boundary
        per prompt token on the per-token path."""
        eng = DecodeEngine(_xf(seed=2), name="c-base").warmup()
        prompt = [5, 9, 2, 11, 3, 1, 4, 8]
        req = eng.submit(prompt, 4)
        req.result(timeout=60.0)
        assert req.ttft_boundaries == len(prompt)
        eng.close()

    def test_chunked_interleaves_with_inflight_decode(self):
        """A long prompt joining mid-stream neither stalls nor
        perturbs an in-flight decode: the short request's tokens are
        bit-identical to its solo run (per-slot determinism across
        the prefill dispatch)."""
        eng = DecodeEngine(_xf(seed=11, max_slots=3), name="c-mix",
                           chunk=32).warmup()
        solo = eng.decode([5, 9, 2], 10, timeout=60.0)
        long_prompt = list(np.random.default_rng(8).integers(
            0, 40, size=200))
        r_long = eng.submit(long_prompt, 6)
        r_short = eng.submit([5, 9, 2], 10)
        assert r_short.result(timeout=120.0) == solo
        assert len(r_long.result(timeout=120.0)) == 6
        eng.close()

    def test_rnn_chunked_prefill_bit_identity(self):
        net = _char_rnn()
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]
        ref = offline_decode(RnnDecodeModel(net, max_slots=3),
                             prompt, 7)
        eng = DecodeEngine(RnnDecodeModel(net, max_slots=3),
                           name="rnn-c", chunk=8).warmup()
        req = eng.submit(prompt, 7)
        assert req.result(timeout=60.0) == ref
        assert req.ttft_boundaries <= 3
        eng.close()

    def test_ledger_executable_set_and_mixed_soak_zero_recompiles(self):
        """The ledger bar: warmup registers exactly the decode
        executable set (step + prefill + verify + draft step + draft
        prefill) as first compiles, and a mixed prefill+decode soak
        adds NO record and NO backend compile."""
        led = compile_ledger.get_ledger()
        draft = TransformerDecodeModel(
            _xf(seed=7).params, n_heads=2, max_slots=2, page=32,
            max_pages_per_slot=18)
        eng = DecodeEngine(
            _xf(seed=7), name="ledset", chunk=16, prefix_cache=True,
            speculative=SpeculativeConfig(draft=draft, k=3)).warmup()
        recs = [r for r in led.describe()
                if r["site"].startswith("decode:ledset:")]
        assert {r["site"] for r in recs} == {
            "decode:ledset:step", "decode:ledset:prefill",
            "decode:ledset:verify", "decode:ledset:draft_step",
            "decode:ledset:draft_prefill"}
        assert all(r["cause"] == "first_compile" for r in recs)
        compiles = _counter("dl4j_compile_total")
        c0 = compiles.value
        rng = np.random.default_rng(0)
        reqs = [eng.submit(list(rng.integers(0, 40, size=n)), 6)
                for n in (40, 3, 75, 18, 51)]
        for r in reqs:
            assert len(r.result(timeout=180.0)) == 6
        assert compiles.value == c0
        assert len([r for r in led.describe()
                    if r["site"].startswith("decode:ledset:")]) == \
            len(recs)
        eng.close()


class TestPrefixCache:
    def test_shared_256_prefix_prefills_only_suffix(self):
        """The acceptance bar: a second request sharing a >=256-token
        prefix adopts the cached pages (dl4j_serving_prefix_hits_total
        moves, per-request boundary count collapses) and a full rerun
        of the first prompt is bit-identical to its cold run."""
        inst = telemetry.serving_instruments("pfx")
        eng = DecodeEngine(_xf(seed=5), name="pfx", chunk=32,
                           prefix_cache=True,
                           instruments=inst).warmup()
        rng = np.random.default_rng(4)
        shared = list(rng.integers(0, 40, size=256))
        p1 = shared + list(rng.integers(0, 40, size=9))
        p2 = shared + list(rng.integers(0, 40, size=14))
        hits0 = _counter("dl4j_serving_prefix_hits_total",
                         model="pfx").value
        r1 = eng.submit(p1, 6)
        cold = r1.result(timeout=180.0)
        cold_boundaries = r1.ttft_boundaries
        r2 = eng.submit(p2, 6)
        r2.result(timeout=180.0)
        assert _counter("dl4j_serving_prefix_hits_total",
                        model="pfx").value == hits0 + 1
        # 256 tokens (8 pages) adopted: only the suffix prefills
        assert r2.ttft_boundaries <= 2
        assert cold_boundaries >= 8
        # rerun of the FIRST prompt: full-prefix adoption, output
        # bit-identical to the cold run
        r3 = eng.submit(p1, 6)
        assert r3.result(timeout=180.0) == cold
        assert r3.ttft_boundaries <= 2
        eng.close()

    def test_no_page_leaks_after_mixed_shared_prefix_soak(self):
        eng = DecodeEngine(_xf(seed=6, max_slots=3, max_len=192,
                               max_pages_per_slot=6, page=32),
                           name="leak", chunk=32,
                           prefix_cache=True).warmup()
        rng = np.random.default_rng(2)
        shared = list(rng.integers(0, 40, size=64))
        reqs = []
        for i in range(8):
            tail = list(rng.integers(0, 40, size=3 + i))
            reqs.append(eng.submit(shared + tail, 5))
        for i in range(4):      # plus unrelated traffic
            reqs.append(eng.submit(
                list(rng.integers(0, 40, size=20 + i)), 5))
        for r in reqs:
            assert len(r.result(timeout=180.0)) == 5
        assert eng._kv.used_pages > 0          # cache holds pages
        eng.clear_prefix_cache()
        assert eng._kv.free_pages == eng._kv.n_pages
        eng.close()

    def test_head_of_line_reclaims_idle_cached_pages(self):
        """The PR-8 wedge fix: a request whose need exceeds the free
        pool but not the pool size must evict refcount==1 idle cached
        pages instead of blocking the FIFO forever."""
        m = TransformerDecodeModel.init(
            vocab=40, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=1, page=8, max_pages_per_slot=8, n_pages=8,
            seed=3)
        eng = DecodeEngine(m, name="hol", chunk=8,
                           prefix_cache=True).warmup()
        pa = list(np.random.default_rng(0).integers(0, 40, size=40))
        pb = list(np.random.default_rng(9).integers(0, 40, size=40))
        eng.decode(pa, 8, timeout=120.0)
        # A's 5 full prompt pages stay cached; B (disjoint prompt)
        # needs 6 pages with only 3 free — without reclaim this
        # head-blocks forever and the decode below times out
        assert eng._kv.free_pages < eng._kv.pages_for(48)
        assert len(eng.decode(pb, 8, timeout=60.0)) == 8
        eng.close()

    def test_exhaustion_under_shared_prefixes_resolves_by_adoption(self):
        """Two same-prefix requests that cannot BOTH hold private
        pages: the second admits anyway by adopting the published
        prefix (needing only its suffix pages)."""
        m = TransformerDecodeModel.init(
            vocab=40, hidden=16, n_layers=1, n_heads=2, max_len=64,
            max_slots=2, page=8, max_pages_per_slot=8, n_pages=8,
            seed=3)
        eng = DecodeEngine(m, name="shx", chunk=8,
                           prefix_cache=True).warmup()
        prompt = list(np.random.default_rng(5).integers(0, 40, size=40))
        r1 = eng.submit(prompt, 8)               # 6 of 8 pages
        r2 = eng.submit(prompt + [7], 8)         # waits, then adopts
        out1 = r1.result(timeout=120.0)
        out2 = r2.result(timeout=120.0)
        assert len(out1) == 8 and len(out2) == 8
        assert eng._pcache.hits >= 1
        eng.close()


class TestSpeculative:
    def test_perfect_draft_greedy_identity_and_acceptance(self):
        """Draft == target params: the verify call accepts every
        proposal, output is exactly the target-only stream, and
        accepted tokens per verify boundary exceed 1 (the acceptance
        bar's 'test model pair')."""
        target = _xf(seed=5, max_len=256, max_pages_per_slot=8)
        draft = TransformerDecodeModel(
            target.params, n_heads=2, max_slots=2, page=32,
            max_pages_per_slot=8)
        prompt = list(np.random.default_rng(1).integers(0, 40, size=20))
        ref = offline_decode(target, prompt, 24)
        inst = telemetry.serving_instruments("specm")
        a0 = _counter("dl4j_decode_accepted_tokens_total",
                      model="specm", outcome="accepted").value
        eng = DecodeEngine(
            _xf(seed=5, max_len=256, max_pages_per_slot=8),
            name="specm", instruments=inst,
            speculative=SpeculativeConfig(draft=draft, k=4)).warmup()
        req = eng.submit(prompt, 24)
        assert req.result(timeout=180.0) == ref
        boundaries = eng._spec._boundaries
        accepted = _counter("dl4j_decode_accepted_tokens_total",
                            model="specm", outcome="accepted").value - a0
        assert boundaries > 0
        assert accepted / boundaries > 1.0
        assert eng._spec._fallback is False
        eng.close()

    def test_weak_draft_identity_and_clean_fallback(self):
        """Acceptance collapse: a draft that NEVER agrees (argmax
        shifted by one — untrained random models can coincidentally
        agree, so the refutation must be constructed) trips the EWMA
        floor, the engine falls back to plain decode, and the output
        is STILL identical to target-only greedy decode."""
        target = _xf(seed=5, max_len=256, max_pages_per_slot=8)

        class _ShiftedDraft(TransformerDecodeModel):
            def _apply(self, params, state, tokens, pos, table, pidx):
                nxt, st = super()._apply(params, state, tokens, pos,
                                         table, pidx)
                return (nxt + 1) % self.vocab, st

        weak = _ShiftedDraft(target.params, n_heads=2, max_slots=2,
                             page=32, max_pages_per_slot=8)
        prompt = list(np.random.default_rng(6).integers(0, 40, size=16))
        ref = offline_decode(target, prompt, 32)
        eng = DecodeEngine(
            _xf(seed=5, max_len=256, max_pages_per_slot=8),
            name="specw",
            speculative=SpeculativeConfig(
                draft=weak, k=4, min_acceptance=0.95,
                warmup_boundaries=2, probe_every=8)).warmup()
        req = eng.submit(prompt, 32)
        assert req.result(timeout=180.0) == ref
        assert eng._spec._fallback is True
        h = eng.health()["speculative"]
        assert h["fallback"] is True and h["acceptance_ewma"] < 0.95
        eng.close()

    def test_speculative_composes_with_prefix_cache(self):
        target = _xf(seed=5, max_len=256, max_pages_per_slot=8)
        draft = TransformerDecodeModel(
            target.params, n_heads=2, max_slots=2, page=32,
            max_pages_per_slot=8)
        eng = DecodeEngine(
            _xf(seed=5, max_len=256, max_pages_per_slot=8),
            name="specpfx", chunk=32, prefix_cache=True,
            speculative=SpeculativeConfig(draft=draft, k=3)).warmup()
        prompt = list(np.random.default_rng(3).integers(0, 40, size=70))
        cold = eng.decode(prompt, 10, timeout=180.0)
        warm = eng.submit(prompt, 10)
        assert warm.result(timeout=180.0) == cold
        assert warm.ttft_boundaries <= 2       # 2 pages adopted
        eng.clear_prefix_cache()
        assert eng._kv.free_pages == eng._kv.n_pages
        eng.close()

    def test_config_validation(self):
        target = _xf(seed=5)
        with pytest.raises(DecodeError):
            DecodeEngine(target, speculative=SpeculativeConfig(
                draft=_xf(seed=5, vocab=24), k=2))
        with pytest.raises(DecodeError):
            DecodeEngine(target, speculative=SpeculativeConfig(
                draft=_xf(seed=5, max_slots=4), k=2))
        # draft page geometry must mirror the target's: a different
        # page size breaks adoption-depth units, and a smaller pool
        # would re-introduce the head-of-line wedge on the mirror lane
        with pytest.raises(DecodeError):
            DecodeEngine(target, speculative=SpeculativeConfig(
                draft=_xf(seed=5, page=16, max_pages_per_slot=36),
                k=2))
        with pytest.raises(DecodeError):
            DecodeEngine(target, speculative=SpeculativeConfig(
                draft=_xf(seed=5, max_pages_per_slot=4), k=2))
        from deeplearning4j_tpu.nn import (
            InputType, LossFunction, LSTM, MultiLayerNetwork,
            NeuralNetConfiguration, RnnOutputLayer)

        conf = (NeuralNetConfiguration.Builder().seed(1).list()
                .layer(LSTM.Builder().nOut(8).build())
                .layer(RnnOutputLayer.Builder().nOut(40)
                       .activation("softmax")
                       .lossFunction(LossFunction.MCXENT).build())
                .setInputType(InputType.recurrent(40)).build())
        rnn = RnnDecodeModel(MultiLayerNetwork(conf).init(),
                             max_slots=2)
        with pytest.raises(DecodeError):
            DecodeEngine(rnn, speculative=SpeculativeConfig(
                draft=_xf(seed=5), k=2))


class TestDecodeV2Health:
    def test_health_sections_and_backlog_degradation(self):
        eng = DecodeEngine(_xf(seed=5), name="hlth", chunk=16,
                           prefix_cache=True,
                           backlog_timeout=0.05).warmup()
        h = eng.health()
        assert h["prefill"]["chunk"] == 16
        assert h["kv_pages"]["total"] == eng._kv.n_pages
        assert h["prefix_cache"]["pages"] == 0
        assert not h["degraded"]
        # an aged first-token backlog degrades (not 503): fake a
        # starved head-of-line request
        stale = _DecodeRequest([1, 2, 3], 4, None, 999)
        stale.t_submit -= 100.0
        eng._waiting.append(stale)
        try:
            h2 = eng.health()
            assert h2["prefill"]["starved"] is True
            assert h2["degraded"] is True
        finally:
            eng._waiting.remove(stale)
        assert eng.health()["degraded"] is False
        eng.close()

    def test_session_health_details_and_kwargs_passthrough(self):
        sess = InferenceSession()
        m = _xf(seed=5, max_len=128, max_pages_per_slot=4)
        engine = sess.register_decoder("dv2", m, chunk=16,
                                       prefix_cache=True)
        assert engine._block is not None and engine._pcache is not None
        toks = sess.decode("dv2", [1, 2, 3, 4, 5], 4, timeout=120.0)
        assert len(toks) == 4
        details = sess.health_details()
        assert "prefix_cache" in details["decoders"]["dv2"]
        assert "prefill" in details["decoders"]["dv2"]
        sess.close()

    def test_ttft_histogram_records(self):
        inst = telemetry.serving_instruments("ttftm")
        fam = telemetry.get_registry().histogram(
            "dl4j_decode_ttft_seconds", labelnames=("model",))
        child = fam.labels(model="ttftm")
        c0 = child.count
        eng = DecodeEngine(_xf(seed=5, max_len=128,
                               max_pages_per_slot=4),
                           name="ttftm", instruments=inst).warmup()
        eng.decode([1, 2, 3], 4, timeout=60.0)
        assert child.count == c0 + 1
        eng.close()


@pytest.mark.slow
class TestDecodeV2Soak:
    def test_mixed_arm_soak_under_witness(self):
        """Chunked + prefix + speculative engines under concurrent
        clients with the lock witness armed (slow-marked, ISSUE 7
        contract) — and the pool leak-free afterwards."""
        import threading

        target = _xf(seed=5, max_slots=3, max_len=256,
                     max_pages_per_slot=8)
        draft = TransformerDecodeModel(
            target.params, n_heads=2, max_slots=3, page=32,
            max_pages_per_slot=8)
        eng = DecodeEngine(
            target, name="soak12", chunk=32, prefix_cache=True,
            speculative=SpeculativeConfig(draft=draft, k=3)).warmup()
        shared = list(np.random.default_rng(7).integers(
            0, 40, size=64))
        errors = []

        def client(i):
            try:
                rng = np.random.default_rng(100 + i)
                for k in range(4):
                    prompt = (shared + list(rng.integers(
                        0, 40, size=2 + i + k)) if k % 2 == 0
                        else list(rng.integers(0, 40, size=10 + i)))
                    toks = eng.decode(prompt, 6, timeout=180.0)
                    assert len(toks) == 6
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        eng.clear_prefix_cache()
        assert eng._kv.free_pages == eng._kv.n_pages
        eng.close()


# ---------------------------------------------------------------------------
# ISSUE 27: the KV pool is donated to every executable over it and
# written in place
# ---------------------------------------------------------------------------

# the executables over a TransformerDecodeModel's pool, and the widest
# run of one slot's tokens a launch of each consumes
POOL_EXECUTABLES = {"step": 1, "step_masked": 1, "block1": 1,
                    "block_chunk": 4}


def _exe(model, which):
    """The jitted callable ``which`` names, as the model or its
    ChunkedPrefill holds it."""
    from deeplearning4j_tpu.serving.prefill import ChunkedPrefill

    if which == "step":
        return model._jit_step
    if which == "step_masked":
        return model._jit_masked
    return ChunkedPrefill(model, POOL_EXECUTABLES[which])._jit


def _draft_of(model):
    """A draft lane over the target's own weights and geometry."""
    return TransformerDecodeModel(
        model.params, n_heads=model.n_heads, max_slots=model.max_slots,
        page=model.page, max_pages_per_slot=model.max_pages_per_slot)


def _launch_args(model, which, state, toks, pos0, table):
    """The argument tuple the model's own ``step`` / ``step_masked`` /
    ``ChunkedPrefill.launch`` hands its executable to consume ``toks``
    (one slot's next tokens) in slot 0 from position ``pos0``."""
    S, w = model.max_slots, POOL_EXECUTABLES[which]
    n = len(toks)
    if which in ("step", "step_masked"):
        t = np.zeros((S,), np.int32)
        p = np.zeros((S,), np.int32)
        t[0], p[0] = toks[0], pos0
        args = (model.params, state, t, p, table)
        if which == "step_masked":
            active = np.zeros((S,), bool)
            active[0] = True
            args += (active,)
        return args
    blocks = np.zeros((S, w), np.int32)
    p0 = np.zeros((S,), np.int32)
    counts = np.zeros((S,), np.int32)
    blocks[0, :n], p0[0], counts[0] = toks, pos0, n
    return (model.params, state, blocks, p0, counts, table)


def _greedy(fn, model, which, prompt, max_new):
    """One request in slot 0 through ``fn`` (an executable of kind
    ``which``), the prompt in runs of the executable's width, then one
    token at a time: the served tokens."""
    kv = PagedKVCache(model.n_pages, model.page,
                      model.max_pages_per_slot, model.max_slots)
    kv.reserve(0, len(prompt) + max_new)
    table = np.ascontiguousarray(kv.table)
    state = model.init_state()
    w = POOL_EXECUTABLES[which]
    seq, out, ptr = list(prompt), [], 0
    while len(out) < max_new:
        n = min(w, len(seq) - ptr)
        outs, state = fn(*_launch_args(model, which, state,
                                       seq[ptr:ptr + n], ptr, table))
        outs = np.asarray(outs)
        ptr += n
        if ptr == len(seq):
            tok = int(outs[0] if outs.ndim == 1 else outs[0, n - 1])
            out.append(tok)
            seq.append(tok)
    return out


class _LostTokens:
    """The tokens of a launch that died on the device: reading them
    raises, dispatching the next launch does not."""

    def __init__(self, on_read):
        self._on_read = on_read

    def __array__(self, *a, **kw):
        self._on_read()
        raise RuntimeError("injected launch failure")


def _lose_tokens_once(real, seen):
    """``real`` (a model's ``step``) whose first launch comes back with
    tokens that cannot be read; ``seen`` gets the number of launches
    that had gone out when the engine tried."""
    calls = []

    def wrapper(state, *a, **kw):
        nxt, new_state = real(state, *a, **kw)
        calls.append(True)
        if len(calls) > 1:
            return nxt, new_state
        return _LostTokens(lambda: seen.append(len(calls))), new_state
    return wrapper


def _raise_after(real, site_suffix=""):
    """``real`` made to raise once AFTER its launch went out (the
    first whose ``site`` ends with ``site_suffix``): the state it was
    given is consumed, as after a launch that died."""
    fired = []

    def wrapper(state, *a, site=None, **kw):
        out = real(state, *a, site=site, **kw)
        if fired or not (site or "").endswith(site_suffix):
            return out
        fired.append(True)
        raise RuntimeError("injected launch failure")
    return wrapper


class TestDonatedPool:
    @pytest.mark.parametrize("which", sorted(POOL_EXECUTABLES))
    def test_executable_aliases_the_pool_and_consumes_it(self, which):
        """(a) The executable the model really calls aliases the whole
        pool to its output, and the arrays passed in are gone after the
        call."""
        model = _xf(seed=3, max_len=96, max_pages_per_slot=3)
        fn = _exe(model, which)
        table = np.zeros((model.max_slots, 3), np.int32)
        state = model.init_state()
        args = _launch_args(model, which, state, [5], 0, table)
        mem = fn.lower(*args).compile().memory_analysis()
        assert mem.alias_size_in_bytes >= _pool_bytes_estimate(model)
        _, new_state = fn(*args)
        assert state["k"].is_deleted() and state["v"].is_deleted()
        assert not new_state["k"].is_deleted()
        assert new_state["k"].shape == model._pool_shape()

    @pytest.mark.parametrize("which", sorted(POOL_EXECUTABLES))
    def test_tokens_bit_identical_to_undonated(self, which):
        """(b) Tokens through the donated executable equal, bit for
        bit, those of an undonated ``jax.jit(model._fn)`` driven by
        hand over the same prompt."""
        import jax

        model = _xf(seed=9, max_len=96, max_pages_per_slot=3)
        prompt = list(np.random.default_rng(4).integers(0, 40, size=37))
        undonated = jax.jit(model._fn)
        ref = _greedy(undonated, model, "step", prompt, 9)
        state = model.init_state()
        undonated(*_launch_args(model, "step", state, [1], 0,
                                np.zeros((model.max_slots, 3),
                                         np.int32)))
        assert not state["k"].is_deleted()      # the control donates nothing
        assert _greedy(_exe(model, which), model, which, prompt,
                       9) == ref

    @pytest.mark.parametrize("arm", ["plain", "chunk", "speculative",
                                     "mesh"])
    def test_engine_survives_warmup_then_serves(self, arm):
        """(c) ``warmup()`` carries each throwaway launch's state on
        (the pool it passed is consumed), so the engine serves after
        it: with the block executable, with a draft lane, on a mesh."""
        kw = dict(seed=6, max_len=96, max_pages_per_slot=3)
        model, opts = _xf(**kw), {}
        ref = offline_decode(_xf(**kw), [7, 3, 9, 1, 4, 4, 2], 8)
        if arm == "chunk":
            opts = dict(chunk=4, prefix_cache=True)
        elif arm == "speculative":
            opts = dict(chunk=4, speculative=SpeculativeConfig(
                draft=_draft_of(model), k=2))
        elif arm == "mesh":
            import jax

            from deeplearning4j_tpu.parallel.mesh import MeshConfig
            from deeplearning4j_tpu.serving import (
                ShardedTransformerDecodeModel)

            if len(jax.devices()) < 2:
                pytest.skip("no second device for a mesh")
            mesh = MeshConfig(data=1, model=2,
                              devices=jax.devices()[:2]).build()
            model = ShardedTransformerDecodeModel(
                model.params, 2, mesh, max_slots=2, page=32,
                max_pages_per_slot=3)
        eng = DecodeEngine(model, name=f"don-{arm}", **opts).warmup()
        try:
            for leaf in (eng._state["k"], eng._state["v"]):
                assert not leaf.is_deleted()
            assert eng.decode([7, 3, 9, 1, 4, 4, 2], 8,
                              timeout=120.0) == ref
            assert eng.decode([7, 3, 9, 1, 4, 4, 2], 8,
                              timeout=120.0) == ref
        finally:
            eng.close()

    @pytest.mark.parametrize("arm", ["step", "prefill", "verify",
                                     "step_in_flight"])
    def test_failed_launch_fails_requests_then_serves_from_fresh_pool(
            self, arm, monkeypatch):
        """(d) After a launch that raised (its pool consumed), the
        active requests end with the error, and the next request is
        served correctly from a fresh pool with an empty prefix
        cache. ``step_in_flight``: the error surfaces at the launch's
        read-back, with a second launch already dispatched on the
        consumed pool."""
        kw = dict(seed=8, max_len=96, max_pages_per_slot=3)
        model = _xf(**kw)
        prompt = list(np.random.default_rng(2).integers(0, 40, size=40))
        ref = offline_decode(_xf(**kw), prompt, 6)
        opts, plain, in_flight = {}, arm.startswith("step"), []
        if not plain:
            opts = dict(chunk=4, prefix_cache=True)
        if arm == "verify":
            opts["speculative"] = SpeculativeConfig(
                draft=_draft_of(model), k=3)
        eng = DecodeEngine(model, name=f"fail-{arm}", **opts).warmup()
        try:
            # a served request first: its prompt page is published
            assert eng.decode(prompt, 6, timeout=120.0) == ref
            if not plain:
                assert eng._pcache.stats()["pages"] > 0
            if arm == "step":
                monkeypatch.setattr(model, "step",
                                    _raise_after(model.step))
            elif arm == "step_in_flight":
                monkeypatch.setattr(
                    model, "step", _lose_tokens_once(model.step,
                                                     in_flight))
            else:
                monkeypatch.setattr(
                    eng._block, "launch",
                    _raise_after(eng._block.launch, f":{arm}"))
            old = eng._state
            with pytest.raises(Exception, match="injected launch"):
                eng.decode(prompt[:-1] + [0], 6, timeout=120.0)
            assert old["k"].is_deleted()
            assert eng._state is not old
            assert not eng._state["k"].is_deleted()
            if arm == "step_in_flight":
                assert in_flight == [2] and eng._flight is None
            if not plain:
                assert eng._pcache.stats()["pages"] == 0
            assert eng._kv.free_pages == eng._kv.n_pages
            assert eng.decode(prompt, 6, timeout=120.0) == ref
        finally:
            eng.close()

    @pytest.mark.parametrize("lane", ["step", "prefill", "verify",
                                      "draft_step"])
    def test_compile_ledger_records_the_donation(self, lane):
        """(e) ``GET /debug/compiles`` says what the executable does:
        the state's donation, at each of the four sites."""
        model = _xf(seed=7, max_len=96, max_pages_per_slot=3)
        name = f"ledon-{lane}"
        eng = DecodeEngine(
            model, name=name, chunk=4, speculative=SpeculativeConfig(
                draft=_draft_of(model), k=2)).warmup()
        try:
            recs = [r for r in compile_ledger.get_ledger().describe()
                    if r["site"] == f"decode:{name}:{lane}"]
            assert recs
            assert all(r["signature"]["donation"] == [1] for r in recs)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# ISSUE 31: one token step in flight
# ---------------------------------------------------------------------------

def _overlap_models(kind):
    """(the engine's model, the serial loop's, the vocabulary): three
    slots; the transformer's pool holds 12 pages of 8 rows where its
    slots could ask for 21, so requests wait for pages as for slots."""
    if kind == "rnn":
        net = _char_rnn()
        return (RnnDecodeModel(net, max_slots=3),
                RnnDecodeModel(net, max_slots=3), 11)
    kw = dict(seed=12, max_len=64, max_slots=3, page=8,
              max_pages_per_slot=7, n_pages=12)
    return _xf(**kw), _xf(**kw), 40


def _serial_answer(model, prompt, max_new, eos_id):
    """What a plain serial loop over ``model.step`` gives the request
    alone: the greedy tokens up to ``max_new`` or the first ``eos``."""
    out = offline_decode(model, prompt, max_new)
    if eos_id in out:
        out = out[:out.index(eos_id) + 1]
    return out


@pytest.fixture(scope="module", params=["transformer", "rnn"])
def overlapped_run(request):
    """Eleven requests on three slots through a plain engine (prompts
    1-40, answers 1-12, every second with an ``eos_id`` taken from its
    own greedy stream): what each was given, what the serial loop
    gives it, and what the engine left behind."""
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    model, alone, vocab = _overlap_models(request.param)
    rng = np.random.default_rng(31)
    given = []
    for i in range(11):
        prompt = [int(t) for t in rng.integers(
            1, vocab, size=int(rng.integers(1, 41)))]
        max_new = int(rng.integers(1, 13))
        eos_id = None
        if i % 2 and max_new > 1:
            # a token the stream reaches before its last
            stream = offline_decode(alone, prompt, max_new)
            eos_id = stream[(max_new - 1) // 2]
        given.append((prompt, max_new, eos_id))
    want = [_serial_answer(alone, *g) for g in given]
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    telemetry.enable()
    name = f"overlap-{request.param}"
    eng = DecodeEngine(
        model, name=name,
        instruments=telemetry.serving_instruments(name)).warmup()
    try:
        got = [r.result(timeout=180.0)
               for r in _submit_at_once(eng, given)]
        left = {"free_slots": sorted(eng._free_slots),
                "active": dict(eng._active), "flight": eng._flight,
                "free_pages": (None if eng._kv is None
                               else (eng._kv.free_pages,
                                     eng._kv.n_pages))}
    finally:
        eng.close()
        telemetry.set_registry(prev)
    return {"given": given, "want": want, "got": got, "left": left,
            "snap": reg.snapshot(), "name": name}


def _submit_at_once(eng, given):
    """Every request of ``given`` pending before the engine admits the
    first, so that what overlaps does not depend on how fast this
    thread submits."""
    import threading

    gate, admit = threading.Event(), eng._admit

    def gated(inst):
        gate.wait(30.0)
        return admit(inst)
    eng._admit = gated
    try:
        return [eng.submit(p, m, eos_id=e) for p, m, e in given]
    finally:
        gate.set()


def _sum(snap, family, **labels):
    return sample_sum(snap, family, **labels) or 0.0


def _row_of_position_0(model, token):
    """The K rows, one a layer, that a step of ``token`` at position 0
    writes into a fresh pool."""
    kv = PagedKVCache(model.n_pages, model.page,
                      model.max_pages_per_slot, model.max_slots)
    kv.reserve(0, 1)
    table = np.ascontiguousarray(kv.table)
    toks = np.zeros((model.max_slots,), np.int32)
    toks[0] = token
    _, state = model.step(model.init_state(), toks,
                          np.zeros((model.max_slots,), np.int32), table)
    return np.asarray(state["k"])[:, table[0, 0], 0]


_SCRIPT = [([5, 9, 2, 11, 3, 1, 4, 8, 6, 6, 2, 7, 1, 9, 3, 5, 8, 2, 4,
             7, 3], 9), ([4, 4, 1], 5), ([7], 12)]


class TestOneStepInFlight:
    def test_tokens_are_the_serial_loops(self, overlapped_run):
        """(a) Token for token what a plain serial loop over
        ``model.step`` computes for each request alone, with slots and
        pages reused while a launch is in flight."""
        run = overlapped_run
        cut_short = [len(w) < m for w, (_, m, e) in
                     zip(run["want"], run["given"]) if e is not None]
        assert cut_short and all(cut_short)     # ``eos`` really came
        assert run["got"] == run["want"]

    def test_nothing_leaks_and_a_discarded_row_counts_nowhere(
            self, overlapped_run):
        """(b) Every page and slot is free afterwards, nothing is in
        flight, and the positions counted are those delivered: the row
        a request rode after its ``eos`` is in no count."""
        run, left = overlapped_run, overlapped_run["left"]
        assert left["free_slots"] == [0, 1, 2]
        assert not left["active"] and left["flight"] is None
        if left["free_pages"] is not None:
            assert left["free_pages"][0] == left["free_pages"][1]
        delivered = sum(len(p) + len(a) - 1 for (p, _, _), a in
                        zip(run["given"], run["got"]))
        snap, name = run["snap"], run["name"]
        assert _sum(snap, "dl4j_decode_positions_total",
                    model=name) == delivered
        assert _sum(snap, "dl4j_decode_positions_total", model=name,
                    kind="prompt") == sum(len(p) for p, _, _ in
                                          run["given"])
        assert _sum(snap, "dl4j_serving_decode_tokens_total",
                    model=name) == sum(len(a) for a in run["got"])
        steps = _sum(snap, "dl4j_decode_boundaries_total", model=name,
                     executable="step")
        overlapped = _sum(snap,
                          "dl4j_decode_overlapped_boundaries_total",
                          model=name)
        # eleven requests pending at once: the engine never idles, and
        # delivers at once only where every slot ends on one boundary
        assert steps - 3 <= overlapped < steps

    @pytest.mark.parametrize("arm", ["plain", "chunk", "draft"])
    def test_overlapped_boundaries_over_a_scripted_run(self, arm):
        """(c) ``dl4j_decode_overlapped_boundaries_total``: every
        boundary of one uninterrupted burst but the last on a plain
        engine; none with a block executable or a draft, whose tokens
        are those of the plain engine bit for bit."""
        from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

        kw = dict(seed=14, max_len=64, max_slots=3, page=8,
                  max_pages_per_slot=5)
        model = _xf(**kw)
        opts = {"plain": {}, "chunk": {"chunk": 4},
                "draft": {"speculative": SpeculativeConfig(
                    draft=_draft_of(model), k=2)}}[arm]
        want = [offline_decode(_xf(**kw), p, m) for p, m in _SCRIPT]
        reg = MetricsRegistry()
        prev = telemetry.set_registry(reg)
        telemetry.enable()
        name = f"burst-{arm}"
        eng = DecodeEngine(
            model, name=name,
            instruments=telemetry.serving_instruments(name),
            **opts).warmup()
        try:
            reqs = _submit_at_once(eng, [(p, m, None)
                                         for p, m in _SCRIPT])
            assert [r.result(timeout=180.0) for r in reqs] == want
        finally:
            eng.close()
            telemetry.set_registry(prev)
        snap = reg.snapshot()
        steps = _sum(snap, "dl4j_decode_boundaries_total", model=name,
                     executable="step")
        overlapped = _sum(snap,
                          "dl4j_decode_overlapped_boundaries_total",
                          model=name)
        if arm == "plain":
            # the longest request alone sets the burst's length
            assert steps == 21 + 9 - 1 and overlapped == steps - 1
        else:
            assert steps > 0 and overlapped == 0

    def test_unfed_slot_leaves_shared_prefix_pages_alone(self):
        """A plain engine with a prefix cache overlaps too. A request
        whose last launch is out holds its pages and is not fed: the
        next launch must write that slot's row to the scratch page, not
        to row 0 of its first page, which is a published prefix page
        that concurrent and later requests share. Tokens are the serial
        loop's, for the publishers, for those that adopt beside them
        and for a rerun that adopts after every publisher is gone."""
        kw = dict(seed=16, max_len=64, max_slots=2, page=8,
                  max_pages_per_slot=5)
        rng = np.random.default_rng(16)
        shared = [int(t) for t in rng.integers(1, 40, size=16)]
        given = [(shared + [int(t) for t in rng.integers(1, 40, size=n)],
                  m, None)
                 for n, m in ((2, 2), (5, 9), (1, 3), (3, 7), (2, 1),
                              (4, 6))]
        alone = _xf(**kw)
        want = [offline_decode(alone, p, m) for p, m, _ in given]
        eng = DecodeEngine(_xf(**kw), name="pfx-fl",
                           prefix_cache=True).warmup()
        try:
            assert eng._pick is not None        # it overlaps
            got = [r.result(timeout=180.0)
                   for r in _submit_at_once(eng, given)]
            assert got == want
            assert eng._pcache.hits >= 3
            again = [eng.submit(p, m) for p, m, _ in given[:3]]
            assert [r.result(timeout=180.0) for r in again] == want[:3]
            # and the first cached page still holds, at row 0, what
            # position 0 of the shared prefix wrote there (the tokens
            # of a toy model can survive one foreign row; this cannot)
            pages, _ = eng._pcache.match(shared + [1])
            assert len(pages) == 2
            np.testing.assert_array_equal(
                np.asarray(eng._state["k"])[:, pages[0], 0],
                _row_of_position_0(alone, shared[0]))
            eng.clear_prefix_cache()
            assert eng._kv.free_pages == eng._kv.n_pages
        finally:
            eng.close()

    def test_close_with_a_launch_in_flight(self):
        """(e) ``close()`` with a launch in flight returns within its
        timeout, nothing is delivered afterwards, and every stream
        ends in ``_END`` exactly once."""
        eng = DecodeEngine(_xf(seed=15, max_len=576), name="close-fl"
                           ).warmup()
        rng = np.random.default_rng(6)
        reqs = [eng.submit([int(t) for t in rng.integers(1, 40, size=n)],
                           60) for n in (400, 300, 350)]
        deadline = time.monotonic() + 30.0
        while eng._flight is None and time.monotonic() < deadline:
            time.sleep(0.0005)
        assert eng._flight is not None
        t0 = time.monotonic()
        eng.close(timeout=5.0)
        assert time.monotonic() - t0 < 5.0
        assert not eng._thread.is_alive() and eng._flight is None
        time.sleep(0.1)
        for r in reqs:
            items = []
            while not r.stream.empty():
                items.append(r.stream.get_nowait())
            assert items.count(_DecodeRequest._END) == 1
            assert items[-1] is _DecodeRequest._END
            assert r.future.done()


# ---------------------------------------------------------------------------
# ISSUE 29: the token step's attention visits the live pages only
# ---------------------------------------------------------------------------

# 8 slots of 32 pages of 4 rows: a list of 256 entries, four chunks of 64
LIVE = dict(vocab=40, hidden=32, n_layers=1, n_heads=4, max_len=128,
            max_slots=8, page=4, max_pages_per_slot=32)


def _live_model(seed=1, **kw):
    return TransformerDecodeModel.init(seed=seed, **dict(LIVE, **kw))


def _own_pages(model):
    """Slot s holds pool pages 1 + s*P .. (s+1)*P, in order."""
    S, P = model.max_slots, model.max_pages_per_slot
    return (1 + np.arange(S * P, dtype=np.int32)).reshape(S, P)


def _random_pool(model, seed):
    rng = np.random.default_rng(seed)
    return {n: rng.normal(size=model._pool_shape()).astype(np.float32)
            for n in ("k", "v")}


def _softmax_float64(model, q, kpool, vpool, table, pos, s):
    """Plain softmax attention of slot ``s`` over its own context
    (positions 0..pos[s], through its page table), in float64."""
    H, D = model.n_heads, model.head_dim
    n = int(pos[s]) + 1
    pages = table[s, :(n - 1) // model.page + 1]
    k = np.asarray(kpool)[0][pages].reshape(-1, H, D)[:n].astype(np.float64)
    v = np.asarray(vpool)[0][pages].reshape(-1, H, D)[:n].astype(np.float64)
    sc = np.einsum("hd,nhd->hn", np.asarray(q)[s].reshape(H, D)
                   .astype(np.float64), k) / np.sqrt(D)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hn,nhd->hd", p, v).reshape(-1)


# positions, the slots masked_fn is told are active, another geometry
RAGGED = {
    # a slot at position 0, one at the last row of page 31
    "ragged": ([0, 127, 5, 64, 3, 17, 99, 31], None, {}),
    "all_full": ([127] * 8, None, {}),
    "one_alone": ([0, 0, 0, 77, 0, 0, 0, 0], None, {}),
    "masked_inactive": ([9, 127, 0, 64, 3, 50, 99, 31],
                        [True, False, True, True, False, False, True,
                         True], {}),
    # a table of 90 entries is not whole chunks: the list is 128 long
    "table_not_whole_chunks": ([119, 100, 118], None,
                               dict(max_slots=3, max_pages_per_slot=30)),
}


class TestLivePageAttention:
    @pytest.mark.parametrize("case", sorted(RAGGED))
    def test_step_attention_equals_plain_softmax(self, case, monkeypatch):
        """(a) What ``_fn`` / ``masked_fn`` hand the layer as its
        attention output is, for every slot that was fed, a plain
        softmax over the slot's own context within 1e-5."""
        import jax.numpy as jnp

        pos, active, geometry = RAGGED[case]
        model = _live_model(**geometry)
        pos = np.asarray(pos, np.int32)
        seen = []
        inner = model._paged_attention

        def recording(q, kpool, vpool, li, live):
            out = inner(q, kpool, vpool, li, live)
            seen.append((q, kpool, vpool, out))
            return out
        monkeypatch.setattr(model, "_paged_attention", recording)
        table = _own_pages(model)
        state = {n: jnp.asarray(a)
                 for n, a in _random_pool(model, 0).items()}
        toks = np.arange(3, 3 + model.max_slots, dtype=np.int32)
        if active is None:
            model._fn(model.params, state, toks, pos, table)
            fed = range(model.max_slots)
        else:
            model.masked_fn(model.params, state, toks, pos, table,
                            np.asarray(active))
            fed = np.flatnonzero(active)
        (q, kpool, vpool, out), = seen
        for s in fed:
            ref = _softmax_float64(model, q, kpool, vpool, table, pos, s)
            np.testing.assert_allclose(np.asarray(out)[s], ref,
                                       atol=1e-5, rtol=0)

    def test_slot_is_bit_identical_whatever_its_neighbours_hold(self):
        """(b) Slot 2's attention output and token do not move by a bit
        with its neighbours' lengths and contents: its ten entries
        start at 2 of the list, at 57 (they straddle the first chunk's
        edge), at 64 (on the edge), and there again before other
        followers."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.serving.decode import live_pages

        model = _live_model(seed=2)
        attend = jax.jit(lambda q, k, v, pos, table: model._paged_attention(
            q, k, v, 0, live_pages(pos, table, model.page)))
        table = _own_pages(model)
        mine = _random_pool(model, 7)
        rng = np.random.default_rng(5)
        q = rng.normal(size=(model.max_slots, model.hidden)) \
            .astype(np.float32)
        outs, toks = [], []
        for i, (a, b) in enumerate([(0, 0), (127, 99), (127, 127),
                                    (127, 127)]):
            pos = np.asarray([a, b, 37, 0, 127, 6, 0, 15], np.int32)
            if i == 3:
                pos[3:] = [127, 0, 88, 127, 1]
            pool = _random_pool(model, 100 + i)
            for n in pool:      # the neighbours' pages differ, mine do not
                pool[n][:, table[2]] = mine[n][:, table[2]]
            qi = rng.normal(size=q.shape).astype(np.float32)
            qi[2] = q[2]
            outs.append(np.asarray(attend(qi, pool["k"], pool["v"], pos,
                                          table))[2])
            t = rng.integers(0, 40, size=model.max_slots).astype(np.int32)
            t[2] = 11
            nxt, _ = model.step({n: jnp.asarray(a) for n, a in pool.items()},
                                t, pos, table)
            toks.append(int(np.asarray(nxt)[2]))
        for o in outs[1:]:
            assert np.array_equal(o, outs[0])
        assert len(set(toks)) == 1

    def test_live_list_against_a_hand_count(self):
        """(c) The list builder as a pure function."""
        from deeplearning4j_tpu.serving.decode import live_pages

        table = np.asarray([[7, 0, 0, 0], [3, 9, 4, 0], [5, 6, 0, 0]],
                           np.int32)
        live = {k: np.asarray(v) for k, v in live_pages(
            np.asarray([0, 9, 4], np.int32), table, 4).items()}
        assert int(live["n_live"]) == 6          # 1 + 3 + 2 pages
        assert list(live["slot"][:6]) == [0, 1, 1, 1, 2, 2]
        assert list(live["page"]) == [7, 3, 9, 4, 5, 6] + [0] * 6
        # position 9 is row 1 of its slot's third page
        assert list(live["last"]) == [0, 9, 5, 1, 4, 0] + [-1] * 6
        assert live["own"].tolist() == [[0, 0, 0, 0], [1, 2, 3, 0],
                                        [4, 5, 0, 0]]
        assert live["dead"].tolist() == [[False, True, True, True],
                                         [False, False, False, True],
                                         [False, False, True, True]]

    @pytest.mark.parametrize("pos, n_live", [
        ([0] * 8, 8), ([0, 127, 5, 64, 3, 17, 99, 31], 91),
        ([127] * 8, 256)])
    def test_trip_count_follows_the_live_pages(self, pos, n_live,
                                               monkeypatch):
        """(c) The loop runs ``ceil(n_live / LIVE_CHUNK)`` times, not
        ``max_pages_per_slot``: its body's calls counted with jit off
        (three slices of the list an iteration)."""
        import jax

        from deeplearning4j_tpu.serving import decode

        model = _live_model()
        calls = []
        inner = jax.lax.dynamic_slice_in_dim

        def counted(*a, **kw):
            calls.append(1)
            return inner(*a, **kw)
        monkeypatch.setattr(jax.lax, "dynamic_slice_in_dim", counted)
        pool = _random_pool(model, 3)
        q = np.ones((model.max_slots, model.hidden), np.float32)
        pos = np.asarray(pos, np.int32)
        with jax.disable_jit():
            live = decode.live_pages(pos, _own_pages(model), model.page)
            assert int(live["n_live"]) == n_live
            model._paged_attention(q, pool["k"], pool["v"], 0, live)
        assert len(calls) == 3 * -(-n_live // decode.LIVE_CHUNK)
        assert len(calls) // 3 <= 4 < model.max_pages_per_slot

    def test_live_pages_counter_over_a_scripted_run(self):
        """(d) ``dl4j_decode_live_pages_sum``: every position a request
        is fed (all but its last answer token) adds the pages its
        context reaches, once."""
        from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry()
        prev = telemetry.set_registry(reg)
        telemetry.enable()
        eng = DecodeEngine(
            _live_model(), name="livepages",
            instruments=telemetry.serving_instruments("livepages")).warmup()
        given = [(9, 5), (1, 3), (30, 7)]        # prompt length, max_new
        try:
            reqs = [eng.submit(list(range(1, n + 1)), m) for n, m in given]
            for r in reqs:
                r.result(timeout=120.0)
        finally:
            eng.close()
            telemetry.set_registry(prev)
        snap = reg.snapshot()
        want = sum(p // LIVE["page"] + 1
                   for n, m in given for p in range(n + m - 1))
        assert snap['dl4j_decode_live_pages_sum{model="livepages"}'] == want
