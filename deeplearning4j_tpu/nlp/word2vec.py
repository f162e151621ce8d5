"""Word2Vec: SkipGram / CBOW with negative sampling, device-resident.

Reference capability: deeplearning4j-nlp org.deeplearning4j.models.word2vec
.Word2Vec + SkipGram/CBOW learning algorithms (BASELINE.json configs[4],
SURVEY.md §2.7). The reference's hot loop is a host-driven sparse custom op
(libnd4j `skipgram`) per word pair; here training is BATCHED on device
(SURVEY.md §7 hard part 6): one jitted step takes [B] centers, [B]
contexts, [B,K] negatives, and jax.grad's gather VJP produces exactly the
sparse scatter-add update the reference hand-codes — fused with the SGD
apply, params donated.

Vocab build, frequent-word subsampling, window pairing, and unigram^0.75
negative-table sampling are host-side numpy (they are ETL, not math)."""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nlp.tokenization import (
    DefaultTokenizerFactory, SentenceIterator)


class VocabWord:
    def __init__(self, word, count, index):
        self.word = word
        self.count = count
        self.index = index


class VocabCache:
    def __init__(self):
        self.words: list[VocabWord] = []
        self._by_word: dict[str, VocabWord] = {}

    def add(self, word, count):
        vw = VocabWord(word, count, len(self.words))
        self.words.append(vw)
        self._by_word[word] = vw
        return vw

    def containsWord(self, w):
        return w in self._by_word

    def indexOf(self, w):
        return self._by_word[w].index if w in self._by_word else -1

    def wordAtIndex(self, i):
        return self.words[i].word

    def wordFrequency(self, w):
        return self._by_word[w].count if w in self._by_word else 0

    def numWords(self):
        return len(self.words)

    def totalWordOccurrences(self):
        return sum(w.count for w in self.words)


def _sgns_loss(syn0, syn1, centers, contexts, negatives, weights):
    """Skip-gram negative sampling loss for a batch.
    centers [B], contexts [B], negatives [B,K], weights [B] (0 = padding)."""
    c = syn0[centers]                      # [B,D]
    pos = syn1[contexts]                   # [B,D]
    neg = syn1[negatives]                  # [B,K,D]
    pos_score = jnp.sum(c * pos, axis=-1)
    neg_score = jnp.einsum("bd,bkd->bk", c, neg)
    # -log sigma(pos) - sum log sigma(-neg), numerically stable.
    # SUM over the batch (not mean): each pair must contribute a full
    # per-pair SGD update like the reference's sequential loop — a mean
    # would divide the learning rate by the batch size. Weights zero out
    # tail-padding pairs exactly (sum, so no denominator to bias).
    per_pair = (jax.nn.softplus(-pos_score)
                + jnp.sum(jax.nn.softplus(neg_score), axis=-1))
    return jnp.sum(per_pair * weights)


def _cbow_loss(syn0, syn1, contexts_mat, context_mask, centers, negatives,
               weights):
    """CBOW: mean of context word vectors predicts the center.
    contexts_mat [B,W], context_mask [B,W], centers [B], negatives [B,K],
    weights [B] (0 = padding)."""
    ctx = syn0[contexts_mat]               # [B,W,D]
    m = context_mask[..., None]
    mean = jnp.sum(ctx * m, axis=1) / jnp.maximum(
        jnp.sum(m, axis=1), 1.0)           # [B,D]
    pos = syn1[centers]
    neg = syn1[negatives]
    pos_score = jnp.sum(mean * pos, axis=-1)
    neg_score = jnp.einsum("bd,bkd->bk", mean, neg)
    per_pair = (jax.nn.softplus(-pos_score)
                + jnp.sum(jax.nn.softplus(neg_score), axis=-1))
    return jnp.sum(per_pair * weights)


def _compaction_dests(val_s, cap):
    """Stream-compaction scatter destinations for `cap` slots with
    validity mask `val_s`: valid slot -> its rank among valid slots
    (cumsum-1), invalid slot -> a DISTINCT out-of-range dest (cap +
    slot index). Every dest is unique across the whole array — the
    downstream scatters promise unique_indices=True, and a shared
    sentinel dest would be UB per the JAX scatter docs even though
    mode="drop" discards those writes (ADVICE r4). Returns
    (dests, n_valid) — the count rides the cumsum already computed."""
    csum = jnp.cumsum(val_s.astype(jnp.int32))
    return jnp.where(val_s, csum - 1,
                     cap + jnp.arange(cap, dtype=jnp.int32)), csum[-1]


class Word2Vec:
    class Builder:
        def __init__(self):
            # batchSize 8192: step throughput rose 1.5 -> 4.3 Mpairs/s
            # from 2048 -> 8192 (per-step fixed costs amortize;
            # tools/RESNET_MFU.md section 4: July 2026, not re-measured);
            # SGNS quality is batch-tolerant (hogwild heritage) and the
            # pair order is shuffled
            self._kw = dict(minWordFrequency=5, layerSize=100, windowSize=5,
                            negative=5, learningRate=0.025, epochs=1,
                            iterations=1, seed=42, batchSize=8192,
                            sampling=1e-3, algorithm="skipgram")
            self._iter = None
            self._tok = None

        def minWordFrequency(self, n):
            self._kw["minWordFrequency"] = n
            return self

        def layerSize(self, n):
            self._kw["layerSize"] = n
            return self

        def windowSize(self, n):
            self._kw["windowSize"] = n
            return self

        def negativeSampling(self, n):
            self._kw["negative"] = int(n)
            return self

        def negative(self, n):
            return self.negativeSampling(n)

        def negativeSample(self, n):
            # DL4J name: Word2Vec.Builder#negativeSample(double)
            return self.negativeSampling(n)

        def learningRate(self, lr):
            self._kw["learningRate"] = lr
            return self

        def epochs(self, n):
            self._kw["epochs"] = n
            return self

        def iterations(self, n):
            self._kw["iterations"] = n
            return self

        def seed(self, s):
            self._kw["seed"] = s
            return self

        def batchSize(self, n):
            self._kw["batchSize"] = n
            return self

        def deviceETL(self, b=True):
            """Generate skip-gram pairs on the accelerator (default ON
            for the SGNS path): host uploads only the subsampled corpus.
            Turn off to use the host/native pair generator (needed for
            shufflePairs)."""
            self._kw["deviceETL"] = bool(b)
            return self

        def shufflePairs(self, b=True):
            """Globally shuffle the epoch's (center, context) pairs
            before batching. The reference trains in corpus order, so
            this defaults OFF; turn on to decorrelate batches at ~3 s
            host cost per 10M words."""
            self._kw["shufflePairs"] = bool(b)
            return self

        def sampling(self, s):
            self._kw["sampling"] = s
            return self

        def exactNegatives(self, b=True):
            """Draw fresh negatives for every pair inside every step
            (the r4 semantics). Default OFF: negatives come from a
            per-launch pool of iid unigram^0.75 draws, each step
            slicing a pseudo-random window — cheaper per step
            (tools/RESNET_MFU.md section 4; July 2026, not re-measured
            on this installation), same marginal distribution, but pool
            windows can overlap across steps."""
            self._kw["exactNegatives"] = bool(b)
            return self

        def elementsLearningAlgorithm(self, name):
            self._kw["algorithm"] = ("cbow" if "cbow" in str(name).lower()
                                     else "skipgram")
            return self

        def iterate(self, sentence_iterator: SentenceIterator):
            self._iter = sentence_iterator
            return self

        def tokenizerFactory(self, tok):
            self._tok = tok
            return self

        def build(self) -> "Word2Vec":
            return Word2Vec(self._iter, self._tok or
                            DefaultTokenizerFactory(), **self._kw)

    def __init__(self, sentence_iterator, tokenizer_factory, **kw):
        self.sentences = sentence_iterator
        self.tokenizer = tokenizer_factory
        self.cfg = kw
        self.vocab = VocabCache()
        self.syn0 = None     # input vectors [V,D]
        self.syn1 = None     # output vectors [V,D]
        self._neg_table = None
        self._neg_table_int = None
        self._step_fn = None
        self._multi_fn = None
        self._k_bucket = None

    # -- vocab ---------------------------------------------------------------
    def _invalidate_corpus_caches(self):
        """Drop every token/corpus/pairgen cache derived from the current
        sentences+vocab (ADVICE r5: the caches were never invalidated, so
        refitting after a corpus or vocab change silently trained on the
        stale uploaded corpus). Called by buildVocab(); call directly
        after mutating `sentences` in place without rebuilding the
        vocab."""
        for attr in ("_tok_flat", "_tok_offsets", "_keep_prob",
                     "_corpus_dev", "_keep_prob_dev", "_pairgen_fn",
                     "_neg_table_dev", "_fused_fn", "_fused_sig"):
            if hasattr(self, attr):
                delattr(self, attr)
        # K-bucket / step fns are shape-keyed: a new corpus/vocab means
        # new pair counts and possibly a new vocab size, so let them
        # rebuild rather than reuse a stale bucket
        self._k_bucket = None
        self._step_fn = None
        self._multi_fn = None

    def buildVocab(self):
        self._invalidate_corpus_caches()
        old_words = [w.word for w in self.vocab.words]
        self.vocab = VocabCache()
        counts: dict[str, int] = {}
        for sent in self.sentences:
            for t in self.tokenizer.create(sent).getTokens():
                counts[t] = counts.get(t, 0) + 1
        min_f = self.cfg["minWordFrequency"]
        for w, c in sorted(counts.items(), key=lambda kv: (-kv[1], kv[0])):
            if c >= min_f:
                self.vocab.add(w, c)
        if self.vocab.numWords() == 0:
            raise ValueError(
                f"empty vocab: no word reaches minWordFrequency={min_f}")
        if self.syn0 is not None and \
                [w.word for w in self.vocab.words] != old_words:
            # the word -> index mapping changed (size OR order OR
            # membership): trained vectors no longer line up with
            # indices — restart rather than silently misassign
            self.syn0 = None
            self.syn1 = None
        self._build_neg_tables()
        return self

    def _build_neg_tables(self):
        """Unigram^0.75 negative-sampling tables from the current vocab —
        callable lazily too, for models whose vocab was installed by a
        deserializer rather than buildVocab()."""
        freqs = np.array([max(w.count, 1) for w in self.vocab.words],
                         np.float64)
        probs = freqs ** 0.75
        self._neg_table = (probs / probs.sum()).astype(np.float64)
        # quantized unigram table (the original word2vec trick): sampling
        # becomes a uniform-int gather, ~10x cheaper than choice(p=...)
        table_size = min(1_000_000, max(10_000, 100 * len(freqs)))
        counts = np.maximum(
            1, np.round(self._neg_table * table_size)).astype(np.int64)
        self._neg_table_int = np.repeat(
            np.arange(len(freqs), dtype=np.int32), counts)

    # -- pair generation (host ETL) -----------------------------------------
    def _encode_corpus(self, rng):
        total = self.vocab.totalWordOccurrences()
        t = self.cfg["sampling"]
        encoded = []
        for sent in self.sentences:
            idxs = []
            for tok in self.tokenizer.create(sent).getTokens():
                i = self.vocab.indexOf(tok)
                if i < 0:
                    continue
                if t > 0:
                    f = self.vocab.words[i].count / total
                    keep = (math.sqrt(f / t) + 1) * (t / f) if f > t else 1.0
                    if rng.random() > keep:
                        continue
                idxs.append(i)
            if len(idxs) > 1:
                encoded.append(np.asarray(idxs, np.int32))
        return encoded

    def _flat_token_cache(self):
        """One-time tokenize+index of the whole corpus into a flat int32
        array + sentence offsets, so per-epoch subsampling is a vectorized
        numpy pass instead of a 10M-iteration Python loop (VERDICT
        round-2 item 5: at >=10M words the old per-token loop was the
        bottleneck, not the chip)."""
        if getattr(self, "_tok_flat", None) is not None:
            return self._tok_flat, self._tok_offsets, self._keep_prob
        by_word = self.vocab._by_word
        flats, lens = [], []
        for sent in self.sentences:
            toks = self.tokenizer.create(sent).getTokens()
            idx = [by_word[t].index for t in toks if t in by_word]
            flats.append(np.asarray(idx, np.int32))
            lens.append(len(idx))
        self._tok_flat = (np.concatenate(flats) if flats
                          else np.zeros(0, np.int32))
        self._tok_offsets = np.zeros(len(lens) + 1, np.int64)
        np.cumsum(lens, out=self._tok_offsets[1:])
        t = self.cfg["sampling"]
        if t > 0:
            total = self.vocab.totalWordOccurrences()
            f = np.array([w.count / total for w in self.vocab.words],
                         np.float64)
            keep = np.where(f > t, (np.sqrt(f / t) + 1) * (t / f), 1.0)
            self._keep_prob = np.minimum(keep, 1.0).astype(np.float32)
        else:
            self._keep_prob = None
        return self._tok_flat, self._tok_offsets, self._keep_prob

    def _subsampled_flat(self, rng):
        """Per-epoch frequent-word subsampling, vectorized over the flat
        token array. Returns (flat, offsets)."""
        flat, offsets, keep_prob = self._flat_token_cache()
        if keep_prob is None:
            return flat, offsets
        mask = rng.random(len(flat)) < keep_prob[flat]
        kept = flat[mask]
        # per-sentence kept counts via prefix sums — exact for empty
        # sentences anywhere, including a trailing all-OOV/blank one
        # (np.add.reduceat would index out of bounds there)
        csum = np.zeros(len(flat) + 1, np.int64)
        np.cumsum(mask, out=csum[1:])
        new_offsets = csum[offsets]
        return kept.astype(np.int32), new_offsets

    # -- device-side pair generation (r4, reworked r5) ----------------------
    def _build_pairgen(self, subsample: bool):
        """Jitted per-epoch ETL entirely ON DEVICE: frequent-word
        subsampling (bernoulli keep + stream compaction of the token
        stream), then skip-gram pair generation + pair compaction. The
        host uploads the tokenized corpus ONCE across all epochs; the
        r4 design re-uploaded the host-subsampled corpus every epoch
        and spent its epochs in host numpy + host-to-device transfer.

        Semantics match the host pair-gen: subsample-then-window (the
        window closes over removed tokens), per-position window radius
        b ~ U[1, W], contexts pos+d for 0 < |d| <= b within the same
        sentence, pairs emitted in corpus order (position-major, d
        ascending). Compaction is cumsum + unique-index scatter; the
        invalid slots' scatter targets fall off the end and are
        dropped."""
        w = self.cfg["windowSize"]

        def shift(a, d):
            """a[clip(pos+d, 0, p-1)] as slice+concat: TPU scalar
            gathers measured ~0.19 GB/s on this chip where slices run
            at full bandwidth — the r4 gather formulation spent ~3.4 s
            of the 4.4 s pair-gen in 10 shifted gathers
            (tools/RESNET_MFU.md section 4; July 2026, r5)."""
            p = a.shape[0]
            if d > 0:
                return jnp.concatenate(
                    [a[d:], jnp.broadcast_to(a[-1:], (d,))])
            return jnp.concatenate(
                [jnp.broadcast_to(a[:1], (-d,)), a[:d]])

        def gen(flat, sid, keep_prob, key_sub, key_b):
            p = flat.shape[0]
            if subsample:
                u = jax.random.uniform(key_sub, (p,))
                keep = (sid >= 0) & (u < keep_prob[flat])
                dest, _nk = _compaction_dests(keep, p)
                flat = jnp.zeros((p,), jnp.int32).at[dest].set(
                    flat, mode="drop", unique_indices=True)
                sid = jnp.full((p,), -1, jnp.int32).at[dest].set(
                    sid, mode="drop", unique_indices=True)
            pos = jnp.arange(p, dtype=jnp.int32)
            b = jax.random.randint(key_b, (p,), 1, w + 1)
            cents, ctxs, vals = [], [], []
            for d in (*range(-w, 0), *range(1, w + 1)):
                valid = ((sid >= 0) & (shift(sid, d) == sid)
                         & (jnp.abs(d) <= b)
                         & (pos + d >= 0) & (pos + d < p))
                cents.append(flat)
                ctxs.append(shift(flat, d))
                vals.append(valid)
            cent_s = jnp.stack(cents, 1).reshape(-1)
            ctx_s = jnp.stack(ctxs, 1).reshape(-1)
            val_s = jnp.stack(vals, 1).reshape(-1)
            cap = cent_s.shape[0]
            dest, n_real = _compaction_dests(val_s, cap)
            # (a packed-slot single-scatter + gather-decode variant
            # measured SLOWER than these two element scatters — r4; a
            # [cap, 2] row-scatter variant measured 4x slower still,
            # and scatter-free searchsorted compaction 10x slower —
            # tools/RESNET_MFU.md section 4; July 2026, r5)
            out_c = jnp.zeros((cap,), jnp.int32).at[dest].set(
                cent_s, mode="drop", unique_indices=True)
            out_x = jnp.zeros((cap,), jnp.int32).at[dest].set(
                ctx_s, mode="drop", unique_indices=True)
            return out_c, out_x, n_real

        return jax.jit(gen)

    def _device_pairs(self, rng):
        """Generate + compact the epoch's pairs on device (subsampling
        included). Returns (cent_dev, ctx_dev, n_real) with cent/ctx
        length = the padded slot capacity (first n_real are real)."""
        flat, offsets, keep_prob = self._flat_token_cache()
        if getattr(self, "_corpus_dev", None) is None:
            sid = np.repeat(
                np.arange(len(offsets) - 1, dtype=np.int32),
                np.diff(offsets))
            p_b = -(-max(1, len(flat)) // 1024) * 1024
            flat_pad = np.zeros(p_b, np.int32)
            flat_pad[:len(flat)] = flat
            sid_pad = np.full(p_b, -1, np.int32)
            sid_pad[:len(flat)] = sid
            self._corpus_dev = (jax.device_put(flat_pad),
                                jax.device_put(sid_pad))
            self._keep_prob_dev = (
                jax.device_put(keep_prob) if keep_prob is not None
                else jnp.zeros((1,), jnp.float32))
        if getattr(self, "_pairgen_fn", None) is None:
            self._pairgen_fn = self._build_pairgen(keep_prob is not None)
        key_sub = jax.random.key(int(rng.integers(0, 2 ** 31)))
        key_b = jax.random.key(int(rng.integers(0, 2 ** 31)), impl="rbg")
        cent, ctx, n = self._pairgen_fn(
            *self._corpus_dev, self._keep_prob_dev, key_sub, key_b)
        return cent, ctx, int(n)

    def _make_pairs_flat(self, flat, offsets, rng):
        """Skip-gram pairs straight from (flat, offsets) — native kernel
        when available, list-based fallback otherwise."""
        win = self.cfg["windowSize"]
        bs_all = rng.integers(1, win + 1, len(flat)).astype(np.int32)

        from deeplearning4j_tpu import native

        if native.available():
            pairs = native.sg_pairs_flat(flat, offsets, bs_all)
            if pairs is not None:
                return pairs
        centers, contexts = [], []
        for i in range(len(offsets) - 1):
            idxs = flat[offsets[i]:offsets[i + 1]]
            bs = bs_all[offsets[i]:offsets[i + 1]]
            n = len(idxs)
            for pos in range(n):
                b = bs[pos]
                lo, hi = max(0, pos - b), min(n, pos + b + 1)
                for j in range(lo, hi):
                    if j != pos:
                        centers.append(idxs[pos])
                        contexts.append(idxs[j])
        return (np.asarray(centers, np.int32),
                np.asarray(contexts, np.int32))

    def _make_pairs(self, encoded, rng):
        """List-of-sentences front end over _make_pairs_flat (kept for
        the CBOW path and API compatibility)."""
        if not encoded:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32))
        flat = np.concatenate(encoded).astype(np.int32)
        offsets = np.zeros(len(encoded) + 1, np.int64)
        np.cumsum([len(s) for s in encoded], out=offsets[1:])
        return self._make_pairs_flat(flat, offsets, rng)

    # -- training ------------------------------------------------------------
    def _build_step(self, cbow):
        lr = self.cfg["learningRate"]
        loss_fn = _cbow_loss if cbow else _sgns_loss

        def step(syn0, syn1, *batch):
            loss, (g0, g1) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(syn0, syn1, *batch)
            return loss, syn0 - lr * g0, syn1 - lr * g1

        return jax.jit(step, donate_argnums=(0, 1))

    def _build_multi_step_fused(self, k, bsz, n_pool):
        """Whole-epoch SGNS training in ONE device launch: lax.scan over
        the epoch's [K, bsz] batches, sliced+reshaped from the pair-gen
        output INSIDE the jit (no separate pad/reshape/weights prep
        launches).

        Negatives come from a per-launch POOL: one vectorized
        randint+table-gather of n_pool draws, with each step taking a
        pseudo-random contiguous slice. The r4 per-step fold_in +
        randint + gather cost 0.65 ms of the 1.9 ms step
        (tools/RESNET_MFU.md section 4, July 2026) — a dynamic slice is free,
        and each slice is still iid unigram^0.75 draws independent of
        the step's pairs (windows may overlap across steps; set
        exactNegatives(True) for per-step draws). Step losses are not
        computed (nothing consumed them; the analytic gradients don't
        need the loss value)."""
        lr = self.cfg["learningRate"]
        k_neg = self.cfg["negative"]
        full = k * bsz

        def many_fused(syn0, syn1, cent_all, ctx_all, n_real, table,
                       key):
            tsize = table.shape[0]
            d = syn0.shape[1]
            cent_k = cent_all[:full].reshape(k, bsz)
            ctx_k = ctx_all[:full].reshape(k, bsz)
            w_k = (jnp.arange(full, dtype=jnp.int32) < n_real) \
                .astype(jnp.float32).reshape(k, bsz)
            draws = jax.random.randint(key, (n_pool,), 0, tsize)
            pool = table[draws]
            span = bsz * k_neg

            def body(carry, xs):
                syn0, syn1, i = carry
                cent, ctx, w = xs
                off = (i.astype(jnp.uint32) * jnp.uint32(2654435761)
                       % jnp.uint32(n_pool - span)).astype(jnp.int32)
                negs = jax.lax.dynamic_slice(
                    pool, (off,), (span,)).reshape(bsz, k_neg)
                c = syn0[cent]
                pos = syn1[ctx]
                neg = syn1[negs]
                pos_s = jnp.sum(c * pos, axis=-1)
                neg_s = jnp.einsum("bd,bkd->bk", c, neg)
                dpos = -(1.0 - jax.nn.sigmoid(pos_s)) * w
                dneg = jax.nn.sigmoid(neg_s) * w[:, None]
                gc = dpos[:, None] * pos + \
                    jnp.einsum("bk,bkd->bd", dneg, neg)
                o0 = jnp.argsort(cent)
                syn0 = syn0.at[cent[o0]].add(
                    -lr * gc[o0], indices_are_sorted=True)
                ids1 = jnp.concatenate([ctx, negs.reshape(-1)])
                u1 = jnp.concatenate([
                    dpos[:, None] * c,
                    (dneg[..., None] * c[:, None, :]).reshape(-1, d)])
                o1 = jnp.argsort(ids1)
                syn1 = syn1.at[ids1[o1]].add(
                    -lr * u1[o1], indices_are_sorted=True)
                return (syn0, syn1, i + 1), None

            (syn0, syn1, _), _ = jax.lax.scan(
                body, (syn0, syn1, jnp.int32(0)), (cent_k, ctx_k, w_k))
            return syn0, syn1

        return jax.jit(many_fused, donate_argnums=(0, 1),
                       static_argnames=())

    def _build_multi_step(self):
        """Pre-r5 scan over host-prepared [K, bsz] batches with exact
        per-step negative draws (exactNegatives(True) / shufflePairs
        path)."""
        lr = self.cfg["learningRate"]
        k_neg = self.cfg["negative"]

        def many(syn0, syn1, cent_k, ctx_k, w_k, table, key):
            tsize = table.shape[0]
            d = syn0.shape[1]

            def body(carry, xs):
                syn0, syn1, i = carry
                cent, ctx, w = xs
                draws = jax.random.randint(
                    jax.random.fold_in(key, i),
                    (cent.shape[0], k_neg), 0, tsize)
                negs = table[draws]
                # Analytic SGNS gradients + SORTED row scatters instead
                # of jax.grad: the grad-of-gather path materializes a
                # DENSE [V,D] gradient table per step (plus a dense
                # axpy), measured as the real bound — the sorted
                # in-place row update is ~3x faster at the same math
                # (sort cost ~2% of step; indices_are_sorted lets XLA's
                # scatter skip the unsorted-duplicate slow path: 125M vs
                # 78M rows/s; tools/RESNET_MFU.md section 4, July 2026,
                # not re-measured).
                c = syn0[cent]
                pos = syn1[ctx]
                neg = syn1[negs]
                pos_s = jnp.sum(c * pos, axis=-1)
                neg_s = jnp.einsum("bd,bkd->bk", c, neg)
                loss = jnp.sum(
                    (jax.nn.softplus(-pos_s)
                     + jnp.sum(jax.nn.softplus(neg_s), axis=-1)) * w)
                dpos = -(1.0 - jax.nn.sigmoid(pos_s)) * w      # [B]
                dneg = jax.nn.sigmoid(neg_s) * w[:, None]      # [B,K]
                gc = dpos[:, None] * pos + \
                    jnp.einsum("bk,bkd->bd", dneg, neg)
                o0 = jnp.argsort(cent)
                syn0 = syn0.at[cent[o0]].add(
                    -lr * gc[o0], indices_are_sorted=True)
                ids1 = jnp.concatenate([ctx, negs.reshape(-1)])
                u1 = jnp.concatenate([
                    dpos[:, None] * c,
                    (dneg[..., None] * c[:, None, :]).reshape(-1, d)])
                o1 = jnp.argsort(ids1)
                syn1 = syn1.at[ids1[o1]].add(
                    -lr * u1[o1], indices_are_sorted=True)
                return (syn0, syn1, i + 1), loss

            (syn0, syn1, _), losses = jax.lax.scan(
                body, (syn0, syn1, jnp.int32(0)), (cent_k, ctx_k, w_k))
            return losses, syn0, syn1

        return jax.jit(many, donate_argnums=(0, 1))

    def fit(self):
        if self.vocab.numWords() == 0:
            self.buildVocab()
        if self._neg_table_int is None:
            # vocab may have been installed by a deserializer
            self._build_neg_tables()
        cfg = self.cfg
        v, d = self.vocab.numWords(), cfg["layerSize"]
        rng = np.random.default_rng(cfg["seed"])
        key = jax.random.key(cfg["seed"])
        if self.syn0 is None:
            self.syn0 = (jax.random.uniform(key, (v, d), jnp.float32)
                         - 0.5) / d
            self.syn1 = jnp.zeros((v, d), jnp.float32)
        cbow = cfg["algorithm"] == "cbow"
        if self._step_fn is None:
            self._step_fn = self._build_step(cbow)
        k_neg = cfg["negative"]
        bsz = cfg["batchSize"]
        syn0, syn1 = self.syn0, self.syn1
        if not cbow and getattr(self, "_neg_table_dev", None) is None:
            self._neg_table_dev = jax.device_put(
                jnp.asarray(self._neg_table_int))
        for _epoch in range(cfg["epochs"]):
            if not cbow:
                # SGNS fast path: vectorized subsampling over the cached
                # flat token array, native pair-gen, then the epoch's
                # batches stacked into one scan launch per `iterations`
                # pass with on-device negative draws
                device_etl = (self.cfg.get("deviceETL", True)
                              and not self.cfg.get("shufflePairs"))
                if device_etl:
                    # upload the ~30 MB corpus, generate pairs on chip
                    cent_all, ctx_all, n = self._device_pairs(rng)
                else:
                    flat, offsets = self._subsampled_flat(rng)
                    centers, contexts = self._make_pairs_flat(
                        flat, offsets, rng)
                    if self.cfg.get("shufflePairs"):
                        # the reference trains in corpus order; opt-in
                        # shuffle costs ~3 s/epoch per 10M words on host
                        order = rng.permutation(len(centers))
                        centers = centers[order]
                        contexts = contexts[order]
                    n = len(centers)
                k = max(1, (n + bsz - 1) // bsz)
                # bucket K with a 2% margin (and to a multiple of 8) so
                # subsampling-induced pair-count jitter across epochs
                # reuses ONE compiled scan — a bare multiple-of-8 bucket
                # left ~0.2% headroom, so a later epoch could exceed it
                # and silently RECOMPILE the whole-epoch scan (~12 s)
                # inside fit (r4 bench diagnosis); extra batches are
                # zero-weighted
                k = -(-(k + max(8, k // 50)) // 8) * 8
                if self._k_bucket is None or k > self._k_bucket:
                    self._k_bucket = k
                k = self._k_bucket
                full = k * bsz
                if device_etl and not self.cfg.get("exactNegatives"):
                    # fused path: slice/reshape/weights + pooled
                    # negatives inside ONE launch
                    if full > cent_all.shape[0]:
                        cent_all = jnp.pad(
                            cent_all, (0, full - cent_all.shape[0]))
                        ctx_all = jnp.pad(
                            ctx_all, (0, full - ctx_all.shape[0]))
                    pool = max(1 << 21, 2 * bsz * k_neg)
                    if getattr(self, "_fused_fn", None) is None or \
                            self._fused_sig != (k, bsz):
                        self._fused_fn = self._build_multi_step_fused(
                            k, bsz, pool)
                        self._fused_sig = (k, bsz)
                    for it in range(cfg["iterations"]):
                        key = jax.random.key(
                            int(rng.integers(0, 2**31)))
                        syn0, syn1 = self._fused_fn(
                            syn0, syn1, cent_all, ctx_all,
                            jnp.int32(n), self._neg_table_dev, key)
                    continue
                if device_etl:
                    # first n slots are real pairs; the tail (and any
                    # slice beyond the compacted region) is zero-weighted
                    pad = full - cent_all.shape[0]
                    if pad > 0:
                        cent_all = jnp.pad(cent_all, (0, pad))
                        ctx_all = jnp.pad(ctx_all, (0, pad))
                    cent_k = cent_all[:full].reshape(k, bsz)
                    ctx_k = ctx_all[:full].reshape(k, bsz)
                    w_k = (jnp.arange(full, dtype=jnp.int32) < n) \
                        .astype(jnp.float32).reshape(k, bsz)
                else:
                    w_flat = np.concatenate(
                        [np.ones(n, np.float32),
                         np.zeros(full - n, np.float32)])
                    # device_put explicitly: numpy args to a jitted call
                    # take a synchronous per-argument transfer path
                    cent_k = jax.device_put(
                        np.resize(centers, full).reshape(k, bsz))
                    ctx_k = jax.device_put(
                        np.resize(contexts, full).reshape(k, bsz))
                    w_k = jax.device_put(w_flat.reshape(k, bsz))
                if getattr(self, "_multi_fn", None) is None:
                    self._multi_fn = self._build_multi_step()
                for it in range(cfg["iterations"]):
                    # threefry, not rbg: the per-step fold_in+randint
                    # inside the scan measured 0.26 ms/step cheaper
                    # (1.55 vs 1.81 ms; tools/RESNET_MFU.md section 4,
                    # July 2026, r5) — rbg's fold_in is the slow part
                    key = jax.random.key(int(rng.integers(0, 2**31)))
                    _losses, syn0, syn1 = self._multi_fn(
                        syn0, syn1, cent_k, ctx_k, w_k,
                        self._neg_table_dev, key)
                continue
            encoded = self._encode_corpus(rng)
            batches = self._cbow_batches(encoded, rng, bsz)
            for _ in range(cfg["iterations"]):
                for batch in batches:
                    b = len(batch[0])
                    if b == 0:
                        continue
                    # pad the tail batch to the full batch size with
                    # zero-weighted pairs: ONE compiled shape regardless of
                    # how the stochastic subsampling changes the pair count
                    # across epochs (the loss is a weighted SUM, so the
                    # padding contributes exactly zero loss and gradient)
                    full = max(bsz, b)
                    pad = full - b
                    weights = np.concatenate(
                        [np.ones(b, np.float32), np.zeros(pad, np.float32)])
                    batch = tuple(
                        np.concatenate(
                            [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                        if pad else a for a in batch)
                    negs = rng.choice(v, size=(full, k_neg),
                                      p=self._neg_table).astype(np.int32)
                    if cbow:
                        ctx_mat, mask, cent = batch
                        loss, syn0, syn1 = self._step_fn(
                            syn0, syn1, ctx_mat, mask, cent, negs, weights)
                    else:
                        cent, ctx = batch
                        loss, syn0, syn1 = self._step_fn(
                            syn0, syn1, cent, ctx, negs, weights)
        self.syn0, self.syn1 = syn0, syn1
        return self

    def _cbow_batches(self, encoded, rng, bsz):
        win = self.cfg["windowSize"]
        rows_ctx, rows_mask, rows_center = [], [], []
        width = 2 * win
        for idxs in encoded:
            n = len(idxs)
            bs = rng.integers(1, win + 1, n)
            for pos in range(n):
                b = bs[pos]
                lo, hi = max(0, pos - b), min(n, pos + b + 1)
                ctx = [idxs[j] for j in range(lo, hi) if j != pos]
                if not ctx:
                    continue
                row = np.zeros(width, np.int32)
                msk = np.zeros(width, np.float32)
                row[:len(ctx)] = ctx
                msk[:len(ctx)] = 1.0
                rows_ctx.append(row)
                rows_mask.append(msk)
                rows_center.append(idxs[pos])
        ctx_m = np.stack(rows_ctx)
        mask = np.stack(rows_mask)
        cent = np.asarray(rows_center, np.int32)
        order = np.random.default_rng(0).permutation(len(cent))
        ctx_m, mask, cent = ctx_m[order], mask[order], cent[order]
        out = [(ctx_m[i:i + bsz], mask[i:i + bsz], cent[i:i + bsz])
               for i in range(0, len(cent), bsz)]
        return out or [(ctx_m, mask, cent)]

    # -- lookups -------------------------------------------------------------
    def getWordVector(self, word) -> np.ndarray:
        i = self.vocab.indexOf(word)
        if i < 0:
            raise KeyError(word)
        return np.asarray(self.syn0[i])

    def getWordVectorMatrix(self) -> np.ndarray:
        return np.asarray(self.syn0)

    def hasWord(self, w):
        return self.vocab.containsWord(w)

    def similarity(self, a, b) -> float:
        va, vb = self.getWordVector(a), self.getWordVector(b)
        return float(va @ vb / (np.linalg.norm(va) * np.linalg.norm(vb)
                                + 1e-12))

    def wordsNearest(self, word_or_vec, n=10) -> list:
        if isinstance(word_or_vec, str):
            vec = self.getWordVector(word_or_vec)
            exclude = {word_or_vec}
        else:
            vec = np.asarray(word_or_vec)
            exclude = set()
        m = self.getWordVectorMatrix()
        norms = np.linalg.norm(m, axis=1) * (np.linalg.norm(vec) + 1e-12)
        sims = m @ vec / np.maximum(norms, 1e-12)
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.wordAtIndex(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= n:
                break
        return out
