"""Benchmarks for the five BASELINE.md configs on one TPU chip.

Default (driver contract): runs the flagship BERT-base MLM config and
prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}. It
refuses any device that is not a TPU (exit code 2): the number is
divided by the v5e peak, and a CPU rate under that name would be a lie.

`python bench.py --all` additionally measures LeNet-MNIST images/sec,
ResNet-50 images/sec + MFU (the BASELINE.json north star), GravesLSTM
char-RNN tokens/sec, Word2Vec SkipGram words/sec and the serving-latency
smoke, MERGING all results into BENCH_ALL.json (one JSON object per
config). `--only name[,name]` re-records a subset (off-TPU runs land
under platform-suffixed keys and never displace chip rows); `--words N`
sizes the Word2Vec corpus. A bench that raises is recorded as
{"error": ...} and the run then exits 1.

Baseline note (BASELINE.md): the reference publishes no in-tree numbers
(`published: {}`), so vs_baseline is reported against BASELINE.json's
north-star target of 40% MFU where MFU is defined (BERT, ResNet-50):
vs_baseline = measured_MFU / 0.40; >1.0 beats the target. Configs whose
baseline rows have no target metric report vs_baseline = null.
Peak bf16 throughput per TPU v5e chip: 197 TFLOP/s.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

V5E_PEAK_BF16 = 197e12
MFU_TARGET = 0.40


def bert_train_flops_per_step(cfg, batch, seq, n_masked):
    """fwd+bwd ~= 3x fwd. Per token, each layer's matmuls cost
    2*h*3h (QKV) + 2*h*h (attn out) + 2*2*h*f (FFN pair); attention
    adds 2*2*T*h per token (QK^T and PV). The tied LM head scores ONLY
    the n_masked masked positions per example (standard BERT pretraining
    head; the model gathers before the vocab matmul, so counting full-
    sequence head FLOPs would inflate MFU)."""
    h, f, L, v = cfg.hidden, cfg.ffn, cfg.num_layers, cfg.vocab_size
    tokens = batch * seq
    fwd = tokens * L * (2 * h * 3 * h + 2 * h * h + 4 * h * f)
    fwd += tokens * L * (4 * seq * h)
    fwd += batch * n_masked * 2 * h * v
    return 3 * fwd


# keep the old name importable
train_flops_per_step = bert_train_flops_per_step


class NotATpu(RuntimeError):
    """The flagship bench was asked to run on a device that is not a
    TPU."""


def bench_bert():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise NotATpu(
            f"bench_bert measures tokens/s and MFU against the TPU v5e "
            f"peak; jax resolved to {dev.platform!r} "
            f"({dev.device_kind!r}). Run it on the chip.")

    from deeplearning4j_tpu.models.bert import (
        BertConfig, BertTrainer, synthetic_mlm_batch)
    from deeplearning4j_tpu.parallel.mesh import MeshConfig

    cfg = BertConfig(vocab_size=30522, hidden=768, num_layers=12,
                     num_heads=12, ffn=3072, max_len=512)
    batch, seq = 16, 512
    mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
    trainer = BertTrainer(cfg, mesh, lr=1e-4)

    # K optimizer steps per launch (lax.scan), best of several trials.
    # k and the batch size were chosen in July 2026 on another
    # installation; they have not been re-swept on this one (PERF.md)
    k = 20
    stacks = [synthetic_mlm_batch(cfg, batch, seq, seed=s)
              for s in range(k)]
    tokens_k = np.stack([s[0] for s in stacks])
    labels_k = np.stack([s[1] for s in stacks])

    def best(repeats):
        float(trainer.train_steps(tokens_k, labels_k,
                                  repeats=repeats)[-1])  # compile
        b = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(trainer.train_steps(tokens_k, labels_k,
                                      repeats=repeats)[-1])
            b = min(b, time.perf_counter() - t0)
        return b

    # slope between 1-pass and 3-pass launches over the same K batches:
    # cancels any fixed per-launch cost
    t1 = best(1)
    t2 = best(3)
    dt = (t2 - t1) / (2 * k)

    tokens_per_sec = batch * seq / dt
    mfu = bert_train_flops_per_step(
        cfg, batch, seq, trainer._max_preds(seq)) / dt / V5E_PEAK_BF16
    return {
        "metric": "bert_base_mlm_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / MFU_TARGET, 3),
        "mfu": round(mfu, 4),
    }


def _fit_throughput(net, batches, epochs_warm=2, epochs_meas=4):
    """Steady-state fit() throughput in examples/sec (includes the host
    loop, i.e. what a user's training run actually sees)."""
    net.fit(batches, epochs_warm)   # compile + warm
    n_examples = sum(np.asarray(b[0]).shape[0] for b in batches)
    t0 = time.perf_counter()
    net.fit(batches, epochs_meas)
    # fit syncs per-listener only; force one final device read
    float(net.score((np.asarray(batches[0][0]), np.asarray(batches[0][1]))))
    dt = time.perf_counter() - t0
    return n_examples * epochs_meas / dt


def _scan_throughput(net, X_k, y_k, trials=3, repeats_long=5):
    """Steady-state step throughput in examples/sec via fitMultiBatch,
    SLOPE-timed: per-step time is the slope between a 1-pass and an
    R-pass launch over the same K stacked batches, which cancels any
    fixed per-launch cost (dividing one launch's wall time by K leaves
    that cost, over K, inside every number)."""
    import jax

    k = X_k.shape[0]
    n_examples = k * X_k.shape[1]
    X_k = jax.device_put(jax.numpy.asarray(X_k))
    y_k = jax.device_put(jax.numpy.asarray(y_k))

    def best(repeats):
        float(net.fitMultiBatch(X_k, y_k, repeats=repeats)[-1])  # compile
        dt = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            float(net.fitMultiBatch(X_k, y_k, repeats=repeats)[-1])
            dt = min(dt, time.perf_counter() - t0)
        return dt

    t1 = best(1)
    # grow the long span until the extra device work clears ~0.1 s of
    # launch-to-launch jitter, else the slope of a sub-ms-step config
    # drowns in noise
    repeats = repeats_long
    while True:
        t2 = best(repeats)
        if t2 - t1 > 0.6 or repeats >= 625:
            break
        repeats *= 5
    per_pass = (t2 - t1) / (repeats - 1)
    return n_examples / per_pass


def bench_lenet():
    from deeplearning4j_tpu.models.zoo import LeNet

    net = LeNet().init()
    rng = np.random.default_rng(0)
    bsz, nb = 512, 8
    X_k = rng.normal(size=(nb, bsz, 1, 28, 28)).astype(np.float32)
    y_k = np.stack([np.eye(10, dtype=np.float32)[
        rng.integers(0, 10, bsz)] for _ in range(nb)])
    ips = _scan_throughput(net, X_k, y_k)
    return {
        "metric": "lenet_mnist_images_per_sec",
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": None,  # BASELINE row 1: functional parity only
    }


def resnet50_train_flops(batch):
    """ResNet-50 fwd = 4.1 GMACs per 224x224 image = 8.2e9 FLOP in the
    2*MAC convention that XLA's cost model and the 197 TFLOP/s v5e peak
    both use; train ~= 3x fwd. (PR-10 cost-model audit: the old 4.1e9
    counted multiply-accumulates as single FLOPs against a peak quoted
    in real FLOP/s — a 2x MFU understatement. cost_analysis() of this
    repo's ResNet50 train step measures 2.25e10 at batch 1, within 10%
    of 3*8.2e9; chip rows recorded before PR 10 carry the old
    convention until re-measured.)"""
    return 3 * 8.2e9 * batch


def bench_resnet50():
    from deeplearning4j_tpu.models.zoo import ResNet50

    import jax.numpy as jnp

    # bfloat16: the TPU-idiomatic training dtype (reference analog:
    # dataType(DataType.HALF)); batch 256 saturates the chip. BN is
    # one-pass f32-accumulated. r4 analysis (tools/RESNET_MFU.md,
    # slope-timed): mid/late bottleneck blocks run at 52-96% of peak
    # under XLA — the ~16-17% model MFU concentrates in the early
    # stages (f=64/128 leaves the 128x128 MXU half-fed; BN stat passes
    # double the s0 forward) and the composed backward. A hand-written
    # Pallas fused bottleneck kernel measured SLOWER than XLA at every
    # stage shape (tools/probe_fused_block.py), and remat / layout /
    # s2d-stem / bf16-stat levers all measured dead, so this row is
    # shape-limited, not scheduling-limited.
    net = ResNet50(numClasses=1000, dataType="bfloat16").init()
    rng = np.random.default_rng(0)
    bsz, k = 256, 16
    X_k = rng.normal(size=(k, bsz, 3, 224, 224)).astype(np.float32)
    y_k = np.stack([np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, bsz)] for _ in range(k)])
    X_k = jnp.asarray(X_k, jnp.bfloat16)
    y_k = jnp.asarray(y_k, jnp.bfloat16)
    ips = _scan_throughput(net, X_k, y_k, trials=3)
    mfu = resnet50_train_flops(1) * ips / V5E_PEAK_BF16
    return {
        "metric": "resnet50_imagenet_images_per_sec_per_chip",
        "value": round(ips, 1),
        "unit": "images/sec",
        "dataType": "bfloat16",
        "vs_baseline": round(mfu / MFU_TARGET, 3),
        "mfu": round(mfu, 4),
    }


def bench_resnet_etl():
    """With-input-pipeline companion to the peak resnet50 number (VERDICT
    round-2 weak item 5): how fast the parallel image ETL
    (datasets/parallel_etl.py) can produce 224x224 training batches from
    disk on THIS host. Reported next to the synthetic-tensor peak; on a
    multi-core host the worker pool scales decode linearly, this
    environment has a single usable core."""
    import os
    import tempfile
    import time as _t

    from PIL import Image

    from deeplearning4j_tpu.datasets import (
        FileSplit, ParallelImageDataSetIterator)

    root = tempfile.mkdtemp(prefix="bench_etl_")
    rng = np.random.default_rng(0)
    n = 512
    for cls in ("a", "b"):
        d = os.path.join(root, cls)
        os.makedirs(d)
        for i in range(n // 2):
            arr = rng.integers(0, 255, (224, 224, 3), np.uint8)
            Image.fromarray(arr, "RGB").save(
                os.path.join(d, f"{i}.jpg"), quality=85)
    workers = max(1, os.cpu_count() or 1)
    it = ParallelImageDataSetIterator(
        FileSplit(root), 224, 224, 3, batchSize=64, numWorkers=workers)
    # time the FULL epoch including worker startup: with a parallel pool
    # most decode overlaps the first next(), so excluding it would
    # measure queue drain, not sustained ETL rate
    t0 = _t.perf_counter()
    count = 0
    while it.hasNext():
        count += np.asarray(it.next().getFeatures()).shape[0]
    dt = _t.perf_counter() - t0
    import shutil

    shutil.rmtree(root, ignore_errors=True)
    return {
        "metric": "resnet50_image_etl_img_per_sec",
        "value": round(count / dt, 1),
        "unit": "images/sec",
        "vs_baseline": None,
        "host_workers": workers,
        "note": ("host-side decode+augment rate feeding the chip; "
                 "scales with host cores (this host has "
                 f"{os.cpu_count()})"),
    }


def bench_etl(n_images=256, side=224):
    """Streaming-ETL engine scaling curve (ISSUE 6 acceptance): img/s of
    the persistent-pool + shm-ring pipeline at 1/2/4/8 workers on a
    synthetic 224x224 JPEG tree, against the legacy single-worker
    equivalent path (per-image full bilinear resize to float32 + a
    pickled-float32 IPC roundtrip per batch — the cost model of the
    pre-ISSUE-6 iterator that recorded 210.9 img/s), plus the trainer
    etl-wait fraction at MNIST scale with and without the
    DevicePrefetcher."""
    import os
    import pickle
    import shutil
    import tempfile
    import time as _t

    from PIL import Image

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.datasets import (
        FileSplit, ParallelImageDataSetIterator, set_default_depth)
    from deeplearning4j_tpu.datasets.image import (
        NativeImageLoader, _bilinear_resize_chw)

    root = tempfile.mkdtemp(prefix="bench_etl_")
    rng = np.random.default_rng(0)
    for cls in ("a", "b"):
        d = os.path.join(root, cls)
        os.makedirs(d)
        for i in range(n_images // 2):
            arr = rng.integers(0, 255, (side, side, 3), np.uint8)
            Image.fromarray(arr, "RGB").save(
                os.path.join(d, f"{i}.jpg"), quality=85)
    files = sorted(os.path.join(root, c, f)
                   for c in ("a", "b")
                   for f in os.listdir(os.path.join(root, c)))
    batch = 64

    # -- legacy equivalent: the pre-rebuild per-image pipeline ---------------
    loader = NativeImageLoader(side, side, 3)
    t0 = _t.perf_counter()
    for lo in range(0, n_images, batch):
        feats = []
        for p in files[lo:lo + batch]:
            hwc = loader._decode_hwc(p)
            feats.append(_bilinear_resize_chw(hwc, side, side))
        arr = np.stack(feats).astype(np.float32)
        arr = pickle.loads(pickle.dumps(arr))  # the mp.Queue byte cost
    legacy = n_images / (_t.perf_counter() - t0)

    # -- the new engine: serial baseline + 1/2/4/8-worker pool curve ---------
    def epoch_rate(**kw):
        it = ParallelImageDataSetIterator(
            FileSplit(root), side, side, 3, batchSize=batch, **kw)
        # warm epoch: pool fork + page cache; the persistent pool makes
        # epoch 2+ the steady state an epoch-boundary refork would hide
        for _ in it:
            pass
        best = 0.0
        for _ in range(2):   # best-of-2: the shared CI host is noisy
            it.reset()
            t0 = _t.perf_counter()
            count = 0
            for ds in it:
                count += np.asarray(ds.getFeatures()).shape[0]
            best = max(best, count / (_t.perf_counter() - t0))
        it.close()
        return round(best, 1)

    serial = epoch_rate(transport="serial")
    # uint8 output = the streaming configuration (decode stays uint8 end
    # to end, normalize happens on device via DevicePrefetcher)
    curve = {w: epoch_rate(numWorkers=w, transport="shm",
                           floatOutput=False)
             for w in (1, 2, 4, 8)}
    float_out_8 = epoch_rate(numWorkers=8, transport="shm")
    shutil.rmtree(root, ignore_errors=True)

    # -- trainer etl-wait fraction at MNIST scale ----------------------------
    # blocking = the trainer eats split+pad+mask+transfer at every
    # next(); prefetch = the DevicePrefetcher does that in its producer
    # thread and the trainer pops a staged device batch. (On a CPU
    # backend the jitted step itself saturates the host cores, so a
    # decode-heavy input pipeline cannot truly overlap — the img/s curve
    # above carries that contention; this measurement isolates the
    # prefetcher's steady-state wait at MNIST scale, where input prep is
    # cheaper than the step, i.e. the regime a fed chip runs in.)
    from deeplearning4j_tpu.datasets import MnistDataSetIterator
    from deeplearning4j_tpu.nn import (
        DenseLayer, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    def wait_fraction(depth):
        telemetry.get_registry().reset()
        set_default_depth(depth)
        try:
            conf = (NeuralNetConfiguration.Builder().seed(0)
                    .updater(Adam(1e-3)).list()
                    .layer(DenseLayer.Builder(nOut=256,
                                              activation="relu").build())
                    .layer(DenseLayer.Builder(nOut=256,
                                              activation="relu").build())
                    .layer(OutputLayer.Builder().nOut(10)
                           .activation("softmax").build())
                    .setInputType(InputType.feedForward(784))
                    .build())
            net = MultiLayerNetwork(conf)
            net.init()
            it = MnistDataSetIterator(128, num_examples=2048)
            net.fit(it, 3)
            reg = telemetry.get_registry()
            etl = reg.histogram("dl4j_etl_wait_seconds",
                                labelnames=("loop",)).labels(loop="fit")
            step = reg.histogram("dl4j_step_seconds",
                                 labelnames=("loop",)).labels(loop="fit")
            return etl.sum / max(step.sum, 1e-9)
        finally:
            set_default_depth(2)
            telemetry.get_registry().reset()

    blocking_frac = wait_fraction(0)
    prefetch_frac = wait_fraction(2)

    w8 = curve[8]
    return {
        "metric": "etl_img_per_sec_8_workers",
        "value": w8,
        "unit": "images/sec",
        "vs_baseline": None,
        "img_per_sec_by_workers": curve,
        "img_per_sec_serial": serial,
        "img_per_sec_8_workers_float_out": float_out_8,
        "legacy_single_worker_img_per_sec": round(legacy, 1),
        "speedup_vs_legacy_at_8_workers": round(w8 / legacy, 2),
        "etl_wait_fraction_blocking": round(blocking_frac, 4),
        "etl_wait_fraction_prefetch": round(prefetch_frac, 4),
        "host_cores": os.cpu_count(),
        "note": (f"{n_images} synthetic {side}x{side} JPEGs, batch "
                 f"{batch}; steady-state epoch (persistent pool, warm "
                 "page cache); curve is the uint8-to-device "
                 "configuration over the shm ring; legacy = pre-ISSUE-6 "
                 "path (full bilinear resize to f32 + pickled-f32 IPC) "
                 "at 1 worker; wait fractions are "
                 "sum(dl4j_etl_wait)/sum(dl4j_step) for a 784-256-256-10 "
                 "MLP on MNIST, batch 128, DevicePrefetcher off/on; "
                 "worker counts above host_cores oversubscribe, and on "
                 "the CPU backend the step itself occupies the cores "
                 "the decode workers need"),
    }


def bench_graves_lstm():
    """Char-RNN throughput + fraction-of-peak, slope-timed (two launch
    lengths, the fixed cost cancels). The >=4*T sequential recurrence
    chain bounds the gap to peak: each optimizer step serializes 4*T
    dependent iterations whose per-step matmul is latency- not
    throughput-sized."""
    from deeplearning4j_tpu.models.zoo import TextGenerationLSTM

    vocab, seq, bsz = 77, 100, 1024
    net = TextGenerationLSTM(vocabSize=vocab, hidden=256,
                             seqLength=seq).init()
    rng = np.random.default_rng(0)
    k = 8
    ids = rng.integers(0, vocab, (k, bsz, seq + 1))
    X_k = np.stack([np.eye(vocab, dtype=np.float32)[ids[i, :, :-1]]
                    .transpose(0, 2, 1) for i in range(k)])
    y_k = np.stack([np.eye(vocab, dtype=np.float32)[ids[i, :, 1:]]
                    .transpose(0, 2, 1) for i in range(k)])
    eps = _scan_throughput(net, X_k, y_k)
    toks = eps * seq
    h = 256
    fwd_flops = 8 * h * (vocab + h) + 8 * h * (h + h) + 2 * h * vocab
    mfu = toks * 3 * fwd_flops / V5E_PEAK_BF16
    return {
        "metric": "graves_lstm_char_rnn_tokens_per_sec",
        "value": round(toks, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,  # BASELINE row 3: reference unpublished
        "batch": bsz,
        "mfu": round(mfu, 5),
        "bound": ("sequential recurrence (>=4*T dependent scan steps "
                  "per optimizer step; slope-timed, launch RTT "
                  "excluded)"),
    }


def bench_word2vec(total_words=10_000_000):
    """Steady-state SGNS words/s on a >=10M-word zipf corpus (VERDICT
    round-2 item 5: the old 150k-word number measured warm-up, and
    mainstream CPU implementations reach hundreds of k words/s — the
    TPU path must be measured at scale). One warm epoch builds the token
    cache + compiles the scan; the timed epoch covers the full per-epoch
    pipeline: vectorized subsampling -> native pair-gen -> one-launch
    scan with on-device negative draws."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    rng = np.random.default_rng(0)
    vocab, sent_len = 100_000, 25
    n_sent = total_words // sent_len
    zipf = 1.0 / np.arange(1, vocab + 1) ** 1.05
    p = zipf / zipf.sum()
    flat = rng.choice(vocab, n_sent * sent_len, p=p)
    names = np.char.add("w", flat.astype("U7"))
    sents = [" ".join(row) for row in
             names.reshape(n_sent, sent_len)]
    w2v = (Word2Vec.Builder().minWordFrequency(1).layerSize(128)
           .windowSize(5).negativeSample(5).batchSize(8192)
           .epochs(1).seed(1).iterate(sents).build())
    w2v.buildVocab()
    # two warm epochs: token cache + compile, AND stabilize the k-bucket
    # (a subsampling-jitter bucket bump would recompile inside the timed
    # epoch and corrupt the measurement)
    w2v.fit()
    w2v.fit()
    _ = np.asarray(w2v.syn0).sum()
    t0 = time.perf_counter()
    w2v.fit()   # steady-state epoch
    _ = np.asarray(w2v.syn0).sum()  # sync
    dt = time.perf_counter() - t0
    wps = total_words / dt
    # Primitive roofline (r4, slope-timed: tools/probe_scatter.py):
    # sorted row scatter sustains ~125M rows/s; each pair moves
    # ~2*(2+k_neg) rows (gather + scatter across both tables), ~3.8
    # pairs/word after subsampling at window 5. r5 correction: at the
    # production batch width the scatter phase already RUNS at that
    # roofline (0.32 ms for 57k rows/step) — the binding bound is the
    # step's gather/einsum math floor plus scan overhead, not the
    # scatter (tools/probe_w2v_step.py E vs A variants).
    k_neg, pairs_per_word = 5, 3.8
    rows_per_word = pairs_per_word * 2 * (2 + k_neg)
    roof_wps = 125e6 / rows_per_word
    import jax

    if jax.default_backend() != "tpu":
        # the bound analysis below describes the chip; an off-TPU row
        # (bench.py --only word2vec on this host) must not carry it
        return {
            "metric": "word2vec_skipgram_words_per_sec",
            "value": round(wps, 1),
            "unit": "words/sec",
            "vs_baseline": None,
            "corpus_words": total_words,
            "bound": (f"{jax.default_backend()} fallback run (XLA host "
                      "scan); the TPU roofline analysis applies only on "
                      "the chip"),
        }
    return {
        "metric": "word2vec_skipgram_words_per_sec",
        "value": round(wps, 1),
        "unit": "words/sec",
        "vs_baseline": None,  # BASELINE row 5: reference unpublished
        "corpus_words": total_words,
        "scatter_roofline_words_per_sec": round(roof_wps, 1),
        "frac_of_roofline": round(wps / roof_wps, 4),
        "bound": ("r5 epoch = ~2.0s fully-device ETL (subsample + "
                  "slice-shift windows + compaction; was 4.4s device + "
                  "~3.5s host in r4) + ~7.4s training scan at 1.57 "
                  "ms/step (pooled negatives; per-step floor: 0.49 ms "
                  "gather/einsum math + 0.32 ms sort+scatter, scatter "
                  "AT its 125M rows/s roofline). Probes: "
                  "tools/probe_w2v_step.py (batch sweep peaks at 8192; "
                  "segment-sum dedup, unsorted scatter, bulk-draw "
                  "hoist, scan unroll all measured slower), "
                  "tools/probe_w2v_pairgen.py (scalar gathers 0.19 "
                  "GB/s -> slice-shifts; searchsorted and row-scatter "
                  "compaction 4-10x slower). Host numpy reference on "
                  "this 1-core host: ~24k words/s."),
    }


def bench_serving_latency(n_requests=300):
    """ISSUE 2 serving smoke: p50/p99 sync predict latency through the
    DynamicBatcher on a warmed AOT bucket ladder, at batch 1 and batch
    32. Single-client, so batch-1 latency INCLUDES the max-latency flush
    window (1 ms here) the batcher holds open for co-travelers — that
    window is the price of coalescing and belongs in the number."""
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import BucketLadder, InferenceSession

    conf = (NeuralNetConfiguration.Builder().seed(7).list()
            .layer(DenseLayer.Builder().nIn(128).nOut(256)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(10).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    net = MultiLayerNetwork(conf).init()
    session = InferenceSession(max_latency=0.001)
    session.register("bench", net, example_shape=(128,),
                     ladder=BucketLadder((1, 8, 32)), warmup=True)
    rng = np.random.default_rng(0)
    x1 = rng.normal(size=(128,)).astype(np.float32)
    x32 = rng.normal(size=(32, 128)).astype(np.float32)

    def percentiles(x, n):
        for _ in range(10):         # settle the queue/thread path
            session.predict("bench", x)
        lat = np.empty(n)
        for i in range(n):
            t0 = time.perf_counter()
            session.predict("bench", x)
            lat[i] = time.perf_counter() - t0
        return np.percentile(lat * 1e3, [50, 99])

    p50_1, p99_1 = percentiles(x1, n_requests)
    p50_32, p99_32 = percentiles(x32, max(50, n_requests // 4))
    session.close()
    return {
        "metric": "serving_latency_p50_ms_batch1",
        "value": round(float(p50_1), 3),
        "unit": "ms",
        "vs_baseline": None,
        "p99_batch1_ms": round(float(p99_1), 3),
        "p50_batch32_ms": round(float(p50_32), 3),
        "p99_batch32_ms": round(float(p99_32), 3),
        "requests": n_requests,
        "note": ("single-client sync predict through DynamicBatcher on a "
                 "warmed (1,8,32) AOT ladder; batch-1 includes the 1 ms "
                 "coalescing flush window"),
    }


def _host_bound() -> bool:
    """True off-chip: the row's value reflects host capacity (cores,
    scheduler, dispatch overhead), not the model math — benchdiff
    skips regression-gating host-bound rows on non-chip platforms
    (ISSUE 13 satellite; the ROADMAP 'meaningless off-chip' debt)."""
    import jax

    return jax.default_backend() != "tpu"


def bench_serving_load(duration=2.0, deadline_ms=30.0,
                       rows_per_request=16):
    """ISSUE 8: open-loop load generator for the multi-replica serving
    path. Poisson arrivals at fixed offered QPS (requests of
    `rows_per_request` examples), swept geometrically from light load
    to saturation, for three configs on the same MLP: the single-
    batcher path, a 4-replica work-stealing ReplicaSet (one replica
    per CPU mesh device), and int8-PTQ replicas. Every request carries
    a `deadline_ms` timeout, so "saturation throughput" is the max
    completed-rows/s AT THAT DEADLINE — late answers don't count.

    A fourth phase drives the replica config at ~2x its saturation
    with admission control on and a 15/85 high/batch priority mix:
    production overload should shed the best-effort tail (429 +
    Retry-After) while high-priority p99 holds near its unloaded
    value.

    Open loop matters: a closed-loop client backs off exactly when the
    server struggles, hiding the queueing collapse this bench exists
    to measure (the coordinated-omission trap)."""
    import threading
    from collections import Counter as _Counter

    import jax

    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.precision import quantize
    from deeplearning4j_tpu.serving import (
        AdmissionController, BucketLadder, InferenceSession,
        QueueFullError, ServingTimeout, ShedError)

    n_dev = len(jax.devices())
    deadline_s = deadline_ms / 1e3
    ladder = BucketLadder((rows_per_request, 2 * rows_per_request,
                           4 * rows_per_request))
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows_per_request, 128)).astype(np.float32)

    def build_net(seed=7, layers=16, width=192):
        # deep-narrow on purpose: per-op matmuls too small for XLA CPU
        # to split across cores, so one dispatch occupies ~one core —
        # the honest CPU stand-in for one-replica-per-chip (a TPU
        # executable can't borrow a neighbor chip's ALUs either). Wide
        # nets let the SINGLE path grab every core per dispatch and
        # measure nothing but this container's 2-core ceiling.
        b = (NeuralNetConfiguration.Builder().seed(seed).list()
             .layer(DenseLayer.Builder().nIn(128).nOut(width)
                    .activation("relu").build()))
        for _ in range(layers - 1):
            b = b.layer(DenseLayer.Builder().nOut(width)
                        .activation("relu").build())
        conf = (b.layer(OutputLayer.Builder().nOut(10)
                        .activation("softmax")
                        .lossFunction(LossFunction.MCXENT).build())
                .build())
        return MultiLayerNetwork(conf).init()

    net = build_net()

    def open_loop(session, qps, mix=None, run_s=None):
        """One offered-load point. mix: {priority: fraction} (None =
        all normal). Returns completion stats."""
        run_s = duration if run_s is None else run_s
        lats = {"high": [], "normal": [], "batch": []}
        outcomes = _Counter()
        pending = []
        lock = threading.Lock()
        arr = np.random.default_rng(1234)
        prios, cum = (["normal"], [1.0]) if mix is None else (
            list(mix), list(np.cumsum([mix[p] for p in mix])))
        start = time.perf_counter()
        t_next = start
        while t_next < start + run_s:
            now = time.perf_counter()
            if t_next > now:
                time.sleep(t_next - now)
            u = arr.random()
            prio = prios[int(np.searchsorted(cum, u))] \
                if len(prios) > 1 else prios[0]
            t0 = time.perf_counter()
            try:
                f = session.predict_async("m", X, timeout=deadline_s,
                                          priority=prio)

                def cb(fut, t0=t0, prio=prio):
                    err = fut.exception()
                    with lock:
                        if err is None:
                            lats[prio].append(time.perf_counter() - t0)
                            outcomes["ok"] += 1
                        elif isinstance(err, (ServingTimeout,
                                              TimeoutError)):
                            outcomes["timeout"] += 1
                        else:
                            outcomes["error"] += 1

                f.add_done_callback(cb)
                pending.append(f)
            except ShedError:
                outcomes[f"shed_{prio}"] += 1
            except QueueFullError:
                outcomes["rejected"] += 1
            outcomes["offered"] += 1
            t_next += arr.exponential(1.0 / qps)
        # drain stragglers: every future resolves by its deadline (the
        # batcher fails late ones with timeout_queued when it reaches
        # them), so one deadline past the window covers the tail
        t_stop = time.perf_counter() + deadline_s + 0.3
        while time.perf_counter() < t_stop and \
                any(not f.done() for f in pending[-64:]):
            time.sleep(0.01)
        wall = time.perf_counter() - start
        all_lats = [v for p in lats.values() for v in p]

        def pct(vals, q):
            return (round(float(np.percentile(np.asarray(vals) * 1e3,
                                              q)), 2)
                    if vals else None)

        return {
            "offered_qps": round(qps, 1),
            "completed_rows_per_s": round(
                outcomes["ok"] * rows_per_request / wall, 1),
            "p50_ms": pct(all_lats, 50), "p99_ms": pct(all_lats, 99),
            "p99_high_ms": pct(lats["high"], 99),
            "p99_batch_ms": pct(lats["batch"], 99),
            "outcomes": dict(outcomes),
            "shed_rate": round(
                sum(v for k, v in outcomes.items()
                    if k.startswith("shed_") or k == "rejected")
                / max(outcomes["offered"], 1), 4),
        }

    def sweep(session):
        points, best, flat = [], 0.0, 0
        qps = 25.0
        while qps <= 3200 and flat < 2:
            p = open_loop(session, qps)
            points.append(p)
            thr = p["completed_rows_per_s"]
            if thr > best * 1.08:
                best, flat = max(best, thr), 0
            else:
                flat += 1
            qps *= 1.8
        return points, round(best, 1)

    results, sat = {}, {}
    configs = [
        ("single", dict(), net),
        (f"replicas{n_dev}", dict(replicas=n_dev), net),
        (f"replicas{n_dev}_int8", dict(replicas=n_dev),
         quantize(net, [(X, None)], example_shape=(128,))),
    ]
    for label, reg_kw, model in configs:
        session = InferenceSession(max_latency=0.001, queue_size=256)
        session.register("m", model, example_shape=(128,),
                         ladder=ladder, warmup=True, **reg_kw)
        open_loop(session, 50, run_s=0.5)          # settle threads
        # this container's throughput swings ±40% run to run (see the
        # word2vec/etl bench notes): sweep twice, merge per-point by
        # best completed rate, report best-of-both saturation
        merged = {}
        best = 0.0
        for _ in range(2):
            points, peak = sweep(session)
            best = max(best, peak)
            for p in points:
                q = p["offered_qps"]
                if q not in merged or p["completed_rows_per_s"] > \
                        merged[q]["completed_rows_per_s"]:
                    merged[q] = p
        results[label] = [merged[q] for q in sorted(merged)]
        sat[label] = round(best, 1)
        session.close()

    # -- overload: 2x saturation, high vs best-effort under admission --
    repl = f"replicas{n_dev}"
    # budget sized for the SLO: 8 standing requests against ~10k+
    # rows/s of replica capacity keeps worst-case queueing around
    # 10-20 ms — the high class must never wait behind a deep
    # best-effort backlog (batch capped at 50% of even that)
    session = InferenceSession(
        max_latency=0.001, queue_size=256,
        admission=AdmissionController(default_budget=8))
    session.register("m", net, example_shape=(128,), ladder=ladder,
                     warmup=True, replicas=n_dev)
    open_loop(session, 50, run_s=0.5)
    sat_qps = sat[repl] / rows_per_request
    unloaded = open_loop(session, max(10.0, 0.15 * sat_qps),
                         mix={"high": 1.0})
    overload = open_loop(session, 2.0 * sat_qps,
                         mix={"high": 0.15, "batch": 0.85},
                         run_s=2 * duration)
    session.close()
    hi_ratio = (overload["p99_high_ms"] / unloaded["p99_high_ms"]
                if overload["p99_high_ms"] and unloaded["p99_high_ms"]
                else None)
    shed_batch = sum(v for k, v in overload["outcomes"].items()
                     if k == "shed_batch")
    ratio = round(sat[repl] / max(sat["single"], 1e-9), 2)
    return {
        "metric": "serving_load_saturation_ratio",
        "value": ratio,
        "unit": f"x single-batcher rows/s at {deadline_ms:.0f}ms deadline",
        "vs_baseline": None,
        "host_bound": _host_bound(),
        "saturation_rows_per_s": sat,
        "sweep": results,
        "overload": {
            "unloaded_high": unloaded, "at_2x": overload,
            "high_p99_ratio": (round(hi_ratio, 2)
                               if hi_ratio is not None else None),
            "batch_sheds": int(shed_batch),
        },
        "devices": n_dev,
        "host_cores": __import__("os").cpu_count(),
        "rows_per_request": rows_per_request,
        "note": (f"open-loop Poisson, {rows_per_request}-row requests, "
                 f"{deadline_ms:.0f}ms request deadline; saturation = "
                 "max completed rows/s meeting the deadline (best of 2 "
                 "sweeps; this host swings +-40% run to run). CAVEAT: "
                 "this container has 2 cores under the 4-device mesh "
                 "(2:1 oversubscribed) and a lone XLA CPU dispatch "
                 "already uses both cores, so measured concurrent-exec "
                 "headroom is only 1.2-1.9x (probed) and overload p99 "
                 "tails are OS-scheduler noise — the >=2.5x acceptance "
                 "ratio and the 1.5x high-p99 bound need >=1 core (or "
                 "chip) per replica; re-record on chip "
                 "(`python bench.py --only serving_load`)"),
    }


def bench_decode(prompt_len=256, max_new=32, n_requests=6):
    """ISSUE 12: open-loop decode bench over the v2 engine arms —
    plain (PR-8 per-token prefill), chunked prefill, prefix-cache hit,
    and speculative decoding — recording tokens/s and TTFT p50/p99
    per arm plus the boundary counts that explain them. One tiny
    transformer pair (draft = half-width) so the row measures the
    ENGINE (boundary bookkeeping, dispatch count, adoption), not the
    model. benchdiff direction: the headline value is tokens/s
    (higher is better); the per-arm ttft_*_ms details are
    informational."""
    from deeplearning4j_tpu.serving import (
        DecodeEngine, SpeculativeConfig, TransformerDecodeModel)

    def mk(hidden=64, n_layers=2, seed=5):
        return TransformerDecodeModel.init(
            vocab=256, hidden=hidden, n_layers=n_layers, n_heads=2,
            max_len=prompt_len + max_new + 64, max_slots=4, page=32,
            max_pages_per_slot=(prompt_len + max_new + 63) // 32 + 1,
            seed=seed)

    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, 256, size=prompt_len))
    prompts = [shared + list(rng.integers(0, 256, size=4 + i))
               for i in range(n_requests)]

    def run_arm(engine, reuse_prefix=False):
        # sequential requests: TTFT is the number this bench exists
        # to move, and queueing other requests would pollute it
        if reuse_prefix:
            # seed the prefix cache OUTSIDE the timed window — its
            # tokens don't count, so its wall time must not either
            engine.decode(prompts[0], max_new, timeout=600.0)
        ttfts, boundaries = [], []
        t0 = time.perf_counter()
        n_tokens = 0
        for prompt in prompts:
            req = engine.submit(prompt, max_new)
            t_sub = time.perf_counter()
            stream = req.tokens(timeout=600.0)
            next(stream)
            ttfts.append(time.perf_counter() - t_sub)
            n_tokens += 1 + sum(1 for _ in stream)
            boundaries.append(req.ttft_boundaries)
        wall = time.perf_counter() - t0
        engine.close()
        lat = np.asarray(ttfts) * 1e3
        return {
            "tokens_per_s": round(n_tokens / wall, 1),
            "ttft_p50_ms": round(float(np.percentile(lat, 50)), 2),
            "ttft_p99_ms": round(float(np.percentile(lat, 99)), 2),
            "ttft_boundaries_p50": int(np.median(boundaries)),
        }

    arms = {}
    arms["plain"] = run_arm(DecodeEngine(mk(), name="b-plain").warmup())
    arms["chunked"] = run_arm(
        DecodeEngine(mk(), name="b-chunk", chunk=64).warmup())
    arms["prefix_hit"] = run_arm(
        DecodeEngine(mk(), name="b-prefix", chunk=64,
                     prefix_cache=True).warmup(),
        reuse_prefix=True)
    draft = TransformerDecodeModel.init(
        vocab=256, hidden=32, n_layers=1, n_heads=2,
        max_len=prompt_len + max_new + 64, max_slots=4, page=32,
        max_pages_per_slot=(prompt_len + max_new + 63) // 32 + 1,
        seed=5)
    arms["speculative"] = run_arm(
        DecodeEngine(mk(), name="b-spec", chunk=64, prefix_cache=True,
                     speculative=SpeculativeConfig(draft=draft, k=4))
        .warmup(), reuse_prefix=True)
    return {
        "metric": "decode_tokens_per_s",
        "value": arms["plain"]["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": None,
        "host_bound": _host_bound(),
        "arms": arms,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "note": (f"v2 decode arms on a tiny {prompt_len}-token-prompt "
                 "transformer pair; headline value = plain-arm "
                 "tokens/s (benchdiff: higher is better; ttft_*_ms "
                 "and boundary counts are informational — chunked/"
                 "prefix/speculative arms should dominate plain on "
                 "TTFT boundaries everywhere). CAVEAT: CPU row is "
                 "host-bound (dispatch overhead ~ kernel time at "
                 "this model size) — re-record on chip "
                 "(`python bench.py --only decode`)"),
    }


def bench_health_overhead(steps=80, repeats=3):
    """ISSUE 3 smoke: per-step cost of the in-step health stats + host
    publication. Three modes on the SAME architecture (fresh net each,
    jit warmed outside the timed region): health on (telemetry enabled),
    health off (`telemetry.health.configure(enabled=False)` — the stats
    are compiled out of the step), telemetry disabled entirely.
    Acceptance: on-vs-off overhead <= 10%."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.telemetry import health

    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 256)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 128)]

    def build():
        conf = (NeuralNetConfiguration.Builder().seed(11)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer.Builder().nIn(256).nOut(256)
                       .activation("relu").build())
                .layer(DenseLayer.Builder().nOut(256)
                       .activation("relu").build())
                .layer(DenseLayer.Builder().nOut(256)
                       .activation("relu").build())
                .layer(OutputLayer.Builder().nOut(10)
                       .activation("softmax")
                       .lossFunction(LossFunction.MCXENT).build())
                .build())
        return MultiLayerNetwork(conf).init()

    def time_mode(setup, teardown):
        setup()
        try:
            net = build()
            net.fit([(X, y)] * 5)                 # compile + settle
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                net.fit([(X, y)] * steps)
                _ = float(np.asarray(net._params[0]["W"]).sum())  # sync
                best = min(best, time.perf_counter() - t0)
            return best / steps * 1e3             # ms/step
        finally:
            teardown()

    was_enabled = telemetry.enabled()
    on_ms = time_mode(telemetry.enable, lambda: None)
    off_ms = time_mode(lambda: health.configure(enabled=False),
                       lambda: health.configure(enabled=True))
    dis_ms = time_mode(telemetry.disable,
                       telemetry.enable if was_enabled
                       else (lambda: None))
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0
    return {
        "metric": "health_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "%",
        "vs_baseline": None,
        "step_ms_health_on": round(on_ms, 4),
        "step_ms_health_off": round(off_ms, 4),
        "step_ms_telemetry_disabled": round(dis_ms, 4),
        "steps": steps,
        "note": ("min-of-3 mean step time over {n} steps of a 4-layer "
                 "256-wide MLP, batch 128; health on = per-layer fused "
                 "stats in-step + one-behind host publication; off = "
                 "stats compiled out; disabled = no telemetry at "
                 "all".format(n=steps)),
    }


def bench_precision(steps=60, repeats=3, n_requests=200):
    """ISSUE 4 smoke: (a) fp32 vs bf16_mixed steady-state step time on
    the same 4-layer MLP (master weights fp32 in both; the mixed run
    adds the compute casts + the in-step loss scaler), and (b) int8-PTQ
    vs fp32 serving p50/p99 through the DynamicBatcher on a warmed AOT
    ladder. On TPU the bf16/int8 rows are the MXU payoff; on CPU they
    mainly demonstrate the overhead side (bf16 is emulated), which is
    why off-TPU rows land platform-suffixed in BENCH_ALL.json."""
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.precision import quantize
    from deeplearning4j_tpu.serving import BucketLadder, InferenceSession

    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 256)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 128)]

    def build(precision=None):
        b = (NeuralNetConfiguration.Builder().seed(11).updater(Adam(1e-3)))
        if precision:
            b = b.precision(precision)
        conf = (b.list()
                .layer(DenseLayer.Builder().nIn(256).nOut(256)
                       .activation("relu").build())
                .layer(DenseLayer.Builder().nOut(256)
                       .activation("relu").build())
                .layer(DenseLayer.Builder().nOut(256)
                       .activation("relu").build())
                .layer(OutputLayer.Builder().nOut(10)
                       .activation("softmax")
                       .lossFunction(LossFunction.MCXENT).build())
                .build())
        return MultiLayerNetwork(conf).init()

    def step_ms(precision):
        net = build(precision)
        net.fit([(X, y)] * 5)                     # compile + settle
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            net.fit([(X, y)] * steps)
            _ = float(np.asarray(net._params[0]["W"]).sum())   # sync
            best = min(best, time.perf_counter() - t0)
        return best / steps * 1e3

    fp32_ms = step_ms(None)
    bf16_ms = step_ms("bf16_mixed")

    # serving: fp32 servable vs int8 PTQ of the SAME trained net
    net = build(None)
    net.fit([(X, y)] * 10)
    calib = [X[i * 32:(i + 1) * 32] for i in range(4)]
    qsv = quantize(net, calib, example_shape=(256,))

    def percentiles(session, name, x, n):
        for _ in range(10):
            session.predict(name, x)
        lat = np.empty(n)
        for i in range(n):
            t0 = time.perf_counter()
            session.predict(name, x)
            lat[i] = time.perf_counter() - t0
        return np.percentile(lat * 1e3, [50, 99])

    x1 = X[0]
    with InferenceSession(max_latency=0.001) as session:
        ladder = BucketLadder((1, 8, 32))
        session.register("fp32", net, example_shape=(256,), ladder=ladder,
                         warmup=True)
        session.register("int8", qsv, ladder=ladder, warmup=True)
        p50_f, p99_f = percentiles(session, "fp32", x1, n_requests)
        p50_q, p99_q = percentiles(session, "int8", x1, n_requests)

    return {
        "metric": "precision_bf16_vs_fp32_step_ratio",
        "value": round(bf16_ms / fp32_ms, 4),
        "unit": "x (bf16_mixed/fp32 step time; <1 is a speedup)",
        "vs_baseline": None,
        "host_bound": _host_bound(),
        "step_ms_fp32": round(fp32_ms, 4),
        "step_ms_bf16_mixed": round(bf16_ms, 4),
        "serving_p50_ms_fp32": round(float(p50_f), 3),
        "serving_p99_ms_fp32": round(float(p99_f), 3),
        "serving_p50_ms_int8": round(float(p50_q), 3),
        "serving_p99_ms_int8": round(float(p99_q), 3),
        "ptq_calibration_max_err": qsv.calibration_max_err,
        "steps": steps,
        "note": ("4-layer 256-wide MLP batch 128; bf16_mixed = fp32 "
                 "master + bf16 compute + dynamic loss scaling compiled "
                 "into the step; serving p50/p99 at batch 1 through the "
                 "DynamicBatcher on a warmed (1,8,32) ladder (includes "
                 "the 1 ms coalescing window)"),
    }


def bench_resilience(steps_per_epoch=10, epochs=4, every=2):
    """ISSUE 5 smoke: per-step overhead of checkpointing every `every`
    iterations, sync vs async, against a no-checkpoint baseline on the
    same MNIST-scale MLP (784-256-256-10, batch 128). The async row's
    step overhead is the device-side snapshot stall; the sync row eats
    the full serialize+write on the loop. Also reports the measured
    per-checkpoint stall vs write cost (acceptance: stall <= 10% of the
    write cost — the same instruments the tier-1 test asserts on)."""
    import tempfile

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.nn import (
        DenseLayer, InputType, MultiLayerNetwork, NeuralNetConfiguration,
        OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.parallel import ElasticTrainer

    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 128)]
    data = [(X, y)] * steps_per_epoch

    def build():
        conf = (NeuralNetConfiguration.Builder().seed(5)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer.Builder(nOut=256, activation="relu")
                       .build())
                .layer(DenseLayer.Builder(nOut=256, activation="relu")
                       .build())
                .layer(OutputLayer.Builder().nOut(10)
                       .activation("softmax").build())
                .setInputType(InputType.feedForward(784))
                .build())
        return MultiLayerNetwork(conf).init()

    def step_ms(mode, repeats=3):
        net = build()
        if mode == "none":
            fit, cleanup = (lambda e: net.fit(data, e)), (lambda: None)
        else:
            d = tempfile.mkdtemp(prefix=f"bench_ckpt_{mode}_")
            tr = ElasticTrainer(net, d, everyNIterations=every,
                                keepLast=2, asyncSave=(mode == "async"))

            def cleanup(tr=tr, d=d):
                import shutil

                tr.close()
                shutil.rmtree(d, ignore_errors=True)

            # ElasticTrainer.fit treats epochs as the TOTAL budget, so
            # each timed repeat must raise the budget to train again
            fit = lambda e, tr=tr: tr.fit(data, epochs=e)  # noqa: E731
        budget = 1
        fit(budget)             # compile train step + cloner + writer
        # steady state only: the warm pass's one-time cloner compile
        # must not pollute the snapshot-stall histogram
        telemetry.get_registry().reset()
        best = float("inf")
        for _ in range(repeats):
            budget += epochs
            t0 = time.perf_counter()
            fit(budget if mode != "none" else epochs)
            _ = float(np.asarray(net._params[0]["W"]).sum())
            best = min(best, time.perf_counter() - t0)
        cleanup()
        return best / (steps_per_epoch * epochs) * 1e3

    none_ms = step_ms("none")
    sync_ms = step_ms("sync")
    telemetry.get_registry().reset()
    async_ms = step_ms("async")
    reg = telemetry.get_registry()
    snap = reg.histogram("dl4j_ckpt_snapshot_seconds")
    write = reg.histogram("dl4j_ckpt_write_seconds", labelnames=("mode",))
    aw = write.labels(mode="async")
    stall_ms = snap.sum / max(snap.count, 1) * 1e3
    write_ms = aw.sum / max(aw.count, 1) * 1e3
    return {
        "metric": "resilience_ckpt_async_vs_sync_step_overhead",
        "value": round((async_ms - none_ms) / none_ms * 100.0, 2),
        "unit": "% step overhead (async checkpointing vs no checkpoints)",
        "vs_baseline": None,
        "step_ms_no_ckpt": round(none_ms, 4),
        "step_ms_sync_ckpt": round(sync_ms, 4),
        "step_ms_async_ckpt": round(async_ms, 4),
        "sync_overhead_pct": round((sync_ms - none_ms) / none_ms * 100.0,
                                   2),
        "snapshot_stall_ms": round(stall_ms, 4),
        "async_write_ms": round(write_ms, 4),
        "stall_over_write": round(stall_ms / max(write_ms, 1e-9), 4),
        "ckpt_every_n_steps": every,
        "note": ("MNIST-scale MLP (784-256-256-10, batch 128), "
                 f"checkpoint every {every} steps; async pays only the "
                 "device-side snapshot clone on the loop (acceptance: "
                 "stall <= 10% of write cost)"),
    }


def bench_trace_overhead(steps_per_epoch=8, epochs=30, trials=5,
                         n_requests=150):
    """ISSUE 10: what the tracing subsystem costs on the hot paths.

    Same MLP fit loop and same serving path under four modes:
    tracing sampled-ON (rate 1.0: every step/request builds spans),
    sampled-OFF (rate 0: the head sampler declines, per-step cost is a
    falsy-context check), tracing DISABLED (telemetry on, tracing
    compiled out — the pre-PR-10 path), and full telemetry.disable()
    for context. Steps/s are best-of-``trials`` (min wall time), which
    is the standard way to see a <=1% effect through this container's
    scheduler jitter. Acceptance: sampled-off steps/s within 1% of
    tracing-disabled."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import BucketLadder, InferenceSession
    from deeplearning4j_tpu.telemetry import tracing

    conf = (NeuralNetConfiguration.Builder().seed(7).list()
            .layer(DenseLayer.Builder().nIn(128).nOut(256)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(10).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(64, 128)).astype(np.float32),
                np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)])
               for _ in range(steps_per_epoch)]
    session = InferenceSession(max_latency=0.001)
    session.register("trace_bench", net, example_shape=(128,),
                     ladder=BucketLadder((1, 8)), warmup=True)
    x1 = rng.normal(size=(128,)).astype(np.float32)

    modes = {
        "sampled_on": lambda: (telemetry.enable(),
                               tracing.configure(enabled=True,
                                                 sample_rate=1.0)),
        "sampled_off": lambda: (telemetry.enable(),
                                tracing.configure(enabled=True,
                                                  sample_rate=0.0)),
        "tracing_disabled": lambda: (telemetry.enable(),
                                     tracing.configure(enabled=False)),
        "telemetry_disabled": lambda: (telemetry.disable(),),
    }

    def traced_predict():
        # a bare session.predict has no ambient trace, so it would
        # measure zero tracing work in EVERY mode — give each request
        # the root an HTTP handler would have opened (start_trace
        # applies this mode's sampler: spans in sampled_on, None in
        # the off/disabled modes)
        root = tracing.start_trace("bench.predict")
        with (root or tracing.NULL):
            session.predict("trace_bench", x1)

    best_s = {m: float("inf") for m in modes}
    lats = {m: [] for m in modes}

    def measure(mode, arm):
        arm()
        t0 = time.perf_counter()
        net.fit(batches, epochs)
        best_s[mode] = min(best_s[mode], time.perf_counter() - t0)
        for _ in range(5):
            traced_predict()
        lat = np.empty(n_requests // trials + 1)
        for i in range(len(lat)):
            t0 = time.perf_counter()
            traced_predict()
            lat[i] = time.perf_counter() - t0
        lats[mode].append(lat)

    tracing_modes = {m: modes[m] for m in
                     ("sampled_on", "sampled_off", "tracing_disabled")}
    try:
        telemetry.enable()
        net.fit(batches, 2)           # warm the telemetry-on step plan
        # INTERLEAVED rounds over the three tracing modes: a <=1%
        # effect is smaller than this container's minute-scale load
        # drift, so back-to-back per-mode blocks alias drift into the
        # comparison; cycling modes inside each round puts every mode
        # under the same drift. All three share one health build plan,
        # so switching costs no step recompile — telemetry_disabled
        # does NOT (its plan compiles health out), so it runs as its
        # own sequential block below (context only, not part of the
        # acceptance comparison).
        for _ in range(trials):
            for mode, arm in tracing_modes.items():
                measure(mode, arm)
        modes["telemetry_disabled"]()
        net.fit(batches, 2)           # warm the disabled step plan
        for _ in range(trials):
            measure("telemetry_disabled", modes["telemetry_disabled"])
    finally:
        telemetry.enable()
        tracing.configure(enabled=True, sample_rate=0.01)
        session.close()
    steps_s, p50_ms, p99_ms = {}, {}, {}
    for mode in modes:
        steps_s[mode] = round(steps_per_epoch * epochs / best_s[mode], 1)
        p50, p99 = np.percentile(np.concatenate(lats[mode]) * 1e3,
                                 [50, 99])
        p50_ms[mode] = round(float(p50), 3)
        p99_ms[mode] = round(float(p99), 3)
    off_pct = 100.0 * (steps_s["tracing_disabled"]
                       - steps_s["sampled_off"]) / \
        steps_s["tracing_disabled"]
    return {
        "metric": "trace_overhead_sampled_off_pct",
        "value": round(off_pct, 2),
        "unit": "%",
        "vs_baseline": None,
        "steps_per_s": steps_s,
        "serving_p50_ms": p50_ms,
        "serving_p99_ms": p99_ms,
        "steps_per_trial": steps_per_epoch * epochs,
        "trials": trials,
        "note": ("MLP 128-256-10 batch 64 fit loop + single-client "
                 "serving predicts; value = sampled-off steps/s deficit "
                 "vs tracing-disabled (acceptance <= 1%); sampled-on "
                 "pays span construction every step/request"),
    }


def bench_profile(steps_per_epoch=8, epochs=30, trials=5,
                  n_requests=150, load_seconds=3.0):
    """ISSUE 18: what the continuous profiler costs, and whether it
    attributes.

    Three measurements: (1) sampler-ON vs sampler-OFF paired fit +
    predict overhead — INTERLEAVED rounds (like trace_overhead: a <=1%
    effect is smaller than this container's minute-scale load drift,
    so every mode must sit under the same drift), best-of-``trials``
    min wall time, acceptance <= 1%; (2) a profile taken under a real
    serving load must attribute >= 90% of samples to named
    (non-``other``) subsystems; (3) the wall cost of one on-demand
    deep capture."""
    import threading

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import BucketLadder, InferenceSession
    from deeplearning4j_tpu.telemetry import profiler

    conf = (NeuralNetConfiguration.Builder().seed(7).list()
            .layer(DenseLayer.Builder().nIn(128).nOut(256)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(10).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(64, 128)).astype(np.float32),
                np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)])
               for _ in range(steps_per_epoch)]
    session = InferenceSession(max_latency=0.001)
    session.register("profile_bench", net, example_shape=(128,),
                     ladder=BucketLadder((1, 8)), warmup=True)
    x1 = rng.normal(size=(128,)).astype(np.float32)

    telemetry.enable()
    profiler.configure(hz=19.0)
    modes = {
        "sampler_on": lambda: profiler.start(),
        "sampler_off": lambda: profiler.stop(),
    }
    best_s = {m: float("inf") for m in modes}
    lats = {m: [] for m in modes}

    def measure(mode, arm):
        arm()
        t0 = time.perf_counter()
        net.fit(batches, epochs)
        best_s[mode] = min(best_s[mode], time.perf_counter() - t0)
        for _ in range(5):
            session.predict("profile_bench", x1)
        lat = np.empty(n_requests // trials + 1)
        for i in range(len(lat)):
            t0 = time.perf_counter()
            session.predict("profile_bench", x1)
            lat[i] = time.perf_counter() - t0
        lats[mode].append(lat)

    att = {}
    capture_wall = 0.0
    capture_meta = {}
    try:
        net.fit(batches, 2)           # warm the step plan
        session.predict("profile_bench", x1)
        for _ in range(trials):
            for mode, arm in modes.items():
                measure(mode, arm)
        # (2) attribution under a real serving load: hammer threads +
        # the main thread drive predict while the sampler runs — the
        # batcher coalescer / replica workers attribute by thread
        # name, the client threads by module-path heuristics
        profiler.clear()
        profiler.start()
        stop_evt = threading.Event()

        def hammer():
            while not stop_evt.is_set():
                session.predict("profile_bench", x1)

        clients = [threading.Thread(target=hammer, daemon=True,
                                    name=f"profile-bench-client-{i}")
                   for i in range(3)]
        for c in clients:
            c.start()
        t_end = time.perf_counter() + load_seconds
        while time.perf_counter() < t_end:
            session.predict("profile_bench", x1)
        stop_evt.set()
        for c in clients:
            c.join(timeout=5.0)
        att = profiler.describe()["attribution"]
        profiler.stop()
        # (3) deep-capture cost (device trace included when the
        # backend supports it; its wall cost ~= the requested window)
        import tempfile
        t0 = time.perf_counter()
        capture_meta = profiler.capture(
            seconds=0.5, out_dir=tempfile.mkdtemp(prefix="dl4j-bench-"))
        capture_wall = time.perf_counter() - t0
    finally:
        profiler.stop()
        session.close()
    steps_s, p50_ms, p99_ms = {}, {}, {}
    for mode in modes:
        steps_s[mode] = round(steps_per_epoch * epochs / best_s[mode], 1)
        p50, p99 = np.percentile(np.concatenate(lats[mode]) * 1e3,
                                 [50, 99])
        p50_ms[mode] = round(float(p50), 3)
        p99_ms[mode] = round(float(p99), 3)
    overhead_pct = 100.0 * (steps_s["sampler_off"]
                            - steps_s["sampler_on"]) / \
        steps_s["sampler_off"]
    total = sum(att.values()) or 1
    non_other = 1.0 - att.get("other", 0) / total
    return {
        "metric": "profile_sampler_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "%",
        "vs_baseline": None,
        "steps_per_s": steps_s,
        "serving_p50_ms": p50_ms,
        "serving_p99_ms": p99_ms,
        "attribution_non_other_fraction": round(non_other, 4),
        "attribution": att,
        "capture_wall_s": round(capture_wall, 3),
        "capture_samples": capture_meta.get("samples"),
        "capture_device_trace": capture_meta.get("device_trace"),
        "steps_per_trial": steps_per_epoch * epochs,
        "trials": trials,
        "note": ("MLP 128-256-10 batch 64 fit loop + serving predicts; "
                 "value = sampler-on steps/s deficit vs sampler-off at "
                 "19Hz (acceptance <= 1%); attribution fraction from a "
                 f"{load_seconds:.0f}s serving-load profile (acceptance "
                 ">= 0.9 non-other); capture cost is one 0.5s deep "
                 "capture incl. device trace"),
    }


def bench_compile_ledger(steps_per_epoch=8, epochs=10, rounds=20):
    """ISSUE 11: what the compile ledger + HLO audit cost on the hot
    paths.

    The ONLY per-step difference between ledger-on and ledger-off is
    the loops' ``compile_ledger.note_step`` call (steady state: one
    thread-local read), so the headline is measured where it is
    actually measurable: the note_step seam is microbenchmarked
    exactly as the fit loop invokes it (same arg tuple, policy label,
    window) and reported as a percentage of the fit loop's measured
    median step time. A whole-fit on/off differential is ALSO recorded
    (paired back-to-back rounds, order alternated, median ratio) as
    ``fit_paired_median_pct`` — context only: this container's
    wall-clock jitter (±1.5% between adjacent 0.1 s windows) dwarfs a
    sub-0.1% effect, which is precisely why the seam measurement is
    the acceptance number (<= 1%). One warmup ladder is also timed
    with the audit on vs off — the eager as_text+parse cost per AOT
    bucket, paid at warmup (never on the request path)."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import BucketLadder, ModelRegistry
    from deeplearning4j_tpu.telemetry import compile_ledger

    conf = (NeuralNetConfiguration.Builder().seed(7).list()
            .layer(DenseLayer.Builder().nIn(128).nOut(256)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(10).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(64, 128)).astype(np.float32),
                np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)])
               for _ in range(steps_per_epoch)]

    modes = {
        "ledger_on": lambda: (telemetry.enable(),
                              compile_ledger.configure(enabled=True)),
        "ledger_off": lambda: (telemetry.enable(),
                               compile_ledger.configure(enabled=False)),
        "telemetry_disabled": lambda: (telemetry.disable(),),
    }
    walls = {m: [] for m in modes}

    def measure(mode):
        modes[mode]()
        t0 = time.perf_counter()
        net.fit(batches, epochs)
        dt = time.perf_counter() - t0
        walls[mode].append(dt)
        return dt

    def warm_ladder(audit_on):
        compile_ledger.configure(enabled=audit_on)
        reg = ModelRegistry()
        t0 = time.perf_counter()
        # a fresh registration AOT-compiles the whole ladder (jax's
        # AOT cache makes repeats cheap, so the FIRST arm pays the
        # backend compiles — run audit-off first so the audit arm
        # isolates as_text+parse+ledger, not XLA)
        reg.register(f"ledger_bench_{int(audit_on)}", net,
                     example_shape=(128,),
                     ladder=BucketLadder((1, 8, 64)), warmup=True)
        return time.perf_counter() - t0

    ratios = []
    try:
        telemetry.enable()
        net.fit(batches, 2)            # warm the step executable
        for i in range(rounds):
            on_first = i % 2 == 0      # alternate order per round
            first, second = (("ledger_on", "ledger_off") if on_first
                             else ("ledger_off", "ledger_on"))
            t_first = measure(first)
            t_second = measure(second)
            t_on, t_off = ((t_first, t_second) if on_first
                           else (t_second, t_first))
            ratios.append(t_on / t_off)
        modes["telemetry_disabled"]()
        net.fit(batches, 2)            # warm the disabled step plan
        for _ in range(rounds // 4):
            measure("telemetry_disabled")
        telemetry.enable()
        warm_off = warm_ladder(False)
        warm_on = warm_ladder(True)
        records = len(compile_ledger.get_ledger().describe())
    finally:
        telemetry.enable()
        compile_ledger.configure(enabled=True)
    ratios.sort()
    median_ratio = ratios[len(ratios) // 2]
    steps_s = {m: round(steps_per_epoch * epochs / min(walls[m]), 1)
               for m in modes}

    # the seam itself, measured as the fit loop calls it: steady-state
    # note_step against a warmed site (one thread-local read)
    from deeplearning4j_tpu.telemetry import compile_ledger as _cl

    _cl.configure(enabled=True)
    telemetry.enable()
    import jax as _jax

    step_fn = net._train_step
    f0, l0 = batches[0]
    lmask0 = np.ones((f0.shape[0],), np.float32)
    note_args = (net._params, net._states, net._opt_states,
                 net._prec_state, f0, l0, lmask0,
                 _jax.random.key(0), 0)
    _cl.note_step("bench_seam", step_fn, note_args)   # warm the path
    n_calls = 50_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        _cl.note_step("bench_seam", step_fn, note_args,
                      policy="float32/h10", window=(0.0, 1.0))
    note_us = (time.perf_counter() - t0) / n_calls * 1e6
    median_step_s = sorted(walls["ledger_on"])[
        len(walls["ledger_on"]) // 2] / (steps_per_epoch * epochs)
    seam_pct = 100.0 * (note_us * 1e-6) / median_step_s
    return {
        "metric": "compile_ledger_overhead_pct",
        "value": round(seam_pct, 3),
        "unit": "%",
        "vs_baseline": None,
        "note_step_us": round(note_us, 2),
        "median_step_ms": round(median_step_s * 1e3, 3),
        "fit_paired_median_pct": round(100.0 * (median_ratio - 1.0), 2),
        "steps_per_s": steps_s,
        "warmup_audit_on_s": round(warm_on, 4),
        "warmup_audit_off_s": round(warm_off, 4),
        "ledger_records": records,
        "steps_per_round": steps_per_epoch * epochs,
        "rounds": rounds,
        "note": ("MLP 128-256-10 batch 64 fit loop; value = measured "
                 "steady-state note_step seam cost (the ONLY per-step "
                 "ledger-on/off difference) as % of the measured "
                 "median step time (acceptance <= 1%). "
                 "fit_paired_median_pct is the whole-fit paired-round "
                 "differential — context only, dominated by ±1.5% "
                 "container wall jitter. warmup_audit_*_s: a 3-bucket "
                 "AOT ladder warmup with the eager HLO audit on vs off "
                 "(audit cost is paid at warmup, never per request)"),
    }


def bench_memory(steps_per_epoch=8, epochs=10, rounds=12,
                 census_trials=20):
    """ISSUE 14: what the HBM ownership ledger costs on the hot path.

    The ONLY per-step difference between ledger-on and ledger-off is
    the loops' ``Claim.touch()`` (one dict read + one gauge set), so —
    exactly like the compile-ledger row — the headline is the touch
    seam microbenchmarked as the fit loop invokes it, reported as a
    percentage of the measured median step time (acceptance <= 1%). A
    whole-fit paired differential — ledger on vs off with the REST of
    telemetry held constant (``memledger.configure(enabled=)``, the
    compile-ledger isolation pattern) — rides along as context
    (dominated by this container's ±1.5% wall jitter), a
    telemetry-disabled block anchors the absolute floor, and the
    census cost (the /metrics-scrape-time claims-vs-device
    reconciliation, incl. the live-array fallback walk on CPU) is
    timed separately — it is a scrape cost, never a step cost."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.telemetry import memledger

    conf = (NeuralNetConfiguration.Builder().seed(7).list()
            .layer(DenseLayer.Builder().nIn(128).nOut(256)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(10).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(64, 128)).astype(np.float32),
                np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)])
               for _ in range(steps_per_epoch)]

    modes = {
        "ledger_on": lambda: (telemetry.enable(),
                              memledger.configure(enabled=True)),
        "ledger_off": lambda: (telemetry.enable(),
                               memledger.configure(enabled=False)),
        "telemetry_disabled": lambda: (telemetry.disable(),),
    }
    walls = {m: [] for m in modes}

    def measure(mode):
        modes[mode]()
        t0 = time.perf_counter()
        net.fit(batches, epochs)
        dt = time.perf_counter() - t0
        walls[mode].append(dt)
        return dt

    ratios = []
    try:
        telemetry.enable()
        net.fit(batches, 2)             # warm the instrumented plan
        for i in range(rounds):
            on_first = i % 2 == 0       # alternate order per round
            first, second = (("ledger_on", "ledger_off") if on_first
                             else ("ledger_off", "ledger_on"))
            t_first = measure(first)
            t_second = measure(second)
            t_on, t_off = ((t_first, t_second) if on_first
                           else (t_second, t_first))
            ratios.append(t_on / t_off)
        modes["telemetry_disabled"]()
        net.fit(batches, 2)             # warm the disabled plan
        for _ in range(max(1, rounds // 4)):
            measure("telemetry_disabled")
    finally:
        telemetry.enable()
        memledger.configure(enabled=True)

    # the seam itself, measured as the fit loop calls it: one running-
    # total read + one gauge set against the live train claim
    mem = memledger.claim(
        "train", "bench_seam",
        tree={"p": net._params, "o": net._opt_states})
    mem.touch()                          # warm the path
    n_calls = 50_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        mem.touch()
    touch_us = (time.perf_counter() - t0) / n_calls * 1e6
    mem.release()

    census_walls = []
    for _ in range(census_trials):
        t0 = time.perf_counter()
        memledger.census()
        census_walls.append(time.perf_counter() - t0)
    census_walls.sort()

    ratios.sort()
    median_ratio = ratios[len(ratios) // 2]
    median_step_s = sorted(walls["ledger_on"])[
        len(walls["ledger_on"]) // 2] / (steps_per_epoch * epochs)
    seam_pct = 100.0 * (touch_us * 1e-6) / median_step_s
    steps_s = {m: round(steps_per_epoch * epochs / min(walls[m]), 1)
               for m in modes}
    n_claims = len(memledger.get_memledger().claims())
    return {
        "metric": "memory_ledger_overhead_pct",
        "value": round(seam_pct, 3),
        "unit": "%",
        "vs_baseline": None,
        "touch_us": round(touch_us, 3),
        "median_step_ms": round(median_step_s * 1e3, 3),
        "fit_paired_median_pct": round(100.0 * (median_ratio - 1.0), 2),
        "census_median_ms": round(
            census_walls[len(census_walls) // 2] * 1e3, 3),
        "census_claims": n_claims,
        "steps_per_s": steps_s,
        "steps_per_round": steps_per_epoch * epochs,
        "rounds": rounds,
        "note": ("MLP 128-256-10 batch 64 fit loop; value = measured "
                 "steady-state Claim.touch() seam cost (the ONLY "
                 "per-step ledger-on/off difference) as % of the "
                 "measured median step time (acceptance <= 1%). "
                 "fit_paired_median_pct is the whole-fit paired-round "
                 "ledger-on-vs-off differential with the rest of "
                 "telemetry held constant — context only, dominated "
                 "by ±1.5% container wall jitter; the "
                 "telemetry_disabled block anchors the absolute "
                 "floor. census_median_ms is the /metrics scrape-time "
                 "reconciliation (live-array fallback walk on this "
                 "CPU host — memory_stats() path on chip is cheaper), "
                 "never paid per step"),
    }


def bench_coldstart():
    """ISSUE 13: cold vs warm process start through the persistent
    executable store (tools/coldstart.py). Every trial is a REAL
    subprocess restart: a 3-bucket serving registration and a
    Supervisor kill-and-resume, each cold (empty store) then warm.
    Zero-compile warm starts are ledger-asserted (causes all
    cache_hit), not inferred from timing."""
    import pathlib
    import sys as _sys

    tools = str(pathlib.Path(__file__).resolve().parent / "tools")
    if tools not in _sys.path:
        _sys.path.insert(0, tools)
    import coldstart

    report = coldstart.run_report()
    s, r = report["serving"], report["resume"]
    return {
        "metric": "coldstart_warm_registration_seconds",
        "value": s["warm"]["register_seconds"],
        "unit": "s",
        "vs_baseline": None,
        # the children run on the host platform regardless of the
        # parent's backend (a bench parent holding the chip cannot
        # hand it to 5 subprocesses), so the row is pinned to cpu and
        # is host-bound by construction: compile/deserialize walls
        # scale with host CPU + filesystem, not the model math
        "platform": "cpu",
        "host_bound": True,
        "serving_cold_s": s["cold"]["register_seconds"],
        "serving_warm_s": s["warm"]["register_seconds"],
        "serving_speedup_x": s["speedup"],
        "serving_warm_compiles": s["warm"]["compiles"],
        "serving_warm_causes": s["warm"]["causes"],
        "resume_cold_s": r["cold"]["resume_seconds"],
        "resume_warm_s": r["warm"]["resume_seconds"],
        "resume_speedup_x": r["speedup"],
        "resume_warm_compiles": r["warm"]["compiles"],
        "resume_warm_fit_causes": r["warm"]["fit_causes"],
        "resume_params_bit_identical":
            r["warm"]["params_sha"] == r["cold"]["params_sha"],
        "store_entries": len(report["store_contents"]),
        "store_bytes": sum(e["bytes"]
                           for e in report["store_contents"]),
        "note": ("subprocess-measured (fresh interpreter per trial): "
                 "8x384 MLP, (1,8,32) serving ladder, 2-epoch "
                 "supervised fit killed after epoch 1. Acceptance: "
                 "warm registration >= 5x faster than cold AND zero "
                 "XLA compiles warm (ledger causes all cache_hit). "
                 "Resume wall includes checkpoint restore + weight-"
                 "init compiles, so its ratio is structurally "
                 "smaller; the step acquisition itself shrinks from "
                 "a >1s compile to a ~15ms deserialize"),
    }


def bench_fleet(duration=1.2, deadline_ms=100.0, rows_per_request=1):
    """ISSUE 15: the fleet-router hop, measured (the PAPERS.md
    off-math-path rule: once kernels are fast, the extra network hop
    is where throughput goes to die — so the router is benched against
    a ~free host-side model, making the router itself the number).

    Three phases, all open-loop (fixed arrival schedule — a closed
    loop would back off exactly when the router struggles):

    - 1-worker vs 3-worker saturation: offered QPS swept geometrically;
      "saturation" is the max completed-rows/s whose completion ratio
      stays >= 90% with every answer inside `deadline_ms`;
    - rollout-in-progress p99: the 3-worker fleet at ~half saturation
      with a canary rollout mirroring 25% of traffic, vs the same load
      with no rollout — the canary tax on client latency (mirrors ride
      a background thread, so the tax should be ~the pin rewrite);
    - router hop overhead: direct-to-worker vs through-router p50 at
      light load, decomposed into the ISSUE 16 hop phases
      (queue/execute/worker_other/transit from the workers'
      Server-Timing headers) whose means must cover >=90% of the
      router-hop mean;
    - SLO-evaluation overhead: one time-series sample + burn-rate
      evaluation over the populated registry, amortized per request at
      the default sampling interval — must stay <=1% of request cost.
    """
    import threading
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.fleet.router import (
        FleetRouter, TransportFailure, _http, spawn_local_workers)
    from deeplearning4j_tpu.telemetry import slo as slo_mod
    from deeplearning4j_tpu.telemetry import timeseries

    # the worker is made the bottleneck ON PURPOSE (20ms serial
    # service, ladder pinned to batch-1 so the batcher cannot coalesce
    # it away): per-worker capacity is exactly 50 rows/s, so the
    # 1-vs-3-worker sweep measures the router's scale-out, not this
    # container's 2-core ceiling (which a ~free model hits at ~200
    # req/s of client+router+worker HTTP work combined)
    spec = {"models": [{"name": "m", "version": 1, "kind": "linear",
                        "scale": 2.0, "delay_ms": 20.0,
                        "example_shape": [8], "ladder": [1]}]}
    body = json.dumps(
        {"instances": [[1.0] * 8] * rows_per_request}).encode()
    deadline_s = deadline_ms / 1e3

    def open_loop(url, qps, run_s):
        lats, failures = [], [0]
        threads = []
        start = time.perf_counter()
        t_next = start

        def fire():
            t0 = time.perf_counter()
            try:
                status, _, _ = _http(
                    url + "/serving/v1/models/m:predict", body=body,
                    timeout=10.0)
            except TransportFailure:
                failures[0] += 1
                return
            dt = time.perf_counter() - t0
            if status == 200 and dt <= deadline_s:
                lats.append(dt)
            else:
                failures[0] += 1

        while t_next < start + run_s:
            now = time.perf_counter()
            if t_next > now:
                time.sleep(t_next - now)
            t = threading.Thread(target=fire, daemon=True)
            t.start()
            threads.append(t)
            t_next += 1.0 / qps
        for t in threads:
            t.join(15.0)
        offered = len(threads)
        lat = np.sort(np.asarray(lats)) if lats else np.zeros(1)
        return {
            "offered_qps": qps, "offered": offered,
            "completed": len(lats),
            "completed_rows_per_s": round(
                len(lats) * rows_per_request / run_s, 1),
            "completion_ratio": round(len(lats) / max(offered, 1), 3),
            "p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 2),
            "p99_ms": round(float(lat[int(len(lat) * 0.99)]) * 1e3, 2),
        }

    def saturation_sweep(url):
        points, best = [], 0.0
        for qps in (25, 50, 100, 150, 200, 300):
            p = open_loop(url, qps, duration)
            points.append(p)
            if p["completion_ratio"] >= 0.9:
                best = max(best, p["completed_rows_per_s"])
            else:
                break
        return points, best

    results = {}
    for n in (1, 3):
        workers = spawn_local_workers(
            n, spec, extra_env={"JAX_PLATFORMS": "cpu"})
        router = FleetRouter(workers, poll_interval=0.25,
                             owns_workers=True).start(port=0)
        url = f"http://127.0.0.1:{router.port}"
        try:
            t_end = time.monotonic() + 15.0
            while time.monotonic() < t_end and \
                    not all(w.models for w in router.workers):
                time.sleep(0.05)
            open_loop(url, 50, 0.3)   # warm the connections
            points, sat = saturation_sweep(url)
            results[f"workers_{n}"] = {"points": points,
                                       "saturation_rows_per_s": sat}
            if n == 3:
                half = max(25, int(sat / rows_per_request / 2))
                baseline = open_loop(url, half, duration)
                router.start_rollout(
                    "m", {"kind": "linear", "scale": 2.0,
                          "delay_ms": 20.0, "example_shape": [8],
                          "ladder": [1]},
                    version=2, fraction=0.25, min_samples=10 ** 9)
                in_rollout = open_loop(url, half, duration)
                results["rollout_in_progress"] = {
                    "offered_qps": half,
                    "baseline_p99_ms": baseline["p99_ms"],
                    "rollout_p99_ms": in_rollout["p99_ms"],
                    "mirrors": router.rollout._mirrors,
                }
                # direct vs routed hop at light load (10 qps: no
                # queueing on either side, so the delta IS the
                # router's added hop)
                w = router.workers[0]
                direct = open_loop(w.url, 10, 0.8)
                before = telemetry.get_registry().snapshot()
                routed = open_loop(url, 10, 0.8)
                after = telemetry.get_registry().snapshot()

                # hop decomposition (ISSUE 16): the router's own
                # dl4j_fleet_hop_seconds deltas over the routed run —
                # the phases partition the measured hop exactly, so
                # their means must cover >=90% of the router-hop mean
                # (the acceptance read; the residual is responses that
                # carried no Server-Timing header)
                def _delta(key):
                    return after.get(key, 0.0) - before.get(key, 0.0)

                phase_ms, phase_sum_s = {}, 0.0
                for phase in ("queue", "execute", "worker_other",
                              "transit"):
                    psum = _delta(
                        f'dl4j_fleet_hop_seconds_sum{{phase="{phase}"}}')
                    pcount = _delta(
                        f'dl4j_fleet_hop_seconds_count{{phase="{phase}"}}')
                    phase_sum_s += psum
                    phase_ms[phase] = round(
                        psum / max(pcount, 1) * 1e3, 3)
                hop_sum_s = hop_count = 0.0
                for key, v in after.items():
                    if key.startswith("dl4j_fleet_request_seconds_sum{"):
                        hop_sum_s += v - before.get(key, 0.0)
                    elif key.startswith(
                            "dl4j_fleet_request_seconds_count{"):
                        hop_count += v - before.get(key, 0.0)
                hop_mean_ms = hop_sum_s / max(hop_count, 1) * 1e3
                results["hop_decomposition"] = {
                    "phase_mean_ms": phase_ms,
                    "hop_mean_ms": round(hop_mean_ms, 3),
                    "coverage": round(
                        phase_sum_s / max(hop_sum_s, 1e-12), 4),
                }

                # SLO-evaluation overhead (ISSUE 16): one sampler tick
                # + burn evaluation over this populated registry,
                # amortized per request at the worker's default
                # sampling interval and the measured 3-worker
                # saturation — must be <=1% of the request's own cost
                slo_mod.declare(slo_mod.Slo(
                    "bench_hop", kind="latency",
                    metric='dl4j_fleet_request_seconds{worker="w0"}',
                    threshold=0.05, objective=0.99))
                timeseries.sample_now()   # warm the ring
                evals = 50
                t0 = time.perf_counter()
                for _ in range(evals):
                    timeseries.sample_now()
                eval_ms = (time.perf_counter() - t0) / evals * 1e3
                slo_mod.remove("bench_hop")
                interval = timeseries.DEFAULT_INTERVAL
                per_req_ms = eval_ms / max(interval * sat, 1e-9)
                results["slo_eval_overhead"] = {
                    "sample_plus_evaluate_ms": round(eval_ms, 4),
                    "interval_s": interval,
                    "amortized_per_request_ms_at_saturation": round(
                        per_req_ms, 6),
                    "pct_of_direct_p50": round(
                        per_req_ms / max(direct["p50_ms"], 1e-9) * 100,
                        4),
                }
                results["hop_overhead_ms"] = round(
                    routed["p50_ms"] - direct["p50_ms"], 2)
        finally:
            router.close()
    sat1 = results["workers_1"]["saturation_rows_per_s"]
    sat3 = results["workers_3"]["saturation_rows_per_s"]
    return {
        "metric": "fleet_router_3worker_saturation_rows_per_s",
        "value": sat3,
        "unit": "rows/s",
        "vs_baseline": None,
        "workers_1_saturation_rows_per_s": sat1,
        "scaling_x": round(sat3 / max(sat1, 1e-9), 2),
        "host_bound": _host_bound(),
        **results,
        "note": ("open-loop fixed-rate arrivals against subprocess "
                 "workers serving a 20ms serial host-side linear "
                 "model (batch-1 ladder: per-worker capacity exactly "
                 "50 rows/s), so the sweep measures the router's "
                 "scale-out and hop machinery, not model math; "
                 "rollout_in_progress compares client p99 at ~half "
                 "saturation with a 25% canary mirror active vs none; "
                 "hop_decomposition attributes the routed hop to "
                 "queue/execute/worker_other/transit via Server-Timing "
                 "subtraction (coverage = attributed/hop time), and "
                 "slo_eval_overhead amortizes one sample+evaluate tick "
                 "per request at the default 5s interval "
                 "(`python bench.py --only fleet`)"),
    }


def bench_fleet_loop(fill=40, n_baseline=80):
    """ISSUE 20: the closed loop, measured. Four numbers against a
    live 2-worker fleet on this box:

    - capture -> fine-tune -> publish -> promote wall clock
      (`loop_wall_s`): live traffic into the capture ring, a fresh
      model distilled from it at the `train` admission priority, the
      checkpoint pushed back through a `from_checkpoint` canary
      rollout, and the canary promoted fleet-wide;
    - serving p99 with vs without the concurrent fine-tune: the train
      class is capped and shed first (arbitration, not isolation —
      the fit still competes for the same cores, so the read is
      "bounded", not "free");
    - respawn MTTR: SIGKILL a spawned worker under traffic and time
      kill -> the respawned process routable again;
    - client-visible errors across the kill window (the router's
      retry budget + the respawner should hold this at 0).
    """
    import os
    import signal as _signal
    import tempfile
    import threading

    from deeplearning4j_tpu.fleet import (
        Autopilot, FleetFineTuner, Respawner, TrafficCapture)
    from deeplearning4j_tpu.fleet.router import (
        FleetRouter, TransportFailure, _http, spawn_local_workers)
    from deeplearning4j_tpu.serving.admission import AdmissionController
    from deeplearning4j_tpu.telemetry import flight

    def _tiny():
        from deeplearning4j_tpu.nn import (
            DenseLayer, InputType, MultiLayerNetwork,
            NeuralNetConfiguration, OutputLayer)
        from deeplearning4j_tpu.optimize.updaters import Adam

        conf = (NeuralNetConfiguration.Builder().seed(7)
                .updater(Adam(1e-2)).list()
                .layer(DenseLayer.Builder().nOut(8)
                       .activation("tanh").build())
                .layer(OutputLayer.Builder().nOut(2)
                       .activation("softmax").build())
                .setInputType(InputType.feedForward(3)).build())
        net = MultiLayerNetwork(conf)
        net.init()
        return net

    tmp = tempfile.mkdtemp(prefix="dl4j_fleet_loop_")
    mlp = {"name": "m", "version": 1, "kind": "mlp", "n_in": 3,
           "n_out": 2, "width": 8, "seed": 7, "example_shape": [3],
           "ladder": [1, 4]}
    spec = {"models": [mlp]}
    handles = spawn_local_workers(
        2, spec, base_dir=os.path.join(tmp, "fleet"), timeout=120.0,
        extra_env={"JAX_PLATFORMS": "cpu"})
    cap = TrafficCapture(sample_interval=1, max_records=512)
    router = FleetRouter(handles, poll_interval=0.1, capture=cap,
                         owns_workers=True,
                         retry_budget=4).start(port=0)
    url = f"http://127.0.0.1:{router.port}"
    rng = np.random.default_rng(5)
    stats = {"sent": 0, "ok": 0}

    def predict_once(lats=None):
        x = rng.normal(size=(2, 3)).astype(np.float32)
        t0 = time.perf_counter()
        try:
            status, _, _ = _http(
                f"{url}/serving/v1/models/m:predict",
                body=json.dumps({"instances": x.tolist()}).encode(),
                timeout=30.0)
        except TransportFailure:
            stats["sent"] += 1
            return 0
        stats["sent"] += 1
        stats["ok"] += status == 200
        if status == 200 and lats is not None:
            lats.append(time.perf_counter() - t0)
        return status

    def p99_ms(lats):
        return round(float(np.quantile(lats, 0.99)) * 1e3, 2) \
            if lats else 0.0

    results = {}
    try:
        # capture + unloaded baseline
        for _ in range(fill):
            predict_once()
        base_lat = []
        for _ in range(n_baseline):
            predict_once(base_lat)
        t_loop = time.perf_counter()
        path = cap.save(os.path.join(tmp, "traffic.jsonl"),
                        append=True)

        # fine-tune at train priority while serving continues
        adm = AdmissionController(default_budget=8)
        ft = FleetFineTuner(
            router, "m", path, _tiny, os.path.join(tmp, "ckpt"),
            admission=adm, epochs=2, batch_size=8,
            spec_extra={"example_shape": [3]},
            rollout_kw={"fraction": 1.0, "min_samples": 5,
                        "p99_ratio": 100.0, "push_timeout": 120.0},
            everyNIterations=1).start()
        during = []
        while ft._thread.is_alive():
            predict_once(during)
            time.sleep(0.002)
        ft.join(60.0)
        t_trained = time.perf_counter()

        # drive the published canary to its verdict
        ctl = router.rollout
        deadline = time.monotonic() + 120.0
        while ctl is not None and not ctl.terminal() and \
                time.monotonic() < deadline:
            predict_once()
            time.sleep(0.002)
        loop_wall = time.perf_counter() - t_loop
        results.update({
            "finetune_state": ft.state,
            "published_version": ft.published_version,
            "rollout_state": None if ctl is None else ctl.state,
            "finetune_s": round(t_trained - t_loop, 2),
            "serving_p99_ms_baseline": p99_ms(base_lat),
            "serving_p99_ms_during_finetune": p99_ms(during),
            "train_sheds": next(
                (e.get("train_sheds") for e in
                 flight.get_recorder().events("finetune_complete")),
                None),
        })

        # respawn MTTR: kill a worker under traffic, time the revival
        rs = Respawner(router, max_respawns=3, spawn_timeout=120.0)
        ap = Autopilot(router, respawner=rs, interval=0.05).start()
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            _, _, hb = _http(url + "/healthz", timeout=10.0)
            if json.loads(hb)["fleet"]["routable"] == 2:
                break
            time.sleep(0.05)
        victim = router.workers[0]
        sent0, ok0 = stats["sent"], stats["ok"]
        t_kill = time.perf_counter()
        os.kill(victim.proc.pid, _signal.SIGKILL)
        mttr = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            predict_once()
            if victim.up and any(
                    e["outcome"] == "ok" for e in
                    flight.get_recorder().events("worker_respawn")):
                mttr = time.perf_counter() - t_kill
                break
            time.sleep(0.01)
        ap.close()
        results.update({
            "respawn_mttr_s": None if mttr is None else round(mttr, 2),
            "kill_window_errors": (stats["sent"] - sent0)
            - (stats["ok"] - ok0),
        })
    finally:
        router.close()
    return {
        "metric": "fleet_loop_capture_to_promoted_s",
        "value": round(loop_wall, 2),
        "unit": "s",
        "vs_baseline": None,
        "host_bound": _host_bound(),
        **results,
        "note": ("2 spawned CPU workers behind the router; loop wall "
                 "covers capture save -> distillation fine-tune at "
                 "train priority (admission-capped, shed first) -> "
                 "from_checkpoint canary -> fleet-wide promote, with "
                 "client traffic flowing throughout; p99 pair is the "
                 "concurrent-training tax on serving (same cores — "
                 "bounded, not free); respawn MTTR is SIGKILL -> "
                 "autopilot-respawned worker routable, with the "
                 "client-visible error count over that window "
                 "(`python bench.py --only fleet_loop`)"),
    }


def bench_sharded_serving(prompt_len=128, max_new=32, n_requests=6):
    """ISSUE 19: GSPMD-sharded serving vs the single-device reference.
    Two arms on one 4-way model-parallel mesh: (a) predict hop — the
    same column-parallel MLP served sharded and replicated through the
    same session/ladder, recording p50/p99 per path and the sharded
    hop overhead (GSPMD dispatch + per-device arg placement); (b)
    decode — a mesh-sharded paged-KV transformer placed OVER BUDGET
    (the memledger budget is set so the whole pool exceeds one
    device's headroom but each page shard fits), recording tokens/s,
    tokens/s/chip and TTFT p50/p99, with the unsharded twin's typed
    rejection asserted in the same row. benchdiff direction: the
    headline value is sharded decode tokens/s/chip (higher is
    better); hop_overhead_ms is the cost knob to watch."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.serving import (
        BucketLadder, DecodeEngine, FnServable, InferenceSession,
        ShardedServable, ShardedTransformerDecodeModel,
        TransformerDecodeModel, column_parallel_mlp)
    from deeplearning4j_tpu.telemetry import memledger

    devices = jax.devices()
    tp = min(4, len(devices))
    if tp < 2:
        raise RuntimeError(
            "sharded_serving needs >= 2 devices; `python bench.py "
            "--only sharded_serving` forces 4 host devices on CPU")
    mesh = MeshConfig(data=1, model=tp, devices=devices[:tp]).build()

    # --- predict arm: sharded vs replicated through one session -----
    sizes = (256, 1024, 256)
    fn, ref_fn, params, specs = column_parallel_mlp(mesh, sizes, seed=3)
    sess = InferenceSession()
    sess.register("sh", ShardedServable(fn, params, (sizes[0],), mesh,
                                        param_specs=specs),
                  ladder=BucketLadder([4]), warmup=True)
    sess.register("rep", FnServable(lambda x: ref_fn(params, x),
                                    (sizes[0],), dtype=np.float32),
                  ladder=BucketLadder([4]), warmup=True)
    x = np.random.default_rng(0).standard_normal(
        (4, sizes[0])).astype(np.float32)

    def time_predict(name, n=60):
        sess.predict(name, x)   # steady state before the clock starts
        lat = []
        for _ in range(n):
            t0 = time.perf_counter()
            sess.predict(name, x)
            lat.append(time.perf_counter() - t0)
        lat = np.asarray(lat) * 1e3
        return {"p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3)}

    predict = {"sharded": time_predict("sh"),
               "replicated": time_predict("rep")}
    predict["hop_overhead_ms"] = round(
        predict["sharded"]["p50_ms"] - predict["replicated"]["p50_ms"],
        3)
    # unregister releases the predict arms' ledger claims (close()
    # alone keeps registry entries live) before the budget demo below
    sess.registry.unregister("sh")
    sess.registry.unregister("rep")
    sess.close()

    # --- decode arm: page-sharded KV pool, placed over budget --------
    # n_pages oversizes the POOL only (the attention loop runs over
    # max_pages_per_slot, so decode cost is untouched): a 32MB pool
    # against a 20MB device budget makes the placement genuinely
    # over-budget while the ~1MB of params stays noise
    pool_kw = dict(max_slots=4, page=32,
                   max_pages_per_slot=(prompt_len + max_new + 63)
                   // 32 + 1, n_pages=1023)
    base = TransformerDecodeModel.init(
        vocab=256, hidden=64, n_layers=2, n_heads=2,
        max_len=prompt_len + max_new + 64, seed=5, **pool_kw)
    sharded = ShardedTransformerDecodeModel(base.params, 2, mesh,
                                            **pool_kw)
    pool_total = sum(sharded.pool_device_bytes().values())
    # whole pool > one device's budget, but each page shard fits
    budget = 20 * 1024 * 1024
    memledger.configure(budget_bytes=budget)
    try:
        try:
            DecodeEngine(base, name="bench-sh-ref")
            unsharded_fate = "admitted (BUG: should not fit)"
        except memledger.CapacityError as e:
            unsharded_fate = f"rejected at {e.site}"
        engine = DecodeEngine(sharded, name="bench-sh").warmup()
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(0, 256, size=prompt_len + i))
                   for i in range(n_requests)]
        ttfts = []
        t0 = time.perf_counter()
        n_tokens = 0
        for prompt in prompts:
            req = engine.submit(prompt, max_new)
            t_sub = time.perf_counter()
            stream = req.tokens(timeout=600.0)
            next(stream)
            ttfts.append(time.perf_counter() - t_sub)
            n_tokens += 1 + sum(1 for _ in stream)
        wall = time.perf_counter() - t0
        engine.close()
    finally:
        memledger.configure(budget_bytes=None)
    lat = np.asarray(ttfts) * 1e3
    tokens_per_s = n_tokens / wall
    decode = {
        "tokens_per_s": round(tokens_per_s, 1),
        "tokens_per_s_per_chip": round(tokens_per_s / tp, 1),
        "ttft_p50_ms": round(float(np.percentile(lat, 50)), 2),
        "ttft_p99_ms": round(float(np.percentile(lat, 99)), 2),
        "pool_bytes": pool_total,
        "device_budget_bytes": budget,
        "pool_shards": sharded.pool_shards,
        "unsharded_twin": unsharded_fate,
    }
    return {
        "metric": "sharded_decode_tokens_per_s_per_chip",
        "value": decode["tokens_per_s_per_chip"],
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "host_bound": _host_bound(),
        "mesh": {"model": tp},
        "predict": predict,
        "decode": decode,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "note": ("4-way model-parallel mesh; predict compares the same "
                 "column-parallel MLP served sharded vs replicated "
                 "(hop_overhead_ms = GSPMD dispatch + per-device arg "
                 "placement at p50); decode streams from a page-"
                 "sharded KV pool deliberately placed over a budget "
                 "one device cannot hold (the unsharded twin's typed "
                 "rejection is recorded in the row). CAVEAT: CPU row "
                 "is host-bound — virtual host devices share the same "
                 "silicon, so tokens/s/chip understates a real slice; "
                 "re-record on chip "
                 "(`python bench.py --only sharded_serving`)"),
    }


ALL_BENCHES = [("bert", bench_bert), ("lenet", bench_lenet),
               ("resnet50", bench_resnet50),
               ("resnet50_etl", bench_resnet_etl),
               ("etl", bench_etl),
               ("graves_lstm", bench_graves_lstm),
               ("word2vec", bench_word2vec),
               ("serving_latency", bench_serving_latency),
               ("serving_load", bench_serving_load),
               ("decode", bench_decode),
               ("health_overhead", bench_health_overhead),
               ("precision", bench_precision),
               ("resilience", bench_resilience),
               ("trace_overhead", bench_trace_overhead),
               ("profile", bench_profile),
               ("compile_ledger", bench_compile_ledger),
               ("memory", bench_memory),
               ("coldstart", bench_coldstart),
               ("fleet", bench_fleet),
               ("fleet_loop", bench_fleet_loop),
               ("sharded_serving", bench_sharded_serving)]


def _merge_bench_all(results, path="BENCH_ALL.json"):
    """Merge measured rows into BENCH_ALL.json instead of clobbering it.
    README calls this file the authoritative record of TPU-chip numbers
    (VERDICT r5 item 2: headline claims must exist as recorded rows), so
    rows measured on another backend land under a platform-suffixed key
    ('word2vec_cpu') and never displace a chip row. Every new row is
    stamped with its platform."""
    import jax

    backend = jax.default_backend()
    try:
        with open(path) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        existing = {}
    for name, rec in results.items():
        rec = dict(rec)
        rec.setdefault("platform", backend)
        key = name if backend == "tpu" else f"{name}_{backend}"
        if "error" in rec and "error" not in existing.get(key, {"error": 1}):
            # a transient bench failure must not destroy a previously
            # measured row; record the failure beside it instead
            existing[key + "_error"] = rec
            continue
        existing[key] = rec
    with open(path, "w") as f:
        json.dump(existing, f, indent=1)
    return existing


def _flag_value(argv, flag, default=None, cast=str):
    if flag in argv:
        i = argv.index(flag) + 1
        if i >= len(argv):
            raise SystemExit(f"{flag} needs a value")
        return cast(argv[i])
    return default


def main():
    argv = sys.argv[1:]
    only = _flag_value(argv, "--only", "")
    if ("serving_load" in only or "sharded_serving" in only
            or "--all" in argv):
        # the replica and sharded benches want a multi-device CPU mesh;
        # the flag only affects the host platform (harmless on TPU) and
        # must be set BEFORE the first jax import
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
    from deeplearning4j_tpu.runtime import RuntimeConfig

    RuntimeConfig.enable_compile_cache()
    words = _flag_value(argv, "--words", 10_000_000, int)
    benches = dict(ALL_BENCHES)
    benches["word2vec"] = lambda: bench_word2vec(words)
    if "--only" in argv:
        # subset run that MERGES into BENCH_ALL.json, e.g.
        #   python bench.py --only word2vec,serving_latency [--words N]
        names = _flag_value(argv, "--only").split(",")
        unknown = [n for n in names if n not in benches]
        if unknown:
            raise SystemExit(f"unknown bench {unknown}; "
                             f"choose from {sorted(benches)}")
        results = {}
        for name in names:
            try:
                results[name] = benches[name]()
            except Exception as e:  # record, keep measuring the rest
                results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(json.dumps({name: results[name]}))
        _merge_bench_all(results)
        return int(any("error" in r for r in results.values()))
    if "--all" in argv:
        results = {}
        for name, _ in ALL_BENCHES:
            fn = benches[name]
            try:
                results[name] = fn()
            except Exception as e:  # record, keep measuring the rest
                results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(json.dumps({name: results[name]}))
        _merge_bench_all(results)
        # driver line last: the flagship result, exactly the 4 contract
        # keys (and a valid record even if the bert bench errored)
        bert = results["bert"]
        if "metric" in bert:
            line = {k: bert[k] for k in
                    ("metric", "value", "unit", "vs_baseline")}
        else:
            line = {"metric": "bert_base_mlm_tokens_per_sec_per_chip",
                    "value": 0.0, "unit": "tokens/sec",
                    "vs_baseline": 0.0}
        print(json.dumps(line))
        return int(any("error" in r for r in results.values()))
    try:
        out = bench_bert()
    except NotATpu as e:
        print(f"bench.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps({k: out[k] for k in
                      ("metric", "value", "unit", "vs_baseline")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
