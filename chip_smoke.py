#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main paths once, through the entry points a user
calls, at the full width of the models the repo supports (weights random,
from a seed):

  bert_train    BertTrainer.train_step x5, BERT-base at 16x512
  kernels       MultiLayerNetwork.fit() of the char-LSTM at batch 1024 with
                the Pallas recurrence kernels proven present in the compiled
                step's HLO; gru_seq forward+backward compiled
  resnet50_fit  ComputationGraph.fit() of ResNet-50 (bf16, batch 256) fed by
                ParallelImageDataSetIterator workers over generated JPEGs
  decode_serve  a BERT-base-width decoder behind UIServer answering 8
                concurrent HTTP decode requests from client threads
  dp4           (>= 4 devices) bert_train on MeshConfig(data=4) and the
                char-LSTM through ParallelWrapper, batch shards on 4 devices

It refuses to run unless jax.devices()[0].platform == "tpu", any phase that
raises ends the run non-zero, and on success the LAST line of stdout is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

    python chip_smoke.py                       # on the chip
    python chip_smoke.py --require-chips 4     # on a four-chip host
    python chip_smoke.py --only kernels,dp4    # builder's debugging
    python chip_smoke.py --dry-cpu             # same code path, toy widths,
                                               # Pallas interpreted; every
                                               # line marked; never a result

Times printed here are smoke timings (one or two samples, set-up included
where it says so) — evidence that the path runs, not measurements.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import threading
import time
from importlib import metadata

HERE = os.path.dirname(os.path.abspath(__file__))
DRY_MARK = "DRY RUN (cpu) — not a chip result"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Smoke:
    """Run-wide state: sizes (full or toy), output, compile counters."""

    def __init__(self, dry, out_dir):
        self.dry = dry
        self.out_dir = out_dir
        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0

    def say(self, msg):
        print(f"{DRY_MARK} | {msg}" if self.dry else msg, flush=True)

    def size(self, full, toy):
        return toy if self.dry else full

    # jax.monitoring listeners: a backend-compile event fires for every
    # executable jax asks XLA for (persistent-cache hit or real compile);
    # a cache-hit event only when the persistent cache answered
    def on_duration(self, event, seconds, **_):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.cache_hits += 1


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats:
        return "n/a (backend reports no memory_stats)"
    return str(stats["peak_bytes_in_use"])


def _finite(x, what):
    if not math.isfinite(x):
        raise AssertionError(f"{what} is not finite: {x}")
    return x


def _fit_twice(fit, net, what):
    """``fit()`` once cold (compile + the steps) and once warm, each ending
    in the host read ``fit`` itself makes; -> (set-up s, warm s, scores)."""
    t = time.perf_counter()
    fit()
    setup = time.perf_counter() - t
    s0 = _finite(net.score(), f"{what} score")
    t = time.perf_counter()
    fit()
    dt = time.perf_counter() - t
    s1 = _finite(net.score(), f"{what} score")
    return setup, dt, s0, s1


# ---------------------------------------------------------------------------
# bert_train (also the first half of dp4)
# ---------------------------------------------------------------------------

def _bert(sm, n_data, batch):
    import jax

    from deeplearning4j_tpu.models import (BertConfig, BertTrainer,
                                           synthetic_mlm_batch)
    from deeplearning4j_tpu.parallel.mesh import MeshConfig

    cfg = sm.size(
        BertConfig(vocab_size=30522, hidden=768, num_layers=12,
                   num_heads=12, ffn=3072, max_len=512),
        BertConfig(vocab_size=128, hidden=32, num_layers=2, num_heads=2,
                   ffn=64, max_len=32))
    seq = cfg.max_len
    mesh = MeshConfig(data=n_data, devices=jax.devices()[:n_data]).build()
    t0 = time.perf_counter()
    trainer = BertTrainer(cfg, mesh, lr=sm.size(1e-4, 1e-3))
    tok, lab = synthetic_mlm_batch(cfg, batch, seq, seed=0)
    losses = [_finite(float(trainer.train_step(tok, lab)), "first loss")]
    setup = time.perf_counter() - t0
    # the same step, synchronised two ways: on a local chip they agree
    dt_block, dt_float = [], []
    for _ in range(2):
        t = time.perf_counter()
        loss = jax.block_until_ready(trainer.train_step(tok, lab))
        dt_block.append(time.perf_counter() - t)
        losses.append(float(loss))
    for _ in range(2):
        t = time.perf_counter()
        losses.append(float(trainer.train_step(tok, lab)))
        dt_float.append(time.perf_counter() - t)
    for v in losses:
        _finite(v, "train_step loss")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a repeated batch: "
                             f"{losses}")
    sm.say(f"  train_step x5 batch={batch}x{seq} data={n_data}: "
           f"setup+first={setup:.2f}s "
           f"step(block_until_ready)={min(dt_block):.4f}s "
           f"step(float read-back)={min(dt_float):.4f}s "
           f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return trainer


def phase_bert_train(sm):
    _bert(sm, n_data=1, batch=sm.size(16, 4))


# ---------------------------------------------------------------------------
# kernels (also the second half of dp4)
# ---------------------------------------------------------------------------

def _char_batches(vocab, seq, batch, n):
    import numpy as np

    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq + 1))
    eye = np.eye(vocab, dtype=np.float32)
    x = eye[ids[:, :-1]].transpose(0, 2, 1)
    y = eye[ids[:, 1:]].transpose(0, 2, 1)
    return ListDataSetIterator([DataSet(x, y)] * n, batch)


def _route_counts(op):
    from deeplearning4j_tpu import telemetry

    samples = telemetry.prometheus.parse(
        telemetry.prometheus.render(collect_system=False))
    return {route: int(samples.get(
        f'dl4j_recurrence_route_total{{op="{op}",route="{route}"}}', 0))
        for route in ("pallas", "interpret", "scan")}


def _check_kernel_in_step(sm, site, rows, routes_before):
    """The compiled step at ledger ``site`` must have taken the Pallas
    recurrence: the route counter says so (against ``routes_before``,
    read before the step was traced), and on the chip its optimized HLO
    holds the Mosaic custom calls, forward and backward for both LSTM
    layers, each over ``rows`` batch rows."""
    from deeplearning4j_tpu import telemetry

    want = "interpret" if sm.dry else "pallas"
    routes = {route: count - routes_before[route]
              for route, count in _route_counts("LSTM").items()}
    if routes[want] < 2 or routes["scan"]:
        raise AssertionError(
            f"bench-width LSTM did not take the {want} kernel: {routes}")
    ledger = telemetry.compile_ledger.get_ledger()
    records = ledger.describe(site=site)
    if not records:
        raise AssertionError(f"no compile-ledger record at site {site!r}")
    audit = ledger.audit(records[0]["key"])
    if "error" in audit:
        raise AssertionError(f"HLO audit of {site!r} failed: {audit}")
    mosaic = audit["custom_call_targets"].get("tpu_custom_call", 0)
    sm.say(f"  {site} step HLO: routes={routes} mosaic_custom_calls="
           f"{mosaic} results={audit['mosaic_results']} "
           f"fusions={audit['fusions']} "
           f"collectives={audit['collectives']['total']}")
    if sm.dry:
        return   # the interpreter lowers to plain HLO: nothing to find
    if mosaic < 4:
        raise AssertionError(
            f"compiled {site!r} step holds {mosaic} Mosaic custom calls, "
            f"need >= 4 (2 LSTM layers x forward+backward)")
    bad = [r for r in audit["mosaic_results"] if f",{rows}," not in r]
    if bad:
        raise AssertionError(
            f"Mosaic calls in {site!r} do not run over {rows} batch "
            f"rows per device: {audit['mosaic_results']}")


def _lstm_net(sm):
    from deeplearning4j_tpu.models import TextGenerationLSTM

    vocab, hidden, seq = sm.size((77, 256, 100), (11, 128, 6))
    return TextGenerationLSTM(vocabSize=vocab, hidden=hidden,
                              seqLength=seq).init(), vocab, seq


def _kernel_vs_scan(sm, name):
    """Small-input agreement of the routed kernel with the lax.scan
    lowering of the same op (the repo's own reference), forward and
    gradients; returns the largest absolute differences."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.autodiff.ops import OPS

    gates = 4 if name == "LSTM" else 3
    n, i_sz, h, t = 8, 16, 128, 12
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(n, i_sz, t)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.normal(size=(i_sz, gates * h)) * 0.1, jnp.float32)
    r = jnp.asarray(rng.normal(size=(h, gates * h)) * 0.1, jnp.float32)
    op = OPS["lstmLayer" if name == "LSTM" else "gruLayer"]

    def run():
        def loss(w_, r_):
            return jnp.sum(jnp.square(op(x, w_, r_)[0]))
        return op(x, w, r)[0], jax.grad(loss, argnums=(0, 1))(w, r)

    out_k, g_k = run()
    os.environ[f"DL4J_DISABLE_PALLAS_{name}"] = "1"   # read at trace time
    try:
        with jax.default_matmul_precision("highest"):
            out_s, g_s = run()
    finally:
        del os.environ[f"DL4J_DISABLE_PALLAS_{name}"]
    d_out = float(jnp.max(jnp.abs(out_k - out_s)))
    d_grad = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(g_k, g_s))
    scale = max(float(jnp.max(jnp.abs(b))) for b in g_s)
    # outputs are tanh-bounded; the scan reference runs at full f32
    # matmul precision, the kernel at the MXU's default
    if d_out > 2e-2 or d_grad > 2e-2 * max(scale, 1.0):
        raise AssertionError(
            f"{name} kernel disagrees with the scan reference: "
            f"max|dout|={d_out:.3e} max|dgrad|={d_grad:.3e} "
            f"(grad scale {scale:.3e})")
    return d_out, d_grad


def phase_kernels(sm):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels.gru import gru_seq

    net, vocab, seq = _lstm_net(sm)
    batch = sm.size(1024, 8)
    data = _char_batches(vocab, seq, batch, 3)
    routes_before = _route_counts("LSTM")
    setup, dt, s0, s1 = _fit_twice(lambda: net.fit(data), net, "LSTM fit")
    sm.say(f"  fit() x3 batch={batch} T={seq}: setup+first={setup:.2f}s "
           f"fit-of-3={dt:.4f}s ({dt / 3:.4f}s/step, input pipeline "
           f"included) score {s0:.4f} -> {s1:.4f}")
    if not s1 < s0:
        raise AssertionError(f"LSTM score did not fall: {s0} -> {s1}")
    _check_kernel_in_step(sm, "fit", batch, routes_before)

    # gru_seq at the bench shape, forward + backward, compiled
    t_, n_, h_ = sm.size((100, 1024, 256), (6, 8, 128))
    key = jax.random.key(0)
    xw = jax.random.normal(key, (t_, n_, 3 * h_), jnp.float32) * 0.3
    r = jax.random.normal(jax.random.fold_in(key, 1), (h_, 3 * h_),
                          jnp.float32) * 0.1
    rb = jnp.zeros((3 * h_,), jnp.float32)
    h0 = jnp.zeros((n_, h_), jnp.float32)

    def gru_loss(xw_, r_, rb_):
        hs, h_t = gru_seq(xw_, r_, rb_, h0, sm.dry)
        return jnp.sum(hs * hs) + jnp.sum(h_t)

    step = jax.jit(jax.value_and_grad(gru_loss, argnums=(0, 1, 2)))
    t = time.perf_counter()
    val, grads = jax.block_until_ready(step(xw, r, rb))
    setup = time.perf_counter() - t
    t = time.perf_counter()
    val, grads = jax.block_until_ready(step(xw, r, rb))
    dt = time.perf_counter() - t
    _finite(float(val), "gru_seq loss")
    for g in grads:
        _finite(float(jnp.sum(g)), "gru_seq gradient sum")
    sm.say(f"  gru_seq fwd+bwd T={t_} N={n_} H={h_} "
           f"{'interpreted' if sm.dry else 'compiled'}: "
           f"setup+first={setup:.2f}s call={dt:.4f}s")
    for name in ("LSTM", "GRU"):
        d_out, d_grad = _kernel_vs_scan(sm, name)
        sm.say(f"  {name} kernel vs lax.scan reference (N=8 H=128 T=12): "
               f"max|dout|={d_out:.2e} max|dgrad|={d_grad:.2e}")


# ---------------------------------------------------------------------------
# resnet50_fit
# ---------------------------------------------------------------------------

def _write_jpegs(root, n_classes, n_images, side):
    """``n_images`` JPEGs over ``n_classes`` class directories (the label
    set of ParallelImageDataSetIterator is the set of directories, so a
    1000-way head needs 1000 of them), smooth so that they stay small."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(0)
    for i in range(n_images):
        d = os.path.join(root, f"c{i % n_classes:04d}")
        os.makedirs(d, exist_ok=True)
        coarse = rng.integers(0, 255, (7, 7, 3), np.uint8)
        img = Image.fromarray(coarse, "RGB").resize((side, side),
                                                    Image.BILINEAR)
        img.save(os.path.join(d, f"{i}.jpg"), quality=85)


def phase_resnet50_fit(sm):
    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.datasets import (FileSplit,
                                             ParallelImageDataSetIterator)
    from deeplearning4j_tpu.models import ResNet50

    n_classes, side, batch, n_images, workers = sm.size(
        (1000, 224, 256, 1024, 4), (4, 32, 8, 24, 2))
    # built (g++) on first use; loaded here so the forked decode workers
    # inherit it instead of racing to build it
    sm.say(f"  native.available()={native.available()} "
           f"etl_workers={workers}")
    root = os.path.join(sm.out_dir, "jpeg")
    shutil.rmtree(root, ignore_errors=True)
    t = time.perf_counter()
    _write_jpegs(root, n_classes, n_images, side)
    t_jpeg = time.perf_counter() - t
    net = ResNet50(numClasses=n_classes, inputShape=(3, side, side),
                   dataType="bfloat16").init()
    # the worker pool forks from this process, which already holds the chip
    it = ParallelImageDataSetIterator(FileSplit(root), side, side, 3,
                                      batchSize=batch, numWorkers=workers)
    try:
        if it.totalOutcomes() != n_classes:
            raise AssertionError(f"iterator sees {it.totalOutcomes()} "
                                 f"classes, wrote {n_classes}")
        steps = len(it)
        setup, dt, s0, s1 = _fit_twice(lambda: net.fit(it), net,
                                       "ResNet-50 fit")
        # "shm", or "queue" where the host refuses the ring's segment
        transport = it.transport
    finally:
        it.close()
        shutil.rmtree(root, ignore_errors=True)
    sm.say(f"  fit() x{steps} batch={batch} {side}x{side} bf16 over "
           f"{n_images} JPEGs ({t_jpeg:.1f}s to write, batches by "
           f"{transport}): "
           f"setup+first-epoch={setup:.2f}s epoch={dt:.4f}s "
           f"({dt / steps:.4f}s/step, decode included) "
           f"score {s0:.4f} , {s1:.4f}")


# ---------------------------------------------------------------------------
# decode_serve
# ---------------------------------------------------------------------------

def _http(port, path, payload=None, timeout=900):
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if payload is None else json.dumps(payload).encode(),
        method="GET" if payload is None else "POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def _compile_total(port):
    from deeplearning4j_tpu import telemetry

    status, body = _http(port, "/metrics")
    if status != 200:
        raise AssertionError(f"/metrics answered {status}")
    return telemetry.prometheus.parse(body.decode()).get(
        "dl4j_compile_total", 0.0)


def phase_decode_serve(sm):
    import numpy as np

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.serving import (InferenceSession,
                                            TransformerDecodeModel)
    from deeplearning4j_tpu.ui.server import UIServer

    vocab, hidden, layers, heads, max_len, pages = sm.size(
        (30522, 768, 12, 12, 512, 32), (64, 32, 2, 2, 64, 4))
    chunk, new_tokens = sm.size((64, 32), (8, 4))
    lengths = sm.size((64, 128, 192, 256, 320, 384, 100, 250),
                      (8, 16, 24, 32, 40, 48, 12, 30))
    name = "smoke-decoder"
    t = time.perf_counter()
    model = TransformerDecodeModel.init(
        vocab=vocab, hidden=hidden, n_layers=layers, n_heads=heads,
        max_len=max_len, max_slots=16, page=16, max_pages_per_slot=pages)
    session = InferenceSession()
    server = UIServer.getInstance()
    try:
        session.register_decoder(name, model, chunk=chunk,
                                 prefix_cache=True)
        server.serveModels(session).start(port=0)
        port = server.port
        setup = time.perf_counter() - t
        rng = np.random.default_rng(0)
        prompts = [rng.integers(3, vocab, n).tolist() for n in lengths]
        path = f"/serving/v1/models/{name}:decode"

        def post(prompt):
            status, body = _http(port, path, {"prompt": prompt,
                                              "max_new_tokens": new_tokens})
            return status, json.loads(body)["tokens"]

        # one request end to end before the counted window: anything the
        # first real request still has to build is set-up, not steady state
        post(prompts[-1][:chunk])
        compiles0 = _compile_total(port)

        replies = [None] * len(prompts)

        def client(i):
            replies[i] = post(prompts[i])

        # the clients are threads of this process (no JAX in them): a
        # second process could not share the chip
        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"smoke-client-{i}")
                   for i in range(len(prompts))]
        t = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=900)
        wall = time.perf_counter() - t
        for i, reply in enumerate(replies):
            if reply is None:
                raise AssertionError(f"request {i} did not complete")
            status, tokens = reply
            if status != 200 or len(tokens) != new_tokens or not all(
                    isinstance(v, int) and 0 <= v < vocab for v in tokens):
                raise AssertionError(f"request {i}: status {status}, "
                                     f"tokens {tokens}")
        # the same prompts again: now served from the prefix cache, and
        # the ids must not change
        for i in (0, len(prompts) - 3):
            status, tokens = post(prompts[i])
            if status != 200 or tokens != replies[i][1]:
                raise AssertionError(
                    f"repeated request {i} changed its ids: "
                    f"{replies[i][1]} then {tokens}")
        compiles1 = _compile_total(port)
        if compiles1 != compiles0:
            raise AssertionError(
                f"dl4j_compile_total moved {compiles0} -> {compiles1} "
                f"across requests after warm-up")
        bodies = {}
        for probe in ("/healthz", "/metrics", "/debug/memory"):
            status, bodies[probe] = _http(port, probe)
            if status != 200:
                raise AssertionError(f"{probe} answered {status}")
        rows = json.loads(bodies["/debug/memory"])["devices"]
        sources = {label: (row.get("source"), row.get("limit"))
                   for label, row in rows.items() if "source" in row}
        # what the admission-time capacity planner did with the KV pool
        plans = [(e["site"], e["need_bytes"], e["headroom_bytes"], e["fits"])
                 for e in telemetry.flight.get_recorder().events(
                     "capacity_plan")]
        if not sm.dry:
            if not all(src == "memory_stats" and limit
                       for src, limit in sources.values()):
                raise AssertionError(
                    f"/debug/memory is not sourced from memory_stats "
                    f"with a limit: {sources}")
            if not any(site == f"decode:{name}:kv" and headroom and fits
                       for site, _, headroom, fits in plans):
                raise AssertionError(
                    f"the capacity planner did not judge the KV pool "
                    f"against real headroom: {plans}")
    finally:
        server.stop()
        session.close()
    sm.say(f"  {len(prompts)}/{len(prompts)} HTTP 200 x {new_tokens} "
           f"tokens (prompts {min(lengths)}-{max(lengths)}), 2 repeats "
           f"identical, dl4j_compile_total flat at {int(compiles1)}: "
           f"setup={setup:.2f}s requests-wall={wall:.2f}s")
    sm.say(f"  /healthz /metrics /debug/memory 200; /debug/memory "
           f"(source, limit) per device: {sources}; capacity plans "
           f"(site, need, headroom, fits): {plans}")


# ---------------------------------------------------------------------------
# dp4
# ---------------------------------------------------------------------------

def phase_dp4(sm):
    import jax

    from deeplearning4j_tpu.parallel.mesh import (replica_devices,
                                                  shard_batch)
    from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

    n = 4
    trainer = _bert(sm, n_data=n, batch=sm.size(64, 8))
    leaves = jax.tree_util.tree_leaves((trainer.params, trainer.opt))
    short = [leaf.shape for leaf in leaves if len(leaf.devices()) != n]
    if short:
        raise AssertionError(f"BERT params/optimizer state not placed on "
                             f"all {n} devices: {short[:4]}")
    batch = sm.size(64, 8)
    shards = shard_batch(trainer.mesh, jax.numpy.zeros(
        (batch, trainer.cfg.max_len), jax.numpy.int32)).addressable_shards
    where = {s.device.id for s in shards}
    if len(where) != n or {s.data.shape[0] for s in shards} != {batch // n}:
        raise AssertionError(
            f"batch shards: devices {sorted(where)}, "
            f"rows {[s.data.shape[0] for s in shards]}")
    replicas = {d.id for d in replica_devices(n, mesh=trainer.mesh)}
    if replicas != where:
        raise AssertionError(f"replica_devices {sorted(replicas)} != "
                             f"mesh devices {sorted(where)}")
    sm.say(f"  BERT state on {n} devices; batch shards of {batch // n} "
           f"rows on devices {sorted(where)}; replica_devices agrees")
    del trainer, leaves

    net, vocab, seq = _lstm_net(sm)
    batch = sm.size(4096, 32)
    data = _char_batches(vocab, seq, batch, 3)
    wrapper = ParallelWrapper.Builder(net).workers(n).build()
    routes_before = _route_counts("LSTM")
    setup, dt, s0, s1 = _fit_twice(lambda: wrapper.fit(data), net,
                                   "sharded LSTM")
    sm.say(f"  ParallelWrapper.fit() x3 batch={batch} over {n} devices: "
           f"setup+first={setup:.2f}s fit-of-3={dt:.4f}s "
           f"({dt / 3:.4f}s/step) score {s0:.4f} -> {s1:.4f}")
    if not s1 < s0:
        raise AssertionError(f"sharded LSTM score did not fall: "
                             f"{s0} -> {s1}")
    _check_kernel_in_step(sm, "sharded", batch // n, routes_before)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:n]}
    sm.say(f"  bytes_in_use per device: {in_use}")
    if not sm.dry and not all(in_use.values()):
        raise AssertionError(f"a device holds no memory: {in_use}")


# ---------------------------------------------------------------------------

PHASES = {"bert_train": phase_bert_train, "kernels": phase_kernels,
          "resnet50_fit": phase_resnet50_fit,
          "decode_serve": phase_decode_serve, "dp4": phase_dp4}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="",
                    help="comma-separated phases: " + ",".join(PHASES))
    ap.add_argument("--require-chips", type=int, default=1,
                    help="fail unless at least this many devices are visible")
    ap.add_argument("--dry-cpu", action="store_true",
                    help="toy widths on the CPU, Pallas interpreted; every "
                         "line is marked and the run is never a chip result")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for generated inputs")
    args = ap.parse_args(argv)
    only = [p for p in args.only.split(",") if p]
    unknown = [p for p in only if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase {unknown}; choose from {list(PHASES)}")

    if args.dry_cpu:
        # before jax initialises: 4 host devices for dp4, the Pallas
        # interpreter in place of Mosaic
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        os.environ["DL4J_PALLAS_INTERPRET"] = "1"

    import jax
    import jaxlib

    sm = Smoke(args.dry_cpu, args.out)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    sm.say(f"device: platform={device['platform']} "
           f"device_kind={device['kind']!r} count={device['count']} | "
           f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
           f"libtpu {libtpu}")
    if not args.dry_cpu and dev.platform != "tpu":
        print(f"chip_smoke: jax resolved to {dev.platform!r}, not a TPU; "
              f"nothing was built (--dry-cpu debugs the code path on the "
              f"CPU)", file=sys.stderr)
        return 2
    if device["count"] < args.require_chips:
        print(f"chip_smoke: --require-chips {args.require_chips} but "
              f"{device['count']} device(s) visible", file=sys.stderr)
        return 2

    from deeplearning4j_tpu.runtime import RuntimeConfig

    cache_dir = RuntimeConfig.enable_compile_cache()
    sm.say(f"compile cache: {cache_dir} "
           f"(JAX_COMPILATION_CACHE_DIR "
           f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    jax.monitoring.register_event_duration_secs_listener(sm.on_duration)
    jax.monitoring.register_event_listener(sm.on_event)
    os.makedirs(sm.out_dir, exist_ok=True)

    phases = only or [p for p in PHASES
                      if p != "dp4" or device["count"] >= 4]
    if "dp4" in phases and device["count"] < 4:
        print(f"chip_smoke: dp4 needs 4 devices, {device['count']} visible",
              file=sys.stderr)
        return 2
    t_all = time.perf_counter()
    for name in phases:
        sm.say(f"[{name}] start")
        c0, h0, t0 = sm.compiles, sm.cache_hits, time.perf_counter()
        PHASES[name](sm)    # a phase that fails raises
        sm.say(f"[{name}] PASS wall={time.perf_counter() - t0:.1f}s "
               f"backend_compiles={sm.compiles - c0} "
               f"persistent_cache_hits={sm.cache_hits - h0} "
               f"peak_bytes_in_use={_peak_bytes()}")
    sm.say(f"all phases passed: {','.join(phases)} "
           f"wall={time.perf_counter() - t_all:.1f}s")
    sm.say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
