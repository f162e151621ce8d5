#!/usr/bin/env python
"""hloaudit: the per-model XLA fusion/remat audit CLI (ISSUE 11).

AOT-lowers and compiles a flagship model's train step (and optionally
its inference function), runs ``telemetry.hlo_audit`` over the
optimized HLO, registers the executable in the compile ledger (site
``hloaudit:<model>``), and prints the structural report ROADMAP item 4
asks for: fusion count, unfused dot/conv ops, collective ops, remat
markers, and the largest buffers. Committed findings live in
docs/HLO_AUDIT.md.

Usage::

    python tools/hloaudit.py --model resnet50 [--batch 8]
    python tools/hloaudit.py --model bert --batch 4 --seq 128
    python tools/hloaudit.py --models resnet50,bert,graves_lstm --json out.json

Models: mlp (smoke), resnet50, bert, graves_lstm. Nothing here touches
the serving/training hot paths — the lower+compile happens in this
process only (jax caches it, so re-running is cheap), and the audit is
a pure text parse.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ones_like_batch(net, f, l):
    import numpy as np

    lmask = np.ones(l.shape[:1] + (() if l.ndim == 2 else (l.shape[2],)),
                    np.float32)
    return lmask


def audit_network(net, f, l, mode="train"):
    """Audit a MultiLayerNetwork's or ComputationGraph's compiled
    step/inference executable against one synthetic batch."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.telemetry import hlo_audit

    is_graph = type(net).__name__ == "ComputationGraph"
    if mode == "infer":
        if is_graph:
            raise SystemExit("--mode infer supports sequential nets only")
        fn = net._infer_fn(False)
        args = (net._params, net._states, np.asarray(f))
    else:
        net._refresh_train_step()
        fn = net._train_step
        rng = jax.random.key(net.conf.seed + 1)
        if is_graph:
            inputs, labels, masks = net._feeds((f, l),
                                               with_ones_masks=True)
            args = (net._params, net._states, net._opt_states,
                    net._prec_state, inputs, labels, masks, rng, 0)
        else:
            lmask = _ones_like_batch(net, f, l)
            args = (net._params, net._states, net._opt_states,
                    net._prec_state, f, l, lmask, rng, 0)
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    out = hlo_audit.audit_compiled(compiled)
    out["compile_seconds"] = round(dt, 3)
    return out, compiled, args


def build_mlp(batch):
    import numpy as np

    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)

    conf = (NeuralNetConfiguration.Builder().seed(7).list()
            .layer(DenseLayer.Builder().nIn(128).nOut(256)
                   .activation("relu").build())
            .layer(OutputLayer.Builder().nOut(10).activation("softmax")
                   .lossFunction(LossFunction.MCXENT).build())
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    f = rng.normal(size=(batch, 128)).astype(np.float32)
    l = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    return net, f, l


def build_resnet50(batch):
    import numpy as np

    from deeplearning4j_tpu.models.zoo import ResNet50

    net = ResNet50(numClasses=1000, dataType="bfloat16").init()
    rng = np.random.default_rng(0)
    f = rng.normal(size=(batch, 3, 224, 224)).astype(np.float32)
    l = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    return net, f, l


def build_graves_lstm(batch, seq=50, vocab=77):
    import numpy as np

    from deeplearning4j_tpu.models.zoo import TextGenerationLSTM

    net = TextGenerationLSTM(vocabSize=vocab, hidden=256,
                             seqLength=seq).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq + 1))
    f = np.eye(vocab, dtype=np.float32)[ids[:, :-1]].transpose(0, 2, 1)
    l = np.eye(vocab, dtype=np.float32)[ids[:, 1:]].transpose(0, 2, 1)
    return net, f, l


def audit_bert(batch, seq):
    """BERT-base MLM train step through BertTrainer's own jitted step
    (single-device mesh): the `jit_step` that the benchmark's
    `bert-large-mlm.train-16x512` cell times, at BERT-base's sizes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.bert import (
        BertConfig, BertTrainer, mlm_gather, synthetic_mlm_batch)
    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.telemetry import hlo_audit

    cfg = BertConfig(vocab_size=30522, hidden=768, num_layers=12,
                     num_heads=12, ffn=3072, max_len=512)
    mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
    trainer = BertTrainer(cfg, mesh, lr=1e-4)
    tokens, labels = synthetic_mlm_batch(cfg, batch, seq, seed=0)
    positions, mlm_labels, weights = mlm_gather(
        labels, max_preds=trainer._max_preds(seq))
    rng = jax.random.key(1, impl="rbg")
    fn = trainer._build()
    args = (trainer.params, trainer.opt, jnp.asarray(tokens, jnp.int32),
            positions, mlm_labels, weights, rng,
            jnp.asarray(0, jnp.int32))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    out = hlo_audit.audit_compiled(compiled)
    out["compile_seconds"] = round(dt, 3)
    out["config"] = {"batch": batch, "seq": seq, "layers": cfg.num_layers,
                     "hidden": cfg.hidden}
    return out, compiled, args


def _ledger(model, compiled, args, seconds):
    """Register the audited executable in the process compile ledger so
    the CLI workflow and the live /debug/compiles view agree."""
    try:
        import jax

        from deeplearning4j_tpu.telemetry import compile_ledger

        leaves = jax.tree_util.tree_leaves(args)
        sig = tuple((tuple(getattr(x, "shape", ())),
                     str(getattr(x, "dtype", type(x).__name__)))
                    for x in leaves)
        compile_ledger.record_executable(
            f"hloaudit:{model}", compiled, sig, seconds=seconds,
            bucketed=False)
    except Exception as e:  # the report matters more than the ledger row
        print(f"[hloaudit] ledger registration failed: {e}",
              file=sys.stderr)


def run_model(model, batch, seq, mode):
    if model == "bert":
        out, compiled, args = audit_bert(batch or 4, seq or 128)
    else:
        builders = {"mlp": build_mlp, "resnet50": build_resnet50,
                    "graves_lstm": build_graves_lstm}
        if model not in builders:
            raise SystemExit(
                f"unknown model {model!r}; choose from "
                f"{sorted(builders) + ['bert']}")
        if model == "graves_lstm":
            net, f, l = build_graves_lstm(batch or 32, seq or 50)
        else:
            net, f, l = builders[model](batch or 8)
        out, compiled, args = audit_network(net, f, l, mode=mode)
        out["config"] = {"batch": int(f.shape[0]), "mode": mode}
    _ledger(model, compiled, args, out.get("compile_seconds"))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default=None)
    ap.add_argument("--models", default=None,
                    help="comma-separated list (one combined report)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--mode", default="train",
                    choices=("train", "infer"))
    ap.add_argument("--json", default=None,
                    help="also write the report to this path")
    args = ap.parse_args(argv)
    names = ([m.strip() for m in args.models.split(",") if m.strip()]
             if args.models else [args.model or "mlp"])
    report = {}
    for name in names:
        print(f"[hloaudit] compiling + auditing {name} ...",
              file=sys.stderr)
        report[name] = run_model(name, args.batch, args.seq, args.mode)
    print(json.dumps(report, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
