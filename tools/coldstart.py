#!/usr/bin/env python
"""coldstart: cold vs warm process-start report for the persistent
executable store (ISSUE 13).

Every trial is a REAL process restart (subprocess), not an in-process
re-register — in-process numbers flatter the warm path because jit
tracing caches, weight-init executables, and the jax runtime are
already live. Two sites are measured:

- **serving**: register a 3-bucket servable ladder with warmup; the
  timed window is the `register(..., warmup=True)` call;
- **resume**: a Supervisor kill-and-resume — one child trains under a
  Supervisor and exits (the "kill"), the next child builds the same
  Supervisor over the same checkpoint dir and runs to the total epoch
  budget; the timed window is `sup.run(...)`.

Each site runs cold (empty store) then warm (the store the cold run
populated). Zero-XLA-compile warm starts are asserted through the
compile ledger (causes all `cache_hit`) and the `dl4j_compile_total`
delta — not timing.

Usage::

    python tools/coldstart.py                 # tmp store, full report
    python tools/coldstart.py --store DIR     # inspect/extend a store
    python tools/coldstart.py --json          # machine-readable report
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# compile-heavy enough that the XLA ladder dominates a cold start (a
# production model compiles for seconds; this one for hundreds of ms),
# small enough for CI: 8x384 MLP, 3 serving buckets, short supervised
# fit
WIDTH, DEPTH, NIN, NOUT = 384, 8, 64, 8
BUCKETS = (1, 8, 32)
TRAIN_STEPS_PER_EPOCH, TRAIN_EPOCHS = 4, 2


def _build_net(seed=7):
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    b = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-3))
         .list())
    b = b.layer(DenseLayer.Builder().nIn(NIN).nOut(WIDTH)
                .activation("tanh").build())
    for _ in range(DEPTH - 2):
        b = b.layer(DenseLayer.Builder().nOut(WIDTH)
                    .activation("tanh").build())
    b = b.layer(OutputLayer.Builder().nOut(NOUT).activation("softmax")
                .lossFunction(LossFunction.MCXENT).build())
    return MultiLayerNetwork(b.build()).init()


def _train_data():
    import numpy as np

    rng = np.random.default_rng(0)
    X = rng.normal(size=(TRAIN_STEPS_PER_EPOCH * 16, NIN)).astype(
        np.float32)
    y = np.eye(NOUT, dtype=np.float32)[
        rng.integers(0, NOUT, len(X))]
    return [(X[i:i + 16], y[i:i + 16])
            for i in range(0, len(X), 16)]


def _compile_total():
    from deeplearning4j_tpu import telemetry

    try:
        return float(telemetry.get_registry()
                     .counter("dl4j_compile_total").value)
    except Exception:
        return 0.0


def _store_modes():
    """{mode: total_seconds} from the dl4j_compile_seconds histogram."""
    from deeplearning4j_tpu import telemetry

    out = {}
    try:
        fam = telemetry.get_registry().histogram(
            "dl4j_compile_seconds", labelnames=("mode",))
        for key, hist in fam.children():
            mode = dict(key).get("mode", "?")
            out[mode] = round(out.get(mode, 0.0) + hist.sum, 6)
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# child trials (each runs in its own interpreter)
# ---------------------------------------------------------------------------

def child_serving():
    from deeplearning4j_tpu import compilestore, telemetry
    from deeplearning4j_tpu.serving import BucketLadder, InferenceSession
    from deeplearning4j_tpu.telemetry import compile_ledger

    telemetry.enable()
    # session first: its store touch starts the code-epoch sweep in
    # the background while the net builds
    session = InferenceSession()
    net = _build_net()
    c0 = _compile_total()
    t0 = time.perf_counter()
    session.register("coldstart", net, example_shape=(NIN,),
                     ladder=BucketLadder(BUCKETS), warmup=True)
    seconds = time.perf_counter() - t0
    causes = compile_ledger.get_ledger().causes("coldstart:v1")
    out = {
        "register_seconds": round(seconds, 4),
        "compiles": _compile_total() - c0,
        "causes": causes,
        "modes": _store_modes(),
        "store": compilestore.describe(),
    }
    session.close()
    return out


def _supervisor(ckpt_dir):
    from deeplearning4j_tpu.resilience import Supervisor, SupervisorConfig

    return Supervisor(_build_net, ckpt_dir,
                      config=SupervisorConfig(max_restarts=1),
                      everyNIterations=2)


def child_train(ckpt_dir):
    """The pre-kill half: supervised fit for ONE epoch of the total
    budget, then exit — the process death IS the kill."""
    from deeplearning4j_tpu import telemetry

    telemetry.enable()
    sup = _supervisor(ckpt_dir)
    t0 = time.perf_counter()
    sup.run(_train_data(), epochs=1)
    return {"train_seconds": round(time.perf_counter() - t0, 4)}


def child_resume(ckpt_dir):
    """The post-kill half: the same Supervisor over the same checkpoint
    dir runs the REMAINING budget; the ledger says whether its train
    step compiled or deserialized."""
    from deeplearning4j_tpu import compilestore, telemetry
    from deeplearning4j_tpu.telemetry import compile_ledger

    telemetry.enable()
    sup = _supervisor(ckpt_dir)
    c0 = _compile_total()
    t0 = time.perf_counter()
    net = sup.run(_train_data(), epochs=TRAIN_EPOCHS)
    seconds = time.perf_counter() - t0
    import numpy as np

    return {
        "resume_seconds": round(seconds, 4),
        "compiles": _compile_total() - c0,
        "fit_causes": compile_ledger.get_ledger().causes("fit"),
        "modes": _store_modes(),
        "iteration": net._iteration,
        "params_sha": __import__("hashlib").sha256(
            np.ascontiguousarray(
                net.params().toNumpy()).tobytes()).hexdigest()[:16],
        "store": compilestore.describe(),
    }


CHILDREN = {"serving": child_serving, "train": child_train,
            "resume": child_resume}


def run_child(kind, store_dir, ckpt_dir=None, timeout=600):
    """Spawn one trial in a fresh interpreter; returns its JSON row."""
    env = dict(os.environ)
    env["DL4J_EXECUTABLE_STORE"] = store_dir
    # hard-pin children to the host platform: a parent holding the
    # chip cannot hand it to subprocesses — inheriting a JAX_PLATFORMS=tpu
    # would crash the trials or mislabel chip numbers as cpu
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--child", kind]
    if ckpt_dir:
        cmd += ["--ckpt", ckpt_dir]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"coldstart child {kind} failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_report(store_dir=None, ckpt_dir=None):
    """The full cold/warm matrix. Returns the report dict."""
    tmp = tempfile.TemporaryDirectory(prefix="dl4j-coldstart-")
    try:
        if store_dir is None:
            store_dir = os.path.join(tmp.name, "store")
        if ckpt_dir is None:
            ckpt_dir = os.path.join(tmp.name, "ckpt")
        serving_cold = run_child("serving", store_dir)
        serving_warm = run_child("serving", store_dir)
        run_child("train", store_dir, ckpt_dir)
        # each resume gets its OWN copy of the post-kill checkpoint: a
        # resume RUNS the remaining epoch budget and checkpoints, so
        # sharing the dir would leave the second trial nothing to do.
        # Copies live under the tmp root (cleaned up on exit; a
        # caller-supplied --ckpt dir is never written beside)
        import shutil

        warm_ckpt = os.path.join(tmp.name, "ckpt-warm")
        cold_ckpt = os.path.join(tmp.name, "ckpt-cold")
        shutil.copytree(ckpt_dir, warm_ckpt)
        shutil.copytree(ckpt_dir, cold_ckpt)
        # warm resume: store was populated by the train child
        resume_warm = run_child("resume", store_dir, warm_ckpt)
        # cold resume: same checkpoint, EMPTY store (a sibling dir —
        # never inside the warm root, its entries must not pollute the
        # report's store listing) — what a restart cost before ISSUE 13
        cold_store = os.path.join(tmp.name, "cold-store")
        resume_cold = run_child("resume", cold_store, cold_ckpt)
        from deeplearning4j_tpu.compilestore import ExecutableStore

        report = {
            "serving": {"cold": serving_cold, "warm": serving_warm,
                        "speedup": round(
                            serving_cold["register_seconds"]
                            / max(serving_warm["register_seconds"],
                                  1e-9), 2)},
            "resume": {"cold": resume_cold, "warm": resume_warm,
                       "speedup": round(
                           resume_cold["resume_seconds"]
                           / max(resume_warm["resume_seconds"],
                                 1e-9), 2)},
            "store_contents": ExecutableStore(store_dir).contents(),
        }
        return report
    finally:
        if tmp is not None:
            tmp.cleanup()


def _print_report(report):
    s = report["serving"]
    r = report["resume"]
    print("== serving: 3-bucket registration (fresh process each) ==")
    print(f"  cold: {s['cold']['register_seconds']:.3f}s "
          f"({s['cold']['compiles']:.0f} XLA compiles, "
          f"causes {s['cold']['causes']})")
    print(f"  warm: {s['warm']['register_seconds']:.3f}s "
          f"({s['warm']['compiles']:.0f} XLA compiles, "
          f"causes {s['warm']['causes']})")
    print(f"  speedup: {s['speedup']}x")
    print("== supervisor kill-and-resume ==")
    print(f"  cold store: {r['cold']['resume_seconds']:.3f}s "
          f"({r['cold']['compiles']:.0f} XLA compiles, "
          f"fit causes {r['cold']['fit_causes']})")
    print(f"  warm store: {r['warm']['resume_seconds']:.3f}s "
          f"({r['warm']['compiles']:.0f} XLA compiles, "
          f"fit causes {r['warm']['fit_causes']})")
    print(f"  speedup: {r['speedup']}x  params_sha "
          f"{r['warm']['params_sha']} "
          f"(== cold: {r['warm']['params_sha'] == r['cold']['params_sha']})")
    print("== store contents ==")
    for row in report["store_contents"]:
        print(f"  {row['key'][:16]}...  {row['bytes']:>8} B  "
              f"site={row.get('site')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help="internal: run one trial in this process")
    ap.add_argument("--store", help="store dir (default: fresh tmp)")
    ap.add_argument("--ckpt", help="checkpoint dir (resume trials)")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON")
    args = ap.parse_args(argv)
    if args.child:
        fn = CHILDREN[args.child]
        out = fn(args.ckpt) if args.child in ("train", "resume") \
            else fn()
        print(json.dumps(out))
        return 0
    report = run_report(store_dir=args.store, ckpt_dir=args.ckpt)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        _print_report(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
