"""Fleet worker: one serving process of the fleet tier (ISSUE 15).

A worker is deliberately nothing new — a full :class:`InferenceSession`
behind a full :class:`UIServer`, exactly the single-process stack every
prior PR built, plus two fleet seams:

- **spec-built models**: the router (and its rollouts) cannot ship live
  Python objects across the process boundary, so models arrive as JSON
  specs and :func:`build_servable` turns a spec into a servable in the
  worker process. ``kind: "mlp"`` builds a real jitted
  MultiLayerNetwork (cold start hits the PR-13 compile store);
  ``kind: "linear"`` is the deterministic host-side stand-in the fleet
  tests and the router-overhead bench lean on (y = scale·x + bias,
  optional injected service delay — the knob a deliberately-regressed
  canary uses); ``kind: "sharded"`` (ISSUE 19) builds a GSPMD
  mesh-partitioned servable over ``model_parallel`` of the worker's
  devices (spec key ``host_devices`` forces N virtual CPU devices at
  process start), serving models bigger than one device behind the
  same router, health polling, and canary machinery;
- **the admin surface**: :class:`WorkerAdmin` exposes the versioned
  re-register seam (``POST /serving/v1/models/<name>:register`` /
  ``:unregister`` on the worker's UIServer, serving/http.py) that
  rolling updates push vN+1 specs through and rollbacks retract them.

Run one with::

    python -m deeplearning4j_tpu.fleet.worker \
        --spec spec.json --port 0 --port-file /tmp/w0.port

The worker writes its bound port to ``--port-file`` (tmp + rename, so a
reader never sees a half-written file) once the server is up, then
serves until SIGTERM/SIGINT.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import threading
import time

import numpy as np

from deeplearning4j_tpu.serving.servable import Servable, as_servable

log = logging.getLogger("deeplearning4j_tpu")


class LinearServable(Servable):
    """Deterministic host-side servable: ``y = scale * x + bias`` in
    float32, with an optional per-dispatch service delay. No device
    work, no compile — which makes it exactly the model the fleet tier
    wants for measuring its OWN overhead (the router hop must be
    measured against a ~free model, PAPERS.md off-math-path rule) and
    for bit-identical canary agreement checks across processes."""

    def __init__(self, example_shape=(4,), scale=1.0, bias=0.0,
                 delay_ms=0.0):
        super().__init__(example_shape, dtype=np.float32)
        self.scale = float(scale)
        self.bias = float(bias)
        self.delay_s = float(delay_ms) / 1e3

    def warmup(self, ladder):
        return []   # nothing to compile

    def infer(self, x):
        if self.delay_s:
            time.sleep(self.delay_s)
        x = np.ascontiguousarray(x, dtype=np.float32)
        return (x * np.float32(self.scale)
                + np.float32(self.bias)).astype(np.float32)


def _build_linear(spec):
    return LinearServable(
        example_shape=tuple(spec.get("example_shape", (4,))),
        scale=spec.get("scale", 1.0), bias=spec.get("bias", 0.0),
        delay_ms=spec.get("delay_ms", 0.0))


def _build_mlp(spec):
    """A real jitted network (the production worker path — its cold
    warmup exercises the PR-13 executable store end to end)."""
    from deeplearning4j_tpu.nn import (
        DenseLayer, LossFunction, MultiLayerNetwork,
        NeuralNetConfiguration, OutputLayer)

    n_in = int(spec.get("n_in", 8))
    n_out = int(spec.get("n_out", 4))
    width = int(spec.get("width", 16))
    b = (NeuralNetConfiguration.Builder().seed(int(spec.get("seed", 7)))
         .list()
         .layer(DenseLayer.Builder().nIn(n_in).nOut(width)
                .activation("tanh").build())
         .layer(OutputLayer.Builder().nOut(n_out).activation("softmax")
                .lossFunction(LossFunction.MCXENT).build()))
    net = MultiLayerNetwork(b.build()).init()
    return as_servable(net, (n_in,), None)


def _build_sharded(spec):
    """A GSPMD mesh-sharded servable (ISSUE 19): a column-parallel MLP
    partitioned over ``model_parallel`` devices. The worker process
    builds its own mesh from its own visible devices — on CPU the spec
    sets ``host_devices`` and main() forces the virtual device count
    BEFORE the first backend touch. Bit-identical to the ``mlp``-style
    single-device reference by construction (serving/sharded.py), so
    canary agreement checks work across sharded and unsharded groups."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.serving.sharded import sharded_mlp_servable

    tp = int(spec.get("model_parallel", 2))
    devices = jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"sharded spec wants model_parallel={tp} but the worker "
            f"sees only {len(devices)} device(s); set host_devices in "
            f"the spec (CPU) or run on a bigger slice")
    mesh = MeshConfig(data=1, model=tp, devices=devices[:tp]).build()
    sizes = tuple(int(s) for s in spec.get(
        "sizes", (int(spec.get("n_in", 8)), int(spec.get("width", 32)),
                  int(spec.get("n_out", 4)))))
    return sharded_mlp_servable(
        mesh, sizes, example_shape=(sizes[0],),
        seed=int(spec.get("seed", 7)),
        batch_axis=spec.get("batch_axis"))


def _build_from_checkpoint(spec):
    """ISSUE 20: serve a trained checkpoint — the fleet fine-tuner's
    publish seam. ``checkpoint`` names a ModelSerializer zip (or a
    sharded checkpoint directory); ``checkpoint_dir`` picks the newest
    COMPLETE checkpoint in an ElasticTrainer directory instead. The
    restored net warms through the PR-13 compile store exactly like an
    ``mlp`` spec (NetworkServable's program digest is the net's own
    conf), so a fine-tuned canary costs zero XLA compiles on a warm
    host."""
    from deeplearning4j_tpu.parallel.elastic import ElasticTrainer
    from deeplearning4j_tpu.utils.serializer import ModelSerializer

    path = spec.get("checkpoint")
    if path is None:
        cdir = spec.get("checkpoint_dir")
        if not cdir:
            raise ValueError('from_checkpoint spec needs "checkpoint" '
                             '(a zip / sharded dir) or "checkpoint_dir"'
                             ' (an ElasticTrainer directory)')
        path = ElasticTrainer.latest_agreed(cdir)
        if path is None:
            raise ValueError(f"no complete checkpoint under {cdir!r}")
    if not os.path.exists(path):
        raise ValueError(f"checkpoint {path!r} does not exist")
    # the updater is training state — a servable only needs params
    net = ModelSerializer.restoreMultiLayerNetwork(
        path, loadUpdater=False, sharded=os.path.isdir(path))
    shape = tuple(int(s) for s in spec.get("example_shape", ())) or None
    return as_servable(net, shape, None)


def _build_decoder(spec):
    """A seeded paged-KV transformer decode model (ISSUE 20 decode
    mirroring): identical seeds build bit-identical params in every
    worker process, and greedy decode is argmax — so a canary's token
    streams match the incumbent's EXACTLY unless the weights differ,
    which is the agreement oracle decode rollouts judge on."""
    from deeplearning4j_tpu.serving.decode import TransformerDecodeModel

    return TransformerDecodeModel.init(
        vocab=int(spec.get("vocab", 32)),
        hidden=int(spec.get("hidden", 16)),
        n_layers=int(spec.get("n_layers", 1)),
        n_heads=int(spec.get("n_heads", 2)),
        max_len=int(spec.get("max_len", 64)),
        seed=int(spec.get("seed", 0)),
        max_slots=int(spec.get("max_slots", 4)),
        page=int(spec.get("page", 8)),
        max_pages_per_slot=int(spec.get("max_pages_per_slot", 8)))


SPEC_BUILDERS = {"linear": _build_linear, "mlp": _build_mlp,
                 "sharded": _build_sharded,
                 "from_checkpoint": _build_from_checkpoint}

# decoder specs register through session.register_decoder (continuous
# batching engine) instead of the versioned predict registry
DECODER_SPEC_BUILDERS = {"decoder": _build_decoder}


def build_servable(spec) -> Servable:
    """A Servable from a JSON-able spec dict: ``{"kind": ..., ...}``.
    Raises ValueError on an unknown kind (HTTP 400 at the admin
    route)."""
    if not isinstance(spec, dict):
        raise ValueError(f"model spec must be a dict, got {type(spec)}")
    kind = spec.get("kind")
    builder = SPEC_BUILDERS.get(kind)
    if builder is None:
        raise ValueError(
            f"unknown model-spec kind {kind!r}; choose from "
            f"{sorted(SPEC_BUILDERS) + sorted(DECODER_SPEC_BUILDERS)}")
    return builder(spec)


class WorkerAdmin:
    """The worker-side half of the rollout seam: registers/unregisters
    spec-built model versions on the worker's InferenceSession.
    Attached to a UIServer via ``serveFleetAdmin`` — the router's
    RolloutController talks to it over
    ``POST /serving/v1/models/<name>:register`` / ``:unregister``."""

    def __init__(self, session):
        self.session = session

    def register_spec(self, name, spec, version, warmup=True):
        if isinstance(spec, dict) and \
                spec.get("kind") in DECODER_SPEC_BUILDERS:
            return self._register_decoder(name, spec, version,
                                          warmup=warmup)
        sv = build_servable(spec)
        kw = {}
        ladder = spec.get("ladder")
        if ladder:
            kw["ladder"] = tuple(int(b) for b in ladder)
        return self.session.register(name, sv, version=int(version),
                                     warmup=bool(warmup), **kw)

    def _register_decoder(self, name, spec, version, warmup=True):
        """Decoder specs (ISSUE 20 decode mirroring) attach a
        continuous-batching DecodeEngine under ``name`` — decoders are
        UNVERSIONED in the session, so rollouts canary them under an
        alias name (``m@v2``) and promotion re-registers the bare name
        (see fleet/rollout.py). Returns a registry-entry-shaped result
        for the :register route's response."""
        import types

        model = DECODER_SPEC_BUILDERS[spec["kind"]](spec)
        kw = {}
        if spec.get("chunk"):
            kw["chunk"] = int(spec["chunk"])
        engine = self.session.register_decoder(
            name, model, warmup=bool(warmup), **kw)
        return types.SimpleNamespace(version=int(version),
                                     warmed=engine._warmed)

    def unregister(self, name, version=None):
        if name in self.session._decoders:
            self.session.unregister_decoder(name)
            return
        self.session.registry.unregister(
            name, None if version is None else int(version))


def _write_port_file(path, port):
    """Commit the bound port via tmp + rename: the spawner polls this
    file and must never read a torn value."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(str(int(port)))
    os.replace(tmp, path)


def serve(spec, port=0, port_file=None, max_latency=0.0,
          admission_budget=None, stop_event=None):
    """Build the session from ``spec`` and serve until ``stop_event``
    is set (the testable core of main()). Returns the UIServer."""
    from deeplearning4j_tpu.serving import (
        AdmissionController, InferenceSession)
    from deeplearning4j_tpu.ui.server import UIServer

    admission = (None if admission_budget is None
                 else AdmissionController(default_budget=admission_budget))
    session = InferenceSession(max_latency=max_latency,
                               admission=admission)
    admin = WorkerAdmin(session)
    for m in spec.get("models", ()):
        admin.register_spec(m["name"], m, m.get("version", 1),
                            warmup=m.get("warmup", True))
    # fleet-wide SLOs (ISSUE 16): the spec can declare objectives and
    # tune the always-on time-series sampler — evaluation ticks ride
    # the sampler thread, breaches surface in the worker's /healthz
    # (degraded-not-503) and flight ring, which the router federates
    from deeplearning4j_tpu.telemetry import slo as slo_mod
    from deeplearning4j_tpu.telemetry import timeseries

    ts_spec = spec.get("timeseries") or {}
    timeseries.configure(
        interval=ts_spec.get("interval"),
        capacity=ts_spec.get("capacity"))
    for s in spec.get("slos", ()):
        slo_mod.declare(slo_mod.Slo(**s))
    timeseries.start()
    # continuous profiler (ISSUE 18): every worker samples its own
    # threads so the router's /debug/fleet/profile merge has per-worker
    # collapsed stacks to federate; spec-tunable, no-op (zero sampler
    # thread) while telemetry is disabled
    from deeplearning4j_tpu.telemetry import profiler

    prof_spec = spec.get("profiler") or {}
    profiler.configure(hz=prof_spec.get("hz"),
                       bucket_seconds=prof_spec.get("bucket_seconds"),
                       capacity=prof_spec.get("capacity"))
    profiler.start()
    # a fresh UIServer instance per worker process — the getInstance()
    # singleton is a same-process convenience the fleet must not share
    server = UIServer()
    server.serveModels(session).serveFleetAdmin(admin).start(port=port)
    if port_file:
        _write_port_file(port_file, server.port)
    log.info("fleet worker pid=%d serving on port %d", os.getpid(),
             server.port)
    if stop_event is not None:
        stop_event.wait()
        profiler.stop()
        timeseries.stop()
        server.stop()
        session.close()
    return server


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="fleet worker: UIServer + InferenceSession from a "
                    "JSON model spec")
    p.add_argument("--spec", required=True,
                   help="JSON file: {\"models\": [{name, version, "
                        "kind, ...}]}")
    p.add_argument("--port", type=int, default=0,
                   help="bind port (0 = OS-assigned)")
    p.add_argument("--port-file", default=None,
                   help="write the bound port here once serving")
    p.add_argument("--max-latency", type=float, default=0.0,
                   help="batcher coalescing window (seconds)")
    p.add_argument("--admission-budget", type=int, default=None,
                   help="attach an AdmissionController with this "
                        "per-model concurrency budget")
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    # sharded workers on CPU (ISSUE 19): the spec can force N virtual
    # host devices for the mesh. XLA reads XLA_FLAGS lazily at first
    # backend init, and nothing above this line touches a device — so
    # setting it here (before serve() builds any servable) is in time.
    # A pre-set force (test harness, operator) wins over the spec.
    n_dev = spec.get("host_devices")
    flags = os.environ.get("XLA_FLAGS", "")
    if n_dev and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{int(n_dev)}").strip()
    from deeplearning4j_tpu.runtime import RuntimeConfig

    RuntimeConfig.enable_compile_cache()
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    serve(spec, port=args.port, port_file=args.port_file,
          max_latency=args.max_latency,
          admission_budget=args.admission_budget, stop_event=stop)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
