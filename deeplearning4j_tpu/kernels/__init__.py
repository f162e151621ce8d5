"""In-repo Pallas TPU kernels — the custom-call tier SURVEY.md §7
reserves for ops where generic XLA lowering demonstrably misses
(reference analog: libnd4j's platform-helper kernels, e.g. the cuDNN
LSTM path). Each kernel ships with an XLA fallback and parity tests.

This module also owns the ROUTING decision between a kernel and its
``lax.scan`` fallback (:func:`recurrence_route`), so that the choice is
made in one place and is never silent: every traced ``lstmLayer`` /
``gruLayer`` call is counted in ``dl4j_recurrence_route_total{op,route}``,
and every traced token step of a paged decode model that has a kernel for
its attention (:func:`decode_attention_route`; `latent_attention.py`) in
``dl4j_decode_attention_route_total{model,route}``, and what writes that
step's new rows into its pool (:func:`decode_pool_write`) in
``dl4j_decode_pool_write_total{model,route}``. `stream_maps.py` (the
maps of a residual path of several streams) is taken by
`models/causal_lm.py:stream_maps` where `_on_tpu()` says so and the
positions fill whole lanes; the trace names its calls `stream_maps`.
"""

import contextlib
import os
import threading

from deeplearning4j_tpu.kernels.lstm import (  # noqa: F401
    lstm_seq, lstm_seq_available)

ROUTE_HELP = ("Traced lstmLayer/gruLayer calls by the implementation "
              "the recurrence was routed to (pallas = compiled Mosaic "
              "kernel, interpret = the same kernel under the Pallas "
              "interpreter, scan = the lax.scan lowering). Counted at "
              "trace time: once per compiled executable, not per step")

DECODE_ROUTE_HELP = ("Traced token steps of a paged decode model by what "
                     "reads the pool in its attention (kernel = the "
                     "compiled Pallas paged-attention kernel, every live "
                     "page read once where it lies; loop = the XLA loop "
                     "over gathered chunks of live pages). Counted at "
                     "trace time: once per traced executable, not per step")

POOL_WRITE_HELP = ("Traced token steps of a paged decode model by what "
                   "writes the step's new rows into its pool (kernel = the "
                   "paged-attention kernel, each fed slot's column set in "
                   "the page it copies to VMEM and that page sent back "
                   "where it lies; scatter = XLA's gather, select and "
                   "scatter of each fed slot's whole page). Counted at "
                   "trace time: once per traced executable, not per step")

_tls = threading.local()


def _count_route(name, help_, labels, **values):
    """One traced decision into the scrape-only counter ``name``."""
    from deeplearning4j_tpu.telemetry import registry as _registry

    if _registry.enabled():
        fam = _registry.get_registry().counter(name, help_, labels)
        fam.local = True   # depends on the host's backend: scrape-only
        fam.labels(**values).inc()


def recurrence_route(op: str, available: bool) -> str:
    """Which implementation one traced recurrence takes: ``"pallas"``
    (the compiled kernel: TPU backend, shape/dtype/VMEM gate passed),
    ``"interpret"`` (the same kernel through the Pallas interpreter,
    only when ``DL4J_PALLAS_INTERPRET=1`` — chip_smoke.py's ``--dry-cpu``
    and nothing else), or ``"scan"``.

    ``op`` is ``"LSTM"`` or ``"GRU"``; ``DL4J_DISABLE_PALLAS_<op>=1``
    forces the scan (the gated tier's A/B parity tests). The decision is
    counted in ``dl4j_recurrence_route_total`` so a bench-width config
    that quietly fell back to the scan shows up on /metrics and fails
    chip_smoke.py."""
    import jax

    if not available or \
            os.environ.get(f"DL4J_DISABLE_PALLAS_{op}") == "1":
        route = "scan"
    elif jax.default_backend() == "tpu":
        route = "pallas"
    elif os.environ.get("DL4J_PALLAS_INTERPRET") == "1":
        route = "interpret"
    else:
        route = "scan"
    _count_route("dl4j_recurrence_route_total", ROUTE_HELP, ("op", "route"),
                 op=op, route=route)
    return route


def _on_tpu():
    """A compile for a described chip, on a host whose backend is the CPU,
    steers this (as `models/causal_lm.py:_on_tpu`): scratch scripts and
    tests, never an option of the program."""
    import jax

    return jax.default_backend() == "tpu"


def decode_attention_route(model: str, available: bool) -> str:
    """What one traced token step of decode model ``model`` (its class's
    name) reads its pool with: ``"kernel"`` (the compiled paged-attention
    kernel: TPU backend, and the model's shape gate passed, ``available``)
    or ``"loop"`` (`serving/decode.py:live_page_attention`: the CPU, a
    small page, a row that is no whole tile). Nothing but what the code
    can observe decides, and the decision is counted in
    ``dl4j_decode_attention_route_total``, so a replica whose step quietly
    fell back to the loop shows up on /metrics."""
    route = "kernel" if available and _on_tpu() else "loop"
    _count_route("dl4j_decode_attention_route_total", DECODE_ROUTE_HELP,
                 ("model", "route"), model=model, route=route)
    return route


def decode_pool_write(model: str, route: str) -> None:
    """Count what writes one traced token step's new rows into the pool of
    decode model ``model``: ``"kernel"`` (the paged-attention kernel sets
    each fed slot's column where its page lies) or ``"scatter"`` (XLA takes
    each fed slot's page out, selects the column in and puts the page back
    whole), in ``dl4j_decode_pool_write_total``: did this replica's step
    stop taking its pages out and back."""
    _count_route("dl4j_decode_pool_write_total", POOL_WRITE_HELP,
                 ("model", "route"), model=model, route=route)


@contextlib.contextmanager
def batch_sharded(mesh, axis):
    """Trace-time scope set by a GSPMD-sharded train step whose batch is
    split over ``axis`` of ``mesh``: a recurrence kernel traced inside
    it runs per batch shard under ``shard_map`` (:func:`per_batch_shard`).
    Without the scope a Mosaic kernel inside a multi-device jit does not
    lower at all ("Mosaic kernels cannot be automatically partitioned"),
    and the kernels' VMEM gates would judge the global batch."""
    prev = getattr(_tls, "shard", None)
    _tls.shard = (mesh, axis)
    try:
        yield
    finally:
        _tls.shard = prev


def _shard_scope(n):
    """(mesh, axis, size) when a batch of ``n`` rows is being traced
    inside a :func:`batch_sharded` scope that really splits it."""
    shard = getattr(_tls, "shard", None)
    if shard is None:
        return None
    mesh, axis = shard
    size = mesh.shape.get(axis, 1)
    if size == 1 or n % size:
        return None
    return mesh, axis, size


def shard_rows(n) -> int:
    """Batch rows ONE device sees for a global batch of ``n`` — what the
    kernels' shape/VMEM gates must judge."""
    scope = _shard_scope(n)
    return n if scope is None else n // scope[2]


def per_batch_shard(fn, n, batch_dims, out_batch_dims):
    """``fn`` wrapped so that, inside a :func:`batch_sharded` scope, it
    runs once per batch shard. ``batch_dims`` / ``out_batch_dims`` give,
    for each positional input / output, the index of its batch axis, or
    None for a replicated one (recurrent weights — their cotangents are
    summed over the axis by shard_map's transpose). Outside a scope
    ``fn`` is returned unchanged."""
    scope = _shard_scope(n)
    if scope is None:
        return fn
    mesh, axis, _ = scope
    import jax
    from jax.sharding import PartitionSpec as P

    def spec(dim):
        return P() if dim is None else P(*([None] * dim + [axis]))

    # check_vma=False: pallas_call outputs carry no varying-axes info
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(d) for d in batch_dims),
        out_specs=tuple(spec(d) for d in out_batch_dims),
        check_vma=False)
