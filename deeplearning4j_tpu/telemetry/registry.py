"""Process-wide metrics registry: Counter / Gauge / Histogram / Timer.

Reference capability: the observability primitives behind OpProfiler /
PerformanceTracker / StatsListener (SURVEY.md §2.3, §2.7, §5) unified
into one registry the way a production serving stack expects — every
hot loop records through the same named instruments, exporters
(Prometheus text exposition, StatsStorage bridge, multi-host
aggregation) read one snapshot.

Design constraints (ISSUE 1 tentpole):

- zero-overhead when disabled: trainers call `loop_instruments(...)`
  ONCE per fit loop; it checks the module flag and returns None, so a
  disabled loop performs no registry calls per step;
- Histogram uses fixed log-scale buckets with a preallocated count
  list — `observe` is a bisect + two adds, no per-sample allocation;
- Timer doubles as a `jax.profiler.TraceAnnotation` context so the
  host-side span shows up in XPlane device traces (TensorBoard) at the
  same wall-clock position as the device work it covers;
- compile visibility comes from `jax.monitoring` listeners (the
  jit-cache-miss hook): every backend compile increments
  `dl4j_compile_total` and adds to `dl4j_compile_seconds_total`, tracing,
  lowering and the persistent cache's reads have running totals beside
  them, and the process's start-up account is frozen from those at its
  first train step or decode boundary (`startup_done`);
- the process's own pauses come from a `gc.callbacks` entry installed with
  those listeners (`dl4j_process_gc_*`);
- nothing here touches a device on the record path (`memory_stats` is
  read only when an exporter asks for it).
"""

from __future__ import annotations

import math
import os
import threading
import time
from bisect import bisect_right
from collections import defaultdict, deque

# -- module state ------------------------------------------------------------

_state = {"enabled": True, "registry": None}
_lock = threading.Lock()
_process_watched = False
# where /proc does not give the process's start, the start-up account runs
# from here (`startup_done`)
_IMPORTED_AT = time.perf_counter()
# the process's collection seconds so far, in a cell the collector's
# callback writes and a decode engine's thread reads (`_install_gc_hook`)
_gc_seconds = [0.0]


def enabled() -> bool:
    return _state["enabled"]


def enable():
    _state["enabled"] = True
    reg = get_registry()
    _process()      # a registry swapped in gets the process-wide series
    return reg


def disable():
    _state["enabled"] = False


def get_registry() -> "MetricsRegistry":
    """The process-wide registry (created lazily; the process's listeners
    installed on first use if `telemetry`'s import has not)."""
    reg = _state["registry"]
    if reg is None:
        with _lock:
            reg = _state["registry"]
            if reg is None:
                reg = MetricsRegistry()
                _state["registry"] = reg
                _bind_process(reg)
    watch_process()
    return reg


def set_registry(registry):
    """Swap the process registry (tests: counting stubs). Returns the
    previous registry."""
    prev = _state["registry"]
    _state["registry"] = registry
    return prev


# -- label handling ----------------------------------------------------------

def _label_key(labelnames, labels):
    if sorted(labels) != sorted(labelnames):
        raise ValueError(
            f"labels {sorted(labels)} do not match declared labelnames "
            f"{sorted(labelnames)}")
    return tuple((k, str(labels[k])) for k in labelnames)


class _Family:
    """One named metric family; unlabeled families hold their values
    directly, labeled ones hand out per-labelset children. `local` marks
    host-specific families (per-device gauges) that exporters render but
    snapshot()/aggregation skip — their label sets differ per host,
    which would break the identical-key-set aggregation contract."""

    kind = "untyped"

    def __init__(self, name, help="", labelnames=()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self.local = False
        self._children = {}
        self._lock = threading.Lock()

    def labels(self, **labels):
        key = _label_key(self.labelnames, labels)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._make_child()
                    self._children[key] = child
        return child

    def children(self):
        """[(labels_tuple, child)] — the unlabeled family yields itself
        under the empty labelset once it has been touched."""
        if self.labelnames:
            # copy under the lock: a /metrics scrape (UI server thread)
            # must not race a training thread's first labels() call
            with self._lock:
                return sorted(self._children.items())
        return [((), self)]

    def reset(self):
        with self._lock:
            self._children.clear()
        self._reset_self()


class Counter(_Family):
    """Monotonic counter. `inc(v)` with v >= 0."""

    kind = "counter"

    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, help, labelnames)
        self.value = 0.0

    def _make_child(self):
        return Counter(self.name)

    def _reset_self(self):
        self.value = 0.0

    def inc(self, amount=1.0):
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        self.value += amount


class Gauge(_Family):
    """Last-value gauge."""

    kind = "gauge"

    def __init__(self, name, help="", labelnames=()):
        super().__init__(name, help, labelnames)
        self.value = 0.0

    def _make_child(self):
        return Gauge(self.name)

    def _reset_self(self):
        self.value = 0.0

    def set(self, value):
        self.value = float(value)

    def inc(self, amount=1.0):
        self.value += amount

    def dec(self, amount=1.0):
        self.value -= amount


def log_buckets(lo, hi, per_decade=4):
    """Fixed log-scale bucket upper bounds covering [lo, hi]."""
    if not (lo > 0 and hi > lo):
        raise ValueError(f"need 0 < lo < hi, got {lo}, {hi}")
    n = int(math.ceil(math.log10(hi / lo) * per_decade)) + 1
    # 3 significant digits keep the exposition readable; a step of 1% or
    # more (per_decade <= 200) keeps rounded bounds strictly increasing
    return tuple(float(f"{lo * 10 ** (i / per_decade):.3g}")
                 for i in range(n))


# seconds: 100 us .. ~1000 s; bytes: 1 KiB .. ~64 GiB
SECONDS_BUCKETS = log_buckets(1e-4, 1e3, per_decade=4)
BYTES_BUCKETS = tuple(float(1 << (10 + 2 * i)) for i in range(14))


class Histogram(_Family):
    """Cumulative histogram over fixed bucket upper bounds (log-scale by
    default). observe() is allocation-free: one bisect into the
    precomputed bounds + integer adds."""

    kind = "histogram"

    def __init__(self, name, help="", labelnames=(),
                 buckets=SECONDS_BUCKETS):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(float(b) for b in buckets)
        if list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"histogram {name}: buckets must be "
                             "strictly increasing")
        # counts[i] = observations <= buckets[i]; counts[-1] = +Inf bucket
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        # bucket_index -> (trace_id, value, wall_ts): the last sampled
        # trace that landed in each bucket (OpenMetrics exemplars —
        # ISSUE 10: a p99 bucket links to a concrete span tree). Lazily
        # allocated; never part of snapshot()/aggregation.
        self.exemplars = None

    def _make_child(self):
        return Histogram(self.name, buckets=self.buckets)

    def _reset_self(self):
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.exemplars = None

    def observe(self, value, exemplar=None):
        idx = bisect_right(self.buckets, value)
        self.counts[idx] += 1
        self.sum += value
        if exemplar is not None:
            if self.exemplars is None:
                self.exemplars = {}
            self.exemplars[idx] = (exemplar, value, time.time())

    @property
    def count(self):
        return sum(self.counts)

    def time(self, annotation=None):
        """A Timer span feeding this histogram (and the XPlane trace)."""
        return Timer(self, annotation or self.name)


class Timer:
    """Span context: wall-clock into a Histogram AND a
    `jax.profiler.TraceAnnotation`, so the host span lands in XPlane
    device traces (TensorBoard trace viewer) alongside the device ops it
    covers. Reusable (one observation per with-block); also usable
    standalone with histogram=None as a pure trace annotation."""

    __slots__ = ("histogram", "name", "exemplar", "seconds", "_t0", "_ann")

    def __init__(self, histogram, name):
        self.histogram = histogram
        self.name = name
        self.exemplar = None   # trace id attached to the observation
        self.seconds = 0.0     # the last with-block's duration
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        try:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:  # profiling unavailable: keep timing
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self.histogram is not None:
            self.histogram.observe(dt, exemplar=self.exemplar)
        return False


def span(name):
    """Pure TraceAnnotation span (no metric) — host-side phase marker
    for XPlane traces."""
    return Timer(None, name)


# -- registry ----------------------------------------------------------------

class MetricsRegistry:
    """Name -> metric family. Re-registering an existing name returns
    the existing family (and rejects a kind/labelnames mismatch), so
    every module can declare its instruments idempotently."""

    def __init__(self):
        self._metrics: dict = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name, help, labelnames, **kw):
        fam = self._metrics.get(name)
        if fam is not None:
            if not isinstance(fam, cls) or \
                    fam.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{fam.kind} with labels {fam.labelnames}")
            return fam
        with self._lock:
            fam = self._metrics.get(name)
            if fam is None:
                fam = cls(name, help, labelnames, **kw)
                self._metrics[name] = fam
            return fam

    def counter(self, name, help="", labelnames=()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=SECONDS_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def collect(self):
        """Metric families, name-sorted (exporter entry point). Copied
        under the lock so a concurrent first-time registration cannot
        resize the dict mid-iteration."""
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def reset(self):
        for fam in self.collect():
            fam.reset()

    # -- snapshot (the aggregation/exchange format) --------------------------
    def snapshot(self) -> dict:
        """Flat {sample_name: float} of every sample, histogram buckets
        included — the unit of multi-host aggregation. Keys are
        Prometheus sample names with sorted label sets, so identical
        instrument sets on every host produce identical key order.
        Families marked local (device-memory gauges) are skipped: their
        per-host label sets would defeat cross-host aggregation."""
        out = {}
        for fam in self.collect():
            if fam.local:
                continue
            for labels, child in fam.children():
                base = _sample_name(fam.name, labels)
                if fam.kind == "histogram":
                    acc = 0
                    for b, c in zip(child.buckets, child.counts):
                        acc += c
                        out[_sample_name(fam.name + "_bucket",
                                         labels + (("le", fmt_float(b)),)
                                         )] = float(acc)
                    out[_sample_name(fam.name + "_bucket",
                                     labels + (("le", "+Inf"),))] = \
                        float(child.count)
                    out[_sample_name(fam.name + "_sum", labels)] = \
                        float(child.sum)
                    out[_sample_name(fam.name + "_count", labels)] = \
                        float(child.count)
                else:
                    out[base] = float(child.value)
        return out


def _sample_name(name, labels):
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return f"{name}{{{inner}}}"


def fmt_float(v):
    """Canonical number formatting shared by snapshot keys and the
    Prometheus exposition (integers render bare, le bounds stay short)."""
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if v != v:      # a gauge of a step that overflowed: shown, not raised
        return "NaN"
    if float(v) == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


# -- the standard instrument set for training loops --------------------------

STEP_HELP = ("Training step wall time in seconds (host dispatch region; "
             "equals device step time in steady state via dispatch-queue "
             "backpressure — no extra sync is added to measure it)")
ETL_HELP = "Seconds the training loop spent waiting for the next batch"
EXAMPLES_HELP = "Examples consumed by training steps"


class LoopInstruments:
    """Bound instruments for one training loop. Obtained once per fit()
    via loop_instruments(); None when telemetry is disabled, so the
    disabled loop body performs zero registry calls."""

    __slots__ = ("step", "etl", "examples", "loop", "_registry",
                 "step_flops")

    def __init__(self, registry, loop):
        self.loop = loop
        self._registry = registry
        self.step = registry.histogram(
            "dl4j_step_seconds", STEP_HELP, ("loop",)).labels(loop=loop)
        self.etl = registry.histogram(
            "dl4j_etl_wait_seconds", ETL_HELP, ("loop",)).labels(loop=loop)
        self.examples = registry.counter(
            "dl4j_examples_total", EXAMPLES_HELP, ("loop",)).labels(
                loop=loop)
        self.step_flops = None   # set via note_flops (costmodel)

    def step_span(self):
        """TraceAnnotation+timer around the step dispatch region."""
        return Timer(self.step, f"dl4j_step/{self.loop}")

    def note_flops(self, flops):
        """Attach the loop's cost-model FLOPs-per-step (ISSUE 10):
        every subsequent record_step refreshes the live dl4j_mfu
        gauge."""
        if flops:
            self.step_flops = float(flops)

    def record_step(self, seconds, examples=0, exemplar=None):
        self.step.observe(seconds, exemplar=exemplar)
        if examples:
            self.examples.inc(examples)
        if self.step_flops:
            from deeplearning4j_tpu.telemetry import costmodel

            costmodel.publish_mfu(self.loop, self.step_flops, seconds,
                                  registry=self._registry)

    def record_etl_wait(self, seconds):
        self.etl.observe(seconds)


def loop_instruments(loop):
    """The per-loop instrument bundle, or None when telemetry is
    disabled. Call once before the hot loop and guard per-step recording
    on the result — that keeps the disabled path at one module-flag
    check per fit() and zero registry calls per step."""
    if not _state["enabled"]:
        return None
    return LoopInstruments(get_registry(), loop)


# -- the standard instrument set for the streaming ETL engine (ISSUE 6) ------

ETL_QUEUE_DEPTH_HELP = ("Decoded batches queued between the ETL worker "
                        "pool and the consumer")
ETL_RING_HELP = ("Occupied slots in the shared-memory batch ring "
                 "(bounded by the ring size; persistently full = "
                 "consumer-bound, empty = decode-bound)")
ETL_DECODED_HELP = "Images decoded by the ETL pipeline"
ETL_PREFETCH_HITS_HELP = ("Device-prefetch queue hits (a batch was "
                          "already staged when the trainer asked)")
ETL_PREFETCH_MISSES_HELP = ("Device-prefetch queue misses (the trainer "
                            "blocked waiting for the producer thread)")
ETL_PREFETCH_DEPTH_HELP = "Batches currently staged by the DevicePrefetcher"


class EtlInstruments:
    """Bound instruments for one ETL pipeline (mirrors LoopInstruments:
    obtained once per iterator/prefetcher, None when telemetry is
    disabled, so a disabled pipeline performs zero registry calls per
    batch)."""

    __slots__ = ("loop", "queue_depth", "ring_occupancy", "decoded",
                 "prefetch_hits", "prefetch_misses", "prefetch_depth")

    def __init__(self, registry, loop):
        self.loop = loop
        self.queue_depth = registry.gauge(
            "dl4j_etl_queue_depth", ETL_QUEUE_DEPTH_HELP,
            ("loop",)).labels(loop=loop)
        self.ring_occupancy = registry.gauge(
            "dl4j_etl_shm_ring_occupancy", ETL_RING_HELP,
            ("loop",)).labels(loop=loop)
        self.decoded = registry.counter(
            "dl4j_etl_decoded_images_total", ETL_DECODED_HELP,
            ("loop",)).labels(loop=loop)
        self.prefetch_hits = registry.counter(
            "dl4j_etl_prefetch_hits_total", ETL_PREFETCH_HITS_HELP,
            ("loop",)).labels(loop=loop)
        self.prefetch_misses = registry.counter(
            "dl4j_etl_prefetch_misses_total", ETL_PREFETCH_MISSES_HELP,
            ("loop",)).labels(loop=loop)
        self.prefetch_depth = registry.gauge(
            "dl4j_etl_prefetch_depth", ETL_PREFETCH_DEPTH_HELP,
            ("loop",)).labels(loop=loop)


def etl_instruments(loop):
    """The per-pipeline ETL instrument bundle, or None when telemetry
    is disabled (same zero-cost-when-off contract as
    loop_instruments)."""
    if not _state["enabled"]:
        return None
    return EtlInstruments(get_registry(), loop)


# -- the standard instrument set for inference serving (ISSUE 2) -------------

SERVING_REQUESTS_HELP = ("Inference requests by terminal outcome "
                         "(ok|timeout_queued|timeout_execute|rejected|"
                         "shed|error|shutdown)")
SERVING_QUEUE_HELP = "Seconds a request waited in the batching queue"
SERVING_EXECUTE_HELP = ("Seconds per coalesced device dispatch (pad + "
                        "execute + split, host-visible)")
SERVING_OCCUPANCY_HELP = ("Real rows / bucket rows of the last coalesced "
                          "dispatch (1.0 = perfectly filled bucket)")
SERVING_DISPATCH_HELP = "Coalesced device dispatches executed"
SERVING_DEPTH_HELP = "Requests currently queued for batching"
SERVING_STEALS_HELP = ("Batches executed by a replica that stole them "
                       "from a sibling's run queue")
SERVING_REPLICA_LOAD_HELP = ("Queued + in-flight batches per replica "
                             "(-1 = replica dead)")
SERVING_SHED_HELP = ("Requests shed by admission control, by priority "
                     "class (HTTP 429 + Retry-After)")
SERVING_TOKENS_HELP = "Tokens emitted by continuous-batching decode"
SERVING_SLOTS_HELP = ("Decode slots currently occupied by in-flight "
                      "sequences")
SERVING_PREFIX_HITS_HELP = ("Decode admissions that adopted cached "
                            "prefix KV pages (prefill skipped for the "
                            "shared prefix)")
SERVING_PREFIX_MISSES_HELP = ("Decode admissions with no cached "
                              "prefix pages to adopt")
DECODE_TTFT_HELP = ("Seconds from decode submit to the request's "
                    "first emitted token")
DECODE_ACCEPTED_HELP = ("Speculative-decode tokens by outcome: "
                        "accepted (emitted via a verify call), "
                        "rejected (drafted but refuted), fallback "
                        "(emitted by plain decode while speculation "
                        "is in acceptance fallback)")
SERVING_KV_OCCUPANCY_HELP = ("Fraction of the paged decode KV pool "
                             "currently reserved (0..1)")
DECODE_PHASES = ("admit", "build", "dispatch", "readback", "emit")
DECODE_BOUNDARY_HELP = ("Seconds the decode engine's thread spent in "
                        "each phase of a boundary (admit|build|dispatch|"
                        "readback|emit: each once a delivered boundary; "
                        "with one token step in flight an iteration of "
                        "its loop runs readback and emit of the boundary "
                        "before the one it dispatched; the same spans "
                        "stand in a profiler trace as "
                        "dl4j.decode.<phase>)")
DECODE_BOUNDARIES_HELP = ("Decode engine boundaries by the executable "
                          "that ran (step|prefill|verify)")
DECODE_POSITIONS_HELP = ("Sequence positions the decode engine advanced, "
                         "by executable and by kind: prompt (the slot "
                         "was fed a prompt token) or answer (a generated "
                         "one)")
DECODE_KV_FILL_HELP = ("Sum over boundaries of the paged KV pool's "
                       "reserved fraction; over "
                       "dl4j_decode_boundaries_total it is the mean fill")
DECODE_LIVE_PAGES_HELP = ("Sum over token-step boundaries of the KV pages "
                          "the active slots' contexts reach (position // "
                          "page + 1 each): what the step's attention "
                          "visits; over dl4j_decode_boundaries_total"
                          "{executable=\"step\"} it is the mean a launch")
DECODE_OVERLAPPED_HELP = ("Token-step boundaries whose successor was "
                          "dispatched before their tokens were read (one "
                          "token step in flight); over "
                          "dl4j_decode_boundaries_total{executable="
                          "\"step\"} it is the overlapped share")
DECODE_SLOT_STATE_HELP = ("Bytes of state the decode model holds by slot "
                          "beside its paged pool (a hybrid model's "
                          "recurrent states and convolution tails), by "
                          "model; the pool's pages are not in it")
DECODE_STATE_STARTS_HELP = ("Requests whose state by slot started from "
                            "nought at their position 0, by model: every "
                            "admission of a model that holds such state "
                            "(a prefix hit would not be one, and the engine "
                            "refuses the prefix cache for such a model)")
DECODE_QUEUE_WAIT_HELP = ("Seconds from decode submit to the boundary "
                          "at which the request took a slot")
DECODE_INTERVAL_HELP = ("Seconds from one token-step dispatch of the decode "
                        "engine to the next, where the engine did not idle "
                        "in between (an idle poll, a failed boundary, a "
                        "block or verify boundary and close() forget the "
                        "last dispatch): every such interval of the "
                        "engine's life")
DECODE_INTERVAL_MAX_HELP = ("The longest interval that "
                            "dl4j_decode_interval_seconds observed, exact")
DECODE_BETWEEN_HELP = ("Of an observed dispatch interval, the seconds the "
                       "engine's thread spent under none of its five phase "
                       "spans: the wait for the interpreter lock between "
                       "them and whatever has no span")
DECODE_INTERVAL_GC_HELP = ("The process's collection seconds "
                           "(dl4j_process_gc_pause_seconds_total) that fell "
                           "inside observed dispatch intervals")
DECODE_LONGEST_HELP = ("The longest dispatch interval split by what the "
                       "engine's thread did in it (admit|build|dispatch|"
                       "readback|emit: seconds under that phase's span, a "
                       "collection inside it taken out; gc: the process's "
                       "collection seconds inside the interval; between: "
                       "the rest); rewritten whenever "
                       "dl4j_decode_interval_max_seconds is, and the parts "
                       "add up to it")
# fine enough to take the time above a threshold from the buckets alone: a
# bound every 15.5%, so a bucket's middle is within 7% of what fell in it
INTERVAL_BUCKETS = log_buckets(1e-4, 1e2, per_decade=16)
LONGEST_PARTS = DECODE_PHASES + ("between", "gc")


MOE_CHOICES_HELP = ("Expert choices the sparse layer's router made "
                    "(tokens x experts per token), by model and layer")
MOE_HELD_HELP = ("Expert choices that fell on an expert this program "
                 "holds, by model and layer")
MOE_DROPPED_HELP = ("Held expert choices that did not fit the expert "
                    "layer's buffer and were left out, by model and layer: "
                    "0 while the layer is dropless")
MOE_LOAD_HELP = ("Sum over steps of the fullest held expert's choices over "
                 "the held experts' mean; over dl4j_moe_steps_total it is "
                 "the mean imbalance, by model and layer")
MOE_TOUCHED_HELP = ("Sum over steps of the held experts at least one "
                    "choice fell on (the experts whose weights a grouped "
                    "step's products read; a dense step reads every held "
                    "expert's), by model and layer")
MOE_STEPS_HELP = ("Steps whose router counts have been published, by model "
                  "(a trainer's train steps, a decode engine's token steps)")
MOE_DENSE_HELP = ("Of dl4j_moe_steps_total, the steps whose expert products "
                  "ran as one batched product over the held experts (the "
                  "worst-case buffer of a small batch: nothing can be "
                  "dropped), by model")


HC_RESIDUAL_HELP = ("Of the decode engine's last delivered token step, over "
                    "the rows it fed and every sublayer: the largest "
                    "|row or column sum - 1| of a residual mixing map "
                    "after its Sinkhorn iterations (0 is doubly "
                    "stochastic), by model")
HC_GAIN_HELP = ("Of the decode engine's last delivered token step, over the "
                "rows it fed: the largest RMS of a row's residual streams "
                "after the last layer over their RMS at the embedding, by "
                "model")


class MoeInstruments:
    """The `dl4j_moe_*` series of one publisher, labelled `model`: a
    trainer's name or a decode engine's. `step(layers, counts)` adds one
    step's counts, a row (every choice, held choices, dropped, the fullest
    held expert over the mean, held experts touched) a sparse layer;
    `dense` says that the step's expert products ran dense
    (`parallel/moe.py:moe_share_dense`, which the publisher asks)."""

    __slots__ = ("model", "_families", "_steps", "_dense")

    def __init__(self, registry, model):
        self.model = model
        self._families = [
            registry.counter(name, text, ("model", "layer"))
            for name, text in (
                ("dl4j_moe_choices_total", MOE_CHOICES_HELP),
                ("dl4j_moe_held_choices_total", MOE_HELD_HELP),
                ("dl4j_moe_dropped_total", MOE_DROPPED_HELP),
                ("dl4j_moe_load_max_over_mean_sum", MOE_LOAD_HELP),
                ("dl4j_moe_touched_experts_total", MOE_TOUCHED_HELP))]
        self._steps = registry.counter(
            "dl4j_moe_steps_total", MOE_STEPS_HELP,
            ("model",)).labels(model=model)
        self._dense = registry.counter(
            "dl4j_moe_dense_steps_total", MOE_DENSE_HELP,
            ("model",)).labels(model=model)

    def step(self, layers, counts, dense=False):
        for layer, row in zip(layers, counts):
            for family, value in zip(self._families, row):
                family.labels(model=self.model,
                              layer=str(layer)).inc(float(value))
        self._steps.inc()
        if dense:
            self._dense.inc()


def moe_instruments(model):
    """The router-count bundle of one publisher, or None when disabled."""
    if not _state["enabled"]:
        return None
    return MoeInstruments(get_registry(), model)


class DispatchAccount:
    """What a decode engine keeps from one token-step dispatch to the
    next, for `ServingInstruments.dispatched`: the dispatch's stamp (None
    once the engine has idled or run something else, so that waiting for
    traffic is never an interval), the seconds its thread has spent under
    each phase Timer since, the collection seconds that fell inside those,
    and the process's collection total at the stamp."""

    __slots__ = ("stamp", "spent", "paused", "gc_mark")

    def __init__(self):
        self.stamp = None
        self.spent = dict.fromkeys(DECODE_PHASES, 0.0)
        self.paused = dict.fromkeys(DECODE_PHASES, 0.0)
        self.gc_mark = 0.0

    def clear(self):
        """Forget the last dispatch: the time to the next is no interval."""
        self.stamp = None


class _PhaseTimer(Timer):
    """A decode phase's Timer that also adds its seconds, and the
    collection seconds inside them, to the engine's DispatchAccount."""

    __slots__ = ("_account", "_phase", "_gc0")

    def __init__(self, histogram, name, phase, account):
        super().__init__(histogram, name)
        self._account, self._phase = account, phase
        self._gc0 = 0.0

    def __enter__(self):
        self._gc0 = _gc_seconds[0]
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        account = self._account
        account.spent[self._phase] += self.seconds
        paused = _gc_seconds[0] - self._gc0
        if paused:
            account.paused[self._phase] += paused
        return False


class ServingInstruments:
    """Bound per-model serving instruments (mirrors LoopInstruments:
    obtained once per batcher, None when telemetry is disabled, so a
    disabled serving path performs zero registry calls per request)."""

    __slots__ = ("model", "_requests", "queue_wait", "execute",
                 "occupancy", "dispatch", "depth", "steals",
                 "_replica_load", "_shed", "tokens", "slots",
                 "prefix_hits", "prefix_misses", "ttft", "_accepted",
                 "kv_occupancy", "_phases", "_boundaries", "_positions",
                 "kv_fill_sum", "live_pages_sum", "overlapped",
                 "decode_queue_wait", "_registry", "_moe", "_state_starts",
                 "interval", "interval_max", "between", "interval_gc",
                 "_longest", "_residual")

    def __init__(self, registry, model):
        self.model = model
        self._registry = registry
        self._moe = None    # bound by the first token step that routes
        self._state_starts = None   # and by a model's first state start
        self._residual = None       # and by the first health numbers
        self._requests = registry.counter(
            "dl4j_serving_requests_total", SERVING_REQUESTS_HELP,
            ("model", "outcome"))
        self.queue_wait = registry.histogram(
            "dl4j_serving_queue_wait_seconds", SERVING_QUEUE_HELP,
            ("model",)).labels(model=model)
        self.execute = registry.histogram(
            "dl4j_serving_execute_seconds", SERVING_EXECUTE_HELP,
            ("model",)).labels(model=model)
        self.occupancy = registry.gauge(
            "dl4j_serving_batch_occupancy", SERVING_OCCUPANCY_HELP,
            ("model",)).labels(model=model)
        self.dispatch = registry.counter(
            "dl4j_serving_dispatch_total", SERVING_DISPATCH_HELP,
            ("model",)).labels(model=model)
        self.depth = registry.gauge(
            "dl4j_serving_queue_depth", SERVING_DEPTH_HELP,
            ("model",)).labels(model=model)
        self.steals = registry.counter(
            "dl4j_serving_steals_total", SERVING_STEALS_HELP,
            ("model",)).labels(model=model)
        self._replica_load = registry.gauge(
            "dl4j_serving_replica_load", SERVING_REPLICA_LOAD_HELP,
            ("model", "replica"))
        self._shed = registry.counter(
            "dl4j_serving_shed_total", SERVING_SHED_HELP,
            ("model", "priority"))
        self.tokens = registry.counter(
            "dl4j_serving_decode_tokens_total", SERVING_TOKENS_HELP,
            ("model",)).labels(model=model)
        self.slots = registry.gauge(
            "dl4j_serving_decode_slots", SERVING_SLOTS_HELP,
            ("model",)).labels(model=model)
        self.prefix_hits = registry.counter(
            "dl4j_serving_prefix_hits_total", SERVING_PREFIX_HITS_HELP,
            ("model",)).labels(model=model)
        self.prefix_misses = registry.counter(
            "dl4j_serving_prefix_misses_total",
            SERVING_PREFIX_MISSES_HELP, ("model",)).labels(model=model)
        self.ttft = registry.histogram(
            "dl4j_decode_ttft_seconds", DECODE_TTFT_HELP,
            ("model",)).labels(model=model)
        self._accepted = registry.counter(
            "dl4j_decode_accepted_tokens_total", DECODE_ACCEPTED_HELP,
            ("model", "outcome"))
        self.kv_occupancy = registry.gauge(
            "dl4j_serving_kv_page_occupancy",
            SERVING_KV_OCCUPANCY_HELP, ("model",)).labels(model=model)
        phases = registry.histogram(
            "dl4j_decode_boundary_seconds", DECODE_BOUNDARY_HELP,
            ("model", "phase"))
        self._phases = {p: (phases.labels(model=model, phase=p),
                            "dl4j.decode." + p) for p in DECODE_PHASES}
        self._boundaries = registry.counter(
            "dl4j_decode_boundaries_total", DECODE_BOUNDARIES_HELP,
            ("model", "executable"))
        self._positions = registry.counter(
            "dl4j_decode_positions_total", DECODE_POSITIONS_HELP,
            ("model", "executable", "kind"))
        self.kv_fill_sum = registry.counter(
            "dl4j_decode_kv_fill_sum", DECODE_KV_FILL_HELP,
            ("model",)).labels(model=model)
        self.live_pages_sum = registry.counter(
            "dl4j_decode_live_pages_sum", DECODE_LIVE_PAGES_HELP,
            ("model",)).labels(model=model)
        self.overlapped = registry.counter(
            "dl4j_decode_overlapped_boundaries_total",
            DECODE_OVERLAPPED_HELP, ("model",)).labels(model=model)
        self.decode_queue_wait = registry.histogram(
            "dl4j_decode_queue_wait_seconds", DECODE_QUEUE_WAIT_HELP,
            ("model",)).labels(model=model)
        self.interval = registry.histogram(
            "dl4j_decode_interval_seconds", DECODE_INTERVAL_HELP,
            ("model",), buckets=INTERVAL_BUCKETS).labels(model=model)
        self.interval_max = registry.gauge(
            "dl4j_decode_interval_max_seconds", DECODE_INTERVAL_MAX_HELP,
            ("model",)).labels(model=model)
        self.between = registry.histogram(
            "dl4j_decode_between_phases_seconds", DECODE_BETWEEN_HELP,
            ("model",), buckets=INTERVAL_BUCKETS).labels(model=model)
        self.interval_gc = registry.counter(
            "dl4j_decode_interval_gc_seconds_total",
            DECODE_INTERVAL_GC_HELP, ("model",)).labels(model=model)
        longest = registry.gauge(
            "dl4j_decode_longest_interval_seconds", DECODE_LONGEST_HELP,
            ("model", "part"))
        self._longest = {p: longest.labels(model=model, part=p)
                         for p in LONGEST_PARTS}

    def request(self, outcome):
        self._requests.labels(model=self.model, outcome=outcome).inc()

    def replica_load(self, replica):
        return self._replica_load.labels(model=self.model,
                                         replica=replica)

    def shed(self, priority):
        self._shed.labels(model=self.model, priority=priority).inc()

    def accepted(self, outcome, n=1):
        self._accepted.labels(model=self.model, outcome=outcome).inc(n)

    def phase(self, phase, account):
        """A Timer over one phase of a decode boundary: the histogram
        and, on the profiler's clock, the span `dl4j.decode.<phase>`. Its
        seconds also go to the engine's DispatchAccount."""
        histogram, annotation = self._phases[phase]
        return _PhaseTimer(histogram, annotation, phase, account)

    def dispatched(self, account, t_b0):
        """A token step is dispatched at `t_b0`. Where the account holds
        the dispatch before it, the time between the two is one interval:
        observed, with what of it lay under no phase span and the
        collections inside it, and split by part where it is the longest
        yet. The account then starts again from this dispatch."""
        gc_now = _gc_seconds[0]
        prev, account.stamp = account.stamp, t_b0
        spent, paused = account.spent, account.paused
        if prev is not None:
            seconds = t_b0 - prev
            between = seconds - sum(spent.values())
            collected = gc_now - account.gc_mark
            self.interval.observe(seconds)
            self.between.observe(between)
            if collected > 0:
                self.interval_gc.inc(collected)
            if seconds > self.interval_max.value:
                self.interval_max.set(seconds)
                # a phase's part is net of the collections inside it, so
                # that the seven parts add up to the interval
                for p in DECODE_PHASES:
                    self._longest[p].set(spent[p] - paused[p])
                self._longest["gc"].set(collected)
                self._longest["between"].set(
                    between + sum(paused.values()) - collected)
        account.gc_mark = gc_now
        for p in DECODE_PHASES:
            spent[p] = paused[p] = 0.0

    def state_start(self, nbytes):
        """One request of a model that holds `nbytes` of state by slot
        started its state from nought."""
        if self._state_starts is None:
            self._state_starts = self._registry.counter(
                "dl4j_decode_state_starts_total", DECODE_STATE_STARTS_HELP,
                ("model",)).labels(model=self.model)
            self._registry.gauge(
                "dl4j_decode_slot_state_bytes", DECODE_SLOT_STATE_HELP,
                ("model",)).labels(model=self.model).set(nbytes)
        self._state_starts.inc()

    def residual_health(self, defect, gain):
        """One token step's two numbers on a residual path of several
        streams (`serving/latent.py:LatentDecodeModel._apply`): how far
        its worst mixing map was from doubly stochastic, and its largest
        gain from entry to exit. Gauges of the last delivered step,
        scrape-only."""
        if self._residual is None:
            self._residual = []
            for name, text in (
                    ("dl4j_hc_sinkhorn_residual_max", HC_RESIDUAL_HELP),
                    ("dl4j_hc_stream_gain_max", HC_GAIN_HELP)):
                fam = self._registry.gauge(name, text, ("model",))
                fam.local = True
                self._residual.append(fam.labels(model=self.model))
        for gauge, value in zip(self._residual, (defect, gain)):
            gauge.set(float(value))

    def moe_step(self, layers, counts, dense=False):
        """One token step's router counts (`MoeInstruments.step`), under
        this model's label."""
        if self._moe is None:
            self._moe = MoeInstruments(self._registry, self.model)
        self._moe.step(layers, counts, dense)

    def boundary(self, executable, prompt=0, answer=0):
        """One decode boundary through `executable` that fed `prompt`
        prompt positions and `answer` generated ones, all slots
        together."""
        self._boundaries.labels(model=self.model,
                                executable=executable).inc()
        for kind, n in (("prompt", prompt), ("answer", answer)):
            if n:
                self._positions.labels(
                    model=self.model, executable=executable,
                    kind=kind).inc(n)


def serving_instruments(model):
    """Per-model serving instrument bundle, or None when disabled."""
    if not _state["enabled"]:
        return None
    return ServingInstruments(get_registry(), model)


# -- the fleet-router instrument set (ISSUE 15) ------------------------------

FLEET_REQUESTS_HELP = ("Fleet requests routed, by worker and outcome "
                       "(ok|shed|timeout|client_error|upstream_error|"
                       "transport|no_worker)")
FLEET_WORKER_UP_HELP = ("Router's view of a worker: 1 = routable, "
                        "0 = ejected by the transport breaker or down")
FLEET_RETRIES_HELP = ("Requests re-sent to a surviving worker after a "
                      "transport failure (the client never saw the "
                      "death)")
FLEET_ROLLOUT_STATE_HELP = ("Rollout state machine position: -1 "
                            "rolled_back, 0 idle, 1 canary, 2 "
                            "promoting, 3 complete")
FLEET_HOP_HELP = ("Router→worker hop seconds (forward + worker "
                  "service + response read)")
FLEET_HOP_PHASE_HELP = ("Router→worker hop seconds decomposed by phase "
                        "(queue|execute|worker_other|transit) from the "
                        "workers' Server-Timing header: queue/execute "
                        "are worker-reported, worker_other is worker "
                        "handler time outside both, transit is the "
                        "serialize+network+parse remainder the router "
                        "attributes by subtraction)")
FLEET_MIRROR_HELP = ("Canary mirror comparisons by verdict "
                     "(agree|disagree|error)")
FLEET_CAPTURED_HELP = ("Live requests head-sampled into the traffic-"
                       "capture ring (train-from-traffic)")
FLEET_RESPAWNS_HELP = ("Autopilot respawn attempts of dead spawned "
                       "workers, by worker and outcome "
                       "(ok|failed|gave_up)")
FLEET_TARGET_WORKERS_HELP = ("Autoscaler's current desired fleet size "
                             "(spawn/retire decisions converge the "
                             "actual size toward it)")


class FleetInstruments:
    """Bound fleet-router instruments (mirrors ServingInstruments:
    obtained once per router, None when telemetry is disabled, so a
    disabled router performs zero registry calls per request)."""

    __slots__ = ("_requests", "_worker_up", "retries", "rollout_state",
                 "_hop", "_hop_phase", "_mirror", "captured",
                 "_respawns", "target_workers")

    def __init__(self, registry):
        self._requests = registry.counter(
            "dl4j_fleet_requests_total", FLEET_REQUESTS_HELP,
            ("worker", "outcome"))
        self._worker_up = registry.gauge(
            "dl4j_fleet_worker_up", FLEET_WORKER_UP_HELP, ("worker",))
        self.retries = registry.counter(
            "dl4j_fleet_retries_total", FLEET_RETRIES_HELP)
        self.rollout_state = registry.gauge(
            "dl4j_fleet_rollout_state", FLEET_ROLLOUT_STATE_HELP)
        self._hop = registry.histogram(
            "dl4j_fleet_request_seconds", FLEET_HOP_HELP, ("worker",))
        self._hop_phase = registry.histogram(
            "dl4j_fleet_hop_seconds", FLEET_HOP_PHASE_HELP, ("phase",))
        self._mirror = registry.counter(
            "dl4j_fleet_mirror_total", FLEET_MIRROR_HELP, ("verdict",))
        self.captured = registry.counter(
            "dl4j_fleet_captured_total", FLEET_CAPTURED_HELP)
        self._respawns = registry.counter(
            "dl4j_fleet_respawns_total", FLEET_RESPAWNS_HELP,
            ("worker", "outcome"))
        self.target_workers = registry.gauge(
            "dl4j_fleet_target_workers", FLEET_TARGET_WORKERS_HELP)

    def request(self, worker, outcome):
        self._requests.labels(worker=worker, outcome=outcome).inc()

    def worker_up(self, worker):
        return self._worker_up.labels(worker=worker)

    def hop(self, worker):
        return self._hop.labels(worker=worker)

    def hop_phase(self, phase):
        return self._hop_phase.labels(phase=phase)

    def mirror(self, verdict):
        self._mirror.labels(verdict=verdict).inc()

    def respawn(self, worker, outcome):
        self._respawns.labels(worker=worker, outcome=outcome).inc()


def fleet_instruments():
    """The fleet-router instrument bundle, or None when telemetry is
    disabled (the zero-cost-when-off contract, gate-listed in the
    dl4jlint telemetry-gate rule)."""
    if not _state["enabled"]:
        return None
    return FleetInstruments(get_registry())


# -- the process's own account: compiles, start-up, collections --------------

COMPILE_HELP = "XLA backend compiles observed in this process"
COMPILE_SECONDS_HELP = ("Seconds inside jax's backend-compile event. Since "
                        "jax 0.9 that event wraps the persistent cache's "
                        "read, so a hit fires it too, with the retrieval's "
                        "time: the seconds that really compiled are this "
                        "total minus dl4j_compile_stage_seconds_total"
                        "{stage=\"cache_load\"}")
COMPILE_STAGE_HELP = ("Seconds this process spent on the way to an "
                      "executable beside the backend compile, by stage: "
                      "trace (Python to jaxpr) and lower (jaxpr to an MLIR "
                      "module), each span's own time with the spans nested "
                      "in it taken out, and cache_load (reading a hit out "
                      "of the persistent compilation cache)")
CACHE_HITS_HELP = ("Executables this process took from jax's persistent "
                   "compilation cache")
CACHE_MISSES_HELP = ("Executables this process compiled and wrote to jax's "
                     "persistent compilation cache")
GC_PAUSE_HELP = ("Seconds this process spent in garbage collections, by "
                 "generation: every Python thread stands still meanwhile")
GC_COLLECTIONS_HELP = "Garbage collections of this process, by generation"
GC_PAUSE_MAX_HELP = "The longest single garbage collection of this process"
STARTUP_HELP = ("What this process's start cost, frozen at its first train "
                "step or delivered decode boundary, by part: total (process "
                "start, or where /proc does not say the telemetry module's "
                "import, to the freeze), trace, lower and cache_load "
                "(dl4j_compile_stage_seconds_total as it stood), compile "
                "(dl4j_compile_seconds_total minus cache_load: cache misses "
                "only). What total holds beyond the parts is the imports, "
                "the device's opening, the weights and the caller's own "
                "work")
STARTUP_EXECUTABLES_HELP = ("Executables acquired before the start-up "
                            "account froze, by outcome: hit (read from the "
                            "persistent compilation cache) or compiled")
GC_SPAN = "dl4j.process.gc"
COMPILE_STAGES = ("trace", "lower", "cache_load")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# the spans whose own time is a stage's; a backend compile has no stage (its
# seconds come from the duration event) and is taken out of what it nests in
_SPAN_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                _COMPILE_EVENT: None}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}


class ProcessInstruments:
    """The process-wide series, bound once a registry so that the
    collector's callback and jax's listeners look nothing up by name."""

    __slots__ = ("registry", "compiles", "compile_seconds", "stage",
                 "cache_hits", "cache_misses", "gc_pause", "gc_collections",
                 "gc_pause_max")

    def __init__(self, registry):
        self.registry = registry
        self.compiles = registry.counter("dl4j_compile_total", COMPILE_HELP)
        self.compile_seconds = registry.counter(
            "dl4j_compile_seconds_total", COMPILE_SECONDS_HELP)
        stage = registry.counter("dl4j_compile_stage_seconds_total",
                                 COMPILE_STAGE_HELP, ("stage",))
        self.stage = {s: stage.labels(stage=s) for s in COMPILE_STAGES}
        self.cache_hits = registry.counter(
            "dl4j_compile_cache_hits_total", CACHE_HITS_HELP)
        self.cache_misses = registry.counter(
            "dl4j_compile_cache_misses_total", CACHE_MISSES_HELP)
        pause = registry.counter("dl4j_process_gc_pause_seconds_total",
                                 GC_PAUSE_HELP, ("generation",))
        count = registry.counter("dl4j_process_gc_collections_total",
                                 GC_COLLECTIONS_HELP, ("generation",))
        self.gc_pause = [pause.labels(generation=g) for g in range(3)]
        self.gc_collections = [count.labels(generation=g) for g in range(3)]
        self.gc_pause_max = registry.gauge(
            "dl4j_process_gc_pause_max_seconds", GC_PAUSE_MAX_HELP)


_process_bound = [None]


def _bind_process(registry):
    """Bind the process-wide series to `registry`. Never from the
    collector's callback: registering a family takes the registry's lock,
    which the thread the collection interrupted may hold."""
    try:
        _process_bound[0] = ProcessInstruments(registry)
    except Exception:   # a stub registry must break neither jit nor a swap
        _process_bound[0] = None


def _process():
    """The process-wide series on the current registry (made if there is
    none yet: the first compile precedes the first instrumented loop), or
    None while telemetry is off or the registry is a stub."""
    if not _state["enabled"]:
        return None
    reg = get_registry()
    bound = _process_bound[0]
    if bound is None or bound.registry is not reg:
        _bind_process(reg)
        bound = _process_bound[0]
    return bound


def watch_process():
    """Register the jax.monitoring listeners and the collector's callback
    once per process: every backend compile (a jit cache miss reaching
    XLA, or since jax 0.9 a persistent-cache hit) bumps dl4j_compile_total
    / dl4j_compile_seconds_total, tracing, lowering and cache reads add to
    dl4j_compile_stage_seconds_total, the cache's outcomes are counted.
    Every listener checks the enabled flag first, so disabling telemetry
    silences them. Called when the `telemetry` package is imported (and
    by `get_registry`, as before): a process's first compiles are its
    weights', made as arguments of a trainer's or a decode model's
    constructor, before any object of this package exists that could
    install a listener. What has happened by then is the import of
    `telemetry`, at the top of `parallel/step_engine.py` (so of both
    trainers' modules) and of `serving/decode.py` (so of every decode
    model's); the `fit()` loops import it when they first run, and freeze
    no start-up account."""
    global _process_watched
    if _process_watched:
        return
    with _lock:
        if _process_watched:
            return
        _process_watched = True
    _install_gc_hook()
    try:
        import jax.monitoring as monitoring
    except Exception:
        return

    def _on_duration(key, seconds, **kw):
        if key == _CACHE_LOAD_EVENT:
            bound = _process()
            if bound is not None:
                bound.stage["cache_load"].inc(seconds)
            return
        if key != _COMPILE_EVENT or not _state["enabled"]:
            return
        bound = _process()
        if bound is not None:
            bound.compiles.inc()
            bound.compile_seconds.inc(seconds)
        try:
            from deeplearning4j_tpu.telemetry import flight

            flight.record("compile", seconds=round(seconds, 6))
        except Exception:
            pass  # the flight recorder must never break jit either
        try:
            # compile-ledger attribution (ISSUE 11): mark this thread
            # so the site live on it (fit-loop note_step / servable
            # warmup) can claim the compile seconds
            from deeplearning4j_tpu.telemetry import compile_ledger

            compile_ledger.note_backend_compile(seconds)
        except Exception:
            pass  # the ledger must never break jit either

    def _on_event(key, **kw):
        which = _CACHE_EVENTS.get(key)
        if which is not None:
            bound = _process()
            if bound is not None:
                getattr(bound, which).inc()

    # by thread, (start, seconds) of the spans it reported that no later
    # span of its own has claimed as nested in it. jax reports a span as it
    # ends, on the thread that ran it, the inner before the outer, and its
    # durations nest: tracing a step traces every jitted function it calls,
    # lowering traces again. A stage's total is each span's own time, so
    # that the stages add up to the thread's wall time.
    stacks = defaultdict(lambda: deque(maxlen=4096))

    def _on_span(key, start, end, **kw):
        if key not in _SPAN_STAGES or not _state["enabled"]:
            return
        unclaimed = stacks[threading.get_ident()]
        seconds = end - start
        own = seconds
        while unclaimed and unclaimed[-1][0] >= start:
            own -= unclaimed.pop()[1]
        unclaimed.append((start, seconds))
        stage = _SPAN_STAGES[key]
        if stage is not None and own > 0:
            bound = _process()
            if bound is not None:
                bound.stage[stage].inc(own)

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_time_span_listener(_on_span)


# the collection in progress: when it started, and its span if it has one
_gc_open = [None, None]


def _on_gc(phase, info):
    """`gc.callbacks` entry: a collection's seconds into the cell the decode
    engines read and into the pre-bound series, and a generation-2
    collection as the span `dl4j.process.gc` on the profiler's clock. It
    takes no lock and allocates nothing the collector tracks but that one
    span's annotation."""
    if phase == "start":
        if not _state["enabled"]:
            return
        if info["generation"] == 2:
            try:
                import jax

                _gc_open[1] = jax.profiler.TraceAnnotation(GC_SPAN)
                _gc_open[1].__enter__()
            except Exception:   # profiling unavailable: keep timing
                _gc_open[1] = None
        _gc_open[0] = time.perf_counter()
        return
    t0, span = _gc_open
    if t0 is None:
        return
    seconds = time.perf_counter() - t0
    _gc_open[0] = _gc_open[1] = None
    if span is not None:
        span.__exit__(None, None, None)
    _gc_seconds[0] += seconds
    bound = _process_bound[0]
    if bound is not None and bound.registry is _state["registry"]:
        generation = info["generation"]
        bound.gc_pause[generation].inc(seconds)
        bound.gc_collections[generation].inc()
        if seconds > bound.gc_pause_max.value:
            bound.gc_pause_max.set(seconds)


def _install_gc_hook():
    import gc

    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# -- the start-up account -----------------------------------------------------

_startup = {"frozen": False}
_startup_lock = threading.Lock()


def _process_age():
    """Seconds since this process started, from /proc, or None."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # field 22, starttime in clock ticks since boot; the fields
            # after the command's closing bracket start at the third
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        with open("/proc/uptime", "rb") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def startup_done():
    """Freeze the start-up account: called by `StepEngine.run` once its
    first call of the step has returned and by `DecodeEngine._deliver` at
    its first delivered token-step boundary. The first call in the process
    writes `dl4j_startup_seconds{part}` and `dl4j_startup_executables
    {outcome}` from the running totals as they stand; every later call is
    one flag read. The totals keep running: what was compiled after the
    first step is the running total minus the frozen part."""
    if _startup["frozen"] or not _state["enabled"]:
        return
    with _startup_lock:
        if _startup["frozen"]:
            return
        _startup["frozen"] = True
    bound = _process()
    if bound is None:
        return
    since_import = time.perf_counter() - _IMPORTED_AT
    total = _process_age()
    if total is None or total < since_import:
        total = since_import
    load = bound.stage["cache_load"].value
    parts = {"total": total, "trace": bound.stage["trace"].value,
             "lower": bound.stage["lower"].value, "cache_load": load,
             "compile": max(0.0, bound.compile_seconds.value - load)}
    seconds = bound.registry.gauge("dl4j_startup_seconds", STARTUP_HELP,
                                   ("part",))
    for part, value in parts.items():
        seconds.labels(part=part).set(value)
    hits = bound.cache_hits.value
    executables = bound.registry.gauge(
        "dl4j_startup_executables", STARTUP_EXECUTABLES_HELP, ("outcome",))
    executables.labels(outcome="hit").set(hits)
    executables.labels(outcome="compiled").set(
        max(0.0, bound.compiles.value - hits))


# -- device memory (read on demand by exporters, never per step) -------------

DEVICE_MEM_HELP = ("Device memory from device.memory_stats(), absent on "
                   "backends that do not report it (e.g. CPU)")
DEVICE_MEM_STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                    "largest_free_block_bytes")


def collect_device_memory(registry=None):
    """Refresh dl4j_device_mem_bytes from each local device's
    memory_stats(). The family is registered even when no device reports
    stats (CPU), so the metric name is always present in the exposition;
    samples appear only where the backend provides them."""
    if not _state["enabled"]:
        return
    reg = registry or get_registry()
    gauge = reg.gauge("dl4j_device_mem_bytes", DEVICE_MEM_HELP,
                      ("device", "stat"))
    gauge.local = True  # device ids are host-specific: scrape-only
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key in DEVICE_MEM_STATS:
            if key in stats:
                gauge.labels(device=f"{d.platform}:{d.id}",
                             stat=key).set(stats[key])
