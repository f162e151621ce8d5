"""Unified telemetry: process-wide metrics registry, phase tracing,
multi-host aggregation, Prometheus exposition (ISSUE 1 tentpole;
SURVEY.md §5 observability — the TPU-native OpProfiler /
PerformanceTracker / StatsListener replacement), plus training-health
diagnostics and the flight recorder (ISSUE 3): per-layer stats computed
inside the jitted step, divergence policies (WARN / HALT raising
DivergenceError / SKIP_BATCH), a bounded event ring dumped on
divergence or via GET /debug/flightrecorder, and GET /healthz.

Quick use::

    from deeplearning4j_tpu import telemetry
    telemetry.enable()                       # on by default
    telemetry.health.configure(policy="halt", ratio_max=10.0)
    net.fit(data, 3)                         # hot loops self-instrument
    print(telemetry.prometheus.render())     # or GET /metrics on UIServer
    agg = telemetry.aggregate_snapshot()     # cross-host min/max/mean/sum
    telemetry.flight.dump("/tmp/flight.jsonl")

ISSUE 10 adds sampled end-to-end tracing (`telemetry.tracing`:
request/step span trees with W3C traceparent propagation, exported at
GET /debug/traces, exemplars on latency histograms) and XLA cost-model
attribution (`telemetry.costmodel`: dl4j_flops_per_step /
dl4j_executable_bytes / a live dl4j_mfu gauge from cost_analysis() at
step-lower / AOT-warmup time).

ISSUE 11 adds compile-side observability: `telemetry.compile_ledger`
(an executable ledger keyed by step/serving site with recompile
forensics — structured causes diffed from argument signatures, compile
seconds off the jax.monitoring hook, HLO fingerprints, exported at
GET /debug/compiles) and `telemetry.hlo_audit` (fusion / unfused-dot /
collective / remat / largest-buffer audit of each ledgered
executable's optimized HLO, at GET /debug/hlo/<key> and
tools/hloaudit.py).

ISSUE 14 adds device-memory observability: `telemetry.memledger` —
the HBM ownership ledger (every memory-pinning subsystem registers a
categorized claim, reconciled against device.memory_stats() into
dl4j_device_memory_claimed_bytes plus an unattributed residual at
GET /debug/memory and /healthz), OOM forensics (typed DeviceOomError +
flight `oom` events naming site / requested bytes / top claims at the
train, serving, decode, prefetch, and snapshot seams), and
admission-time capacity planning (structured CapacityError before any
compile or pool allocation).

ISSUE 16 adds the time dimension and the fleet view:
`telemetry.timeseries` (a bounded ring of periodic windowed snapshots —
counters become rates, histograms become windowed p50/p99 — at
GET /debug/timeseries) and `telemetry.slo` (declared latency /
error-rate objectives evaluated by SRE-style multi-window burn rate
over the ring: dl4j_slo_* metrics, slo_breach/slo_recovered flight
events, a degraded-not-503 /healthz `slo` section, and a
histogram-direct burn judge the rollout controller uses on canaries).

ISSUE 18 adds stack-level attribution: `telemetry.profiler` — an
always-on ~19Hz wall-clock sampler over sys._current_frames() folding
every thread's stack into a bounded ring of collapsed stacks
(flamegraph-ready at GET /debug/profile/cpu, subsystem-attributed via
the dl4j:<subsystem>:<role> thread-name convention + module-path
heuristics, scrape-only dl4j_profile_self_seconds_total), single-flight
deep captures (POST /debug/profile/capture: high-rate sample +
jax.profiler.trace artifacts, content-addressed, atomic_save-committed)
and fleet-merged flamegraphs at GET /debug/fleet/profile.

Disabling (`telemetry.disable()`) removes every per-step registry call
from the training loops — they check the flag once per fit() — and
compiles the health stats OUT of the jitted step (pre-health output
structure, bit-identical math); the same switch means zero tracer
calls per step and per request, and zero compile-ledger calls per
step."""

from deeplearning4j_tpu.telemetry import (
    aggregate, compile_ledger, costmodel, flight, health, hlo_audit,
    memledger, profiler, prometheus, slo, timeseries, tracing)
from deeplearning4j_tpu.telemetry.memledger import (
    CapacityError, DeviceOomError)
from deeplearning4j_tpu.telemetry.aggregate import aggregate_snapshot
from deeplearning4j_tpu.telemetry.flight import FlightRecorder
from deeplearning4j_tpu.telemetry.health import (
    DivergenceError, HealthConfig, HealthMonitor)
from deeplearning4j_tpu.telemetry.listener import MetricsListener
from deeplearning4j_tpu.telemetry.registry import (
    BYTES_BUCKETS, Counter, ETL_HELP, EtlInstruments, FleetInstruments,
    Gauge, Histogram, LoopInstruments, MetricsRegistry, MoeInstruments,
    SECONDS_BUCKETS, STEP_HELP, ServingInstruments, Timer,
    collect_device_memory, disable, enable, enabled, etl_instruments,
    fleet_instruments, get_registry, log_buckets, loop_instruments,
    moe_instruments, serving_instruments, set_registry, span, startup_done,
    watch_process)

__all__ = [
    "BYTES_BUCKETS", "CapacityError", "Counter", "DeviceOomError",
    "DivergenceError", "ETL_HELP",
    "EtlInstruments", "FleetInstruments", "FlightRecorder", "Gauge",
    "HealthConfig",
    "HealthMonitor", "Histogram", "LoopInstruments", "MetricsListener",
    "MetricsRegistry", "MoeInstruments", "SECONDS_BUCKETS", "STEP_HELP",
    "ServingInstruments", "Timer", "aggregate", "aggregate_snapshot",
    "collect_device_memory", "compile_ledger", "costmodel", "disable",
    "enable", "enabled", "etl_instruments", "fleet_instruments",
    "flight", "get_registry",
    "health", "hlo_audit", "log_buckets", "loop_instruments",
    "memledger", "moe_instruments", "profiler", "prometheus",
    "serving_instruments",
    "set_registry", "slo", "span", "startup_done", "timeseries", "tracing",
]

# the compile and collection listeners go in now, before the importing
# module can build its weights (registry.watch_process says why); they stay
# silent under `telemetry.disable()`
watch_process()
