"""XLA cost-model performance attribution (ISSUE 10 tentpole, second
half).

MFU used to exist only as hand-written FLOP formulas beside a timing
loop: an operator watching /metrics could see a step get slower but had
no authoritative FLOP count to say *how far from peak* the executable
runs, and the analytic formulas can silently disagree with what XLA
actually compiled (the PR-10 audit caught a ResNet formula counting
multiply-accumulates as one FLOP — a 2x MFU understatement against a
peak quoted in real FLOP/s).

Sources of truth:

- **training steps**: ``jitted.lower(*args).cost_analysis()`` — the
  trace+lower is host-side only (no second XLA compile; jax caches the
  lowering by signature, so repeat calls cost ~1 ms) and its ``flops``
  is the HLO cost model's count for exactly the step that runs;
- **serving executables**: ``compiled.cost_analysis()`` +
  ``compiled.memory_analysis()`` captured at AOT warmup, where the
  Compiled object is already in hand (serving/servable.py).

Published metrics (canonical list in docs/OBSERVABILITY.md):

- ``dl4j_flops_per_step{executable}`` — HLO-cost-model FLOPs of one
  execution of the named executable (training loops use their loop
  label; serving buckets use ``model:v<version>:<shape>``);
- ``dl4j_executable_bytes{executable,kind}`` — compiled-executable
  memory footprint (``argument|output|temp|code``), AOT path only;
- ``dl4j_mfu{executable}`` — live model-FLOP utilization:
  ``flops / (step_seconds * peak_flops)``, refreshed every recorded
  step once the loop's FLOP count is known. Peak FLOP/s comes from
  :func:`peak_flops` (the :data:`TPU_PEAK_FLOPS` table keyed by
  ``device_kind``, ``DL4J_PEAK_FLOPS`` override,
  :func:`set_peak_flops`). On a non-TPU device there is no peak and the
  MFU gauge is simply not published (a made-up CPU peak would be noise,
  not observability); a TPU whose kind is not in the table is an error,
  never a silently missing gauge.

Overhead guard: training-loop attribution is *throttled by step time*
(``min_step_seconds``, default 20 ms): a fleet of sub-millisecond unit
-test steps never pays the one-time ~100 ms lower+analyze, while every
flagship workload (ResNet, BERT, LSTM — all ≥ tens of ms/step) is
attributed on its second step. ``configure(min_step_seconds=0)`` forces
attribution everywhere (bench, tests). Failures anywhere in the
analysis degrade to "no metric", never into the training loop.
"""

from __future__ import annotations

import logging
import os
import threading

from deeplearning4j_tpu.telemetry import registry as _registry

log = logging.getLogger("deeplearning4j_tpu")

FLOPS_HELP = ("HLO-cost-model FLOPs for one execution of this "
              "executable (training step or serving bucket), from "
              "XLA cost_analysis() at lower/AOT-warmup time")
BYTES_HELP = ("Compiled-executable memory footprint from "
              "memory_analysis() (kind: argument|output|temp|code)")
MFU_HELP = ("Live model-FLOP utilization: cost-model FLOPs per step / "
            "(step seconds * peak FLOP/s); published once the loop's "
            "executable is attributed and a hardware peak is known")

# Peak dense bf16 FLOP/s of ONE chip, keyed by the exact ``device_kind``
# string jax reports for it (chip_smoke.py prints it). Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16; a v5e chip reports
# itself as "TPU v5 lite".
TPU_PEAK_FLOPS = {"TPU v5 lite": 197e12}

_state = {"min_step_seconds": 0.02, "peak": None, "peak_resolved": False}
_lock = threading.Lock()


def configure(min_step_seconds=None, peak_flops=None):
    """Tune the attribution throttle and/or the hardware peak."""
    if min_step_seconds is not None:
        _state["min_step_seconds"] = float(min_step_seconds)
    if peak_flops is not None:
        set_peak_flops(peak_flops)


def min_step_seconds() -> float:
    return _state["min_step_seconds"]


def set_peak_flops(peak):
    """Override the hardware peak FLOP/s (None forgets the override
    and re-detects on next use)."""
    with _lock:
        _state["peak"] = float(peak) if peak is not None else None
        _state["peak_resolved"] = peak is not None


def peak_flops():
    """Peak FLOP/s for MFU: explicit override > ``DL4J_PEAK_FLOPS`` >
    :data:`TPU_PEAK_FLOPS` by ``device_kind``. None on a non-TPU device
    (MFU unpublished); a TPU kind missing from the table raises."""
    with _lock:
        if _state["peak_resolved"]:
            return _state["peak"]
    peak = None
    env = os.environ.get("DL4J_PEAK_FLOPS")
    if env:
        try:
            peak = float(env)
        except ValueError:
            log.warning("DL4J_PEAK_FLOPS=%r is not a number; ignored",
                        env)
    if peak is None:
        import jax

        dev = jax.devices()[0]
        if dev.platform == "tpu":
            if dev.device_kind not in TPU_PEAK_FLOPS:
                raise LookupError(
                    f"no peak FLOP/s known for TPU device_kind "
                    f"{dev.device_kind!r} (table: "
                    f"{sorted(TPU_PEAK_FLOPS)}); add it to "
                    f"telemetry.costmodel.TPU_PEAK_FLOPS with its source "
                    f"or set DL4J_PEAK_FLOPS")
            peak = TPU_PEAK_FLOPS[dev.device_kind]
    with _lock:
        _state["peak"] = peak
        _state["peak_resolved"] = True
    return peak


# ---------------------------------------------------------------------------
# analysis plumbing
# ---------------------------------------------------------------------------

def _publish_flops(executable, flops, registry=None):
    if not _registry.enabled():
        return
    reg = registry if registry is not None else _registry.get_registry()
    fam = reg.gauge("dl4j_flops_per_step", FLOPS_HELP, ("executable",))
    # scrape-only (like device-memory gauges): WHETHER a host attributed
    # an executable depends on its measured step time, so these families
    # must not join the identical-instrument-set cross-host aggregation
    fam.local = True
    fam.labels(executable=executable).set(flops)


def publish_mfu(executable, flops, seconds, registry=None):
    """Refresh ``dl4j_mfu{executable}`` from one step's wall time.
    No-op without a known hardware peak or a sane measurement."""
    if not _registry.enabled() or not flops or seconds <= 0:
        return None
    peak = peak_flops()
    if not peak:
        return None
    mfu = flops / (seconds * peak)
    reg = registry if registry is not None else _registry.get_registry()
    fam = reg.gauge("dl4j_mfu", MFU_HELP, ("executable",))
    fam.local = True   # see _publish_flops
    fam.labels(executable=executable).set(mfu)
    return mfu


def step_cost(executable, jitted, args, cache=None):
    """Attribute one jitted training step: lower it against ``args``
    (host-side trace only — never a second XLA compile), read the HLO
    cost model, publish ``dl4j_flops_per_step{executable}``, and return
    the per-step FLOPs (None on any failure — attribution must never
    break a fit loop).

    ``cache`` is a caller-owned dict (e.g. an attribute on the net)
    keyed here by the args' shape signature, so refits re-publish from
    the cache instead of re-lowering.

    K-step scanned launches (fitMultiBatch) need no normalization: the
    HLO cost model visits a While/scan body exactly ONCE (the trip count
    is not in the module), so the count it returns already IS
    per-step."""
    if not _registry.enabled():
        return None
    try:
        key = _shape_key(args)
    except Exception:
        key = None
    if cache is not None and key is not None and key in cache:
        flops = cache[key]
        if flops:
            _publish_flops(executable, flops)
        return flops
    flops = None
    try:
        analysis = jitted.lower(*args).cost_analysis()
        if analysis is not None:
            flops = float(analysis.get("flops", 0.0))
    except Exception as e:
        log.debug("cost attribution for %r failed: %s", executable, e)
        flops = None
    if cache is not None and key is not None:
        cache[key] = flops
    if flops:
        _publish_flops(executable, flops)
    return flops


def maybe_attribute(tele, executable, jitted, args, owner, steps_seen,
                    dt_step):
    """The fit-loop attribution idiom, shared by the multilayer /
    graph / sharded loops: attribute the loop's step executable on the
    first QUALIFYING steady-state step — step >= 2 (step 1's wall is
    compile-inflated), the loop not yet attributed (``tele.step_flops``
    unset), and ``dt_step`` clearing the throttle; a step that dips
    under the threshold just defers to a later qualifying one. The
    shape-keyed cost cache lives on ``owner`` (the net/trainer), so
    refits re-publish without re-lowering."""
    if tele is None or tele.step_flops is not None or steps_seen < 2 \
            or dt_step < _state["min_step_seconds"]:
        return
    cache = getattr(owner, "_step_cost_cache", None)
    if cache is None:
        cache = owner._step_cost_cache = {}
    tele.note_flops(step_cost(executable, jitted, args, cache=cache))


def attribute_launch(executable, jitted, args, owner, per_step, warm):
    """The scanned-launch attribution idiom of ``fitMultiBatch``:
    attribute when
    the per-step wall clears the throttle, but publish MFU only for
    ``warm`` launches — the caller knows which walls are honest (a
    first launch compiles inside the timed region; an unmaterialized
    dispatch wall is microseconds), and a dishonest wall must neither
    understate nor overstate the live gauge. Returns the FLOPs (or
    None)."""
    if per_step < _state["min_step_seconds"]:
        return None
    cache = getattr(owner, "_step_cost_cache", None)
    if cache is None:
        cache = owner._step_cost_cache = {}
    flops = step_cost(executable, jitted, args, cache=cache)
    if warm:
        publish_mfu(executable, flops, per_step)
    return flops


def _shape_key(args):
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return tuple(
        (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x))))
        for x in leaves)


def executable_cost(executable, compiled, registry=None):
    """Attribute one AOT-compiled executable (serving warmup):
    ``cost_analysis()`` -> ``dl4j_flops_per_step{executable}``,
    ``memory_analysis()`` -> ``dl4j_executable_bytes{executable,kind}``.
    Returns the FLOPs (None on failure)."""
    if not _registry.enabled():
        return None
    reg = registry if registry is not None else _registry.get_registry()
    flops = None
    try:
        analysis = compiled.cost_analysis()
        if analysis is not None:
            flops = float(analysis.get("flops", 0.0))
            _publish_flops(executable, flops, registry=reg)
    except Exception as e:
        log.debug("cost_analysis for %r failed: %s", executable, e)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            fam = reg.gauge("dl4j_executable_bytes", BYTES_HELP,
                            ("executable", "kind"))
            fam.local = True   # see _publish_flops
            for kind, attr in (("argument", "argument_size_in_bytes"),
                               ("output", "output_size_in_bytes"),
                               ("temp", "temp_size_in_bytes"),
                               ("code", "generated_code_size_in_bytes")):
                val = getattr(mem, attr, None)
                if val is not None:
                    fam.labels(executable=executable, kind=kind).set(val)
    except Exception as e:
        log.debug("memory_analysis for %r failed: %s", executable, e)
    return flops
