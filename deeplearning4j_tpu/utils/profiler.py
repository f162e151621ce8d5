"""Profiler + debug instrumentation.

Reference capability: `org.nd4j.linalg.profiler.{OpProfiler, ProfilerConfig}`
+ `PerformanceTracker` (SURVEY.md §2.3, §5 "Tracing / profiling"): per-op
wall time, NaN/Inf panic checking modes, bandwidth tracking, hooked in at
the op-executioner choke point. The TPU-native equivalent exposed here:

- ProfilerConfig: starts/stops the XLA/PJRT profiler (XPlane traces,
  TensorBoard-compatible) — the SURVEY-prescribed mapping ("PJRT/XLA
  already emits XPlane traces; expose a ProfilerConfig-shaped API").
- StepTimer: per-iteration step time + throughput (PerformanceTracker).
- nan_guard / assert_finite: NAN_PANIC / INF_PANIC modes — a finite-check
  compiled INTO the step (cheap on TPU: one all-reduce over grads) that
  raises host-side naming the first offending variable.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp


@dataclass
class ProfilerConfig:
    """Trace-collection config. `checkForNaN`/`checkForInf` mirror the
    reference's ANY_PANIC modes; `trace_dir` enables XPlane traces viewable
    in TensorBoard (tensorboard --logdir <trace_dir>)."""

    trace_dir: str = "/tmp/dl4j_tpu_trace"
    checkForNaN: bool = False
    checkForInf: bool = False
    _active: bool = field(default=False, repr=False)

    def start(self):
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        self._active = True
        return self

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
        return self.trace_dir

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def trace(self, fn, *args, **kwargs):
        """Profile one call; returns (result, trace_dir)."""
        with self:
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
        return out, self.trace_dir


class StepTimer:
    """Per-iteration timing + items/sec (reference: PerformanceTracker /
    PerformanceListener internals). Synchronizes via a scalar device
    read."""

    def __init__(self, window: int = 50):
        self.window = window
        self.times: list[float] = []
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, sync_value=None) -> float:
        if sync_value is not None:
            jax.block_until_ready(sync_value)
        dt = time.perf_counter() - self._t0
        self.times.append(dt)
        if len(self.times) > self.window:
            self.times.pop(0)
        from deeplearning4j_tpu import telemetry

        if telemetry.enabled():
            # route through the shared registry (ISSUE 1) under this
            # module's own loop label — synced timings, true step time
            telemetry.get_registry().histogram(
                "dl4j_step_seconds", telemetry.STEP_HELP,
                ("loop",)).labels(loop="step_timer").observe(dt)
        return dt

    def mean_step_time(self) -> float:
        return float(np.mean(self.times)) if self.times else 0.0

    def throughput(self, items_per_step: int) -> float:
        m = self.mean_step_time()
        return items_per_step / m if m > 0 else 0.0

    def summary(self, items_per_step: int | None = None) -> dict:
        out = {"steps": len(self.times),
               "mean_step_ms": 1e3 * self.mean_step_time()}
        if items_per_step:
            out["items_per_sec"] = self.throughput(items_per_step)
        return out


def finite_flags(tree) -> jnp.ndarray:
    """Inside-jit helper: per-leaf all-finite flags, one bool per leaf
    (cheap reductions XLA fuses into the step)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.stack([jnp.all(jnp.isfinite(l)) for l in leaves])


def assert_finite(tree, where: str = "gradients"):
    """Host-side check naming the first non-finite variable. Use on the
    OUTPUT of a jitted step (flags computed in-step via finite_flags stay
    on device until this reads them)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    paths = _leaf_paths(tree)
    for path, leaf in zip(paths, leaves):
        arr = np.asarray(leaf)
        if not np.all(np.isfinite(arr)):
            n_nan = int(np.isnan(arr).sum())
            n_inf = int(np.isinf(arr).sum())
            raise FloatingPointError(
                f"non-finite values in {where} at '{path}': "
                f"{n_nan} NaN, {n_inf} Inf (shape {arr.shape}). "
                f"Reference capability: OpProfiler NAN_PANIC mode.")


def _leaf_paths(tree) -> list[str]:
    paths = []
    jax.tree_util.tree_map_with_path(
        lambda p, _: paths.append(jax.tree_util.keystr(p)), tree)
    return paths


def nan_panic_check(profiler_cfg, loss, tree=None, where="parameters",
                    context=""):
    """Shared NAN_PANIC/INF_PANIC check used by the trainers' fit loops.

    No-op unless `profiler_cfg` enables checkForNaN/checkForInf (keeps the
    happy path free of a per-step device sync). On a non-finite loss,
    names the first non-finite leaf in `tree` if any, else blames the
    batch."""
    if profiler_cfg is None or not (
            getattr(profiler_cfg, "checkForNaN", False)
            or getattr(profiler_cfg, "checkForInf", False)):
        return
    lv = float(loss)
    if np.isnan(lv) or np.isinf(lv):
        if tree is not None:
            assert_finite(tree, where)
        raise FloatingPointError(
            f"non-finite loss {lv!r}{context} (NAN_PANIC mode); {where} "
            f"were finite — inspect this batch's features/labels")


def profile_step(fn, *args, trace_dir="/tmp/dl4j_tpu_trace", steps=3):
    """One-command step attribution: runs `steps` calls under the XLA
    profiler and returns the trace dir for TensorBoard."""
    cfg = ProfilerConfig(trace_dir=trace_dir)
    with cfg:
        out = None
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
    return trace_dir
