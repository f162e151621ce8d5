"""Typed runtime environment configuration.

Reference capability: tier-2 config — `Nd4jEnvironment` / ND4J system
properties and the scattered XLA/platform flags (SURVEY.md §5 "Config /
flag system": "tier 2 becomes XLA/PJRT flags behind one typed config
class"). This module is the one place that owns platform selection,
virtual device counts, matmul precision, debug toggles and the location
of JAX's persistent compilation cache.

Usage (must run BEFORE the first jax backend touch for platform changes):

    from deeplearning4j_tpu.runtime import RuntimeConfig
    RuntimeConfig(platform="cpu", host_device_count=8).apply()
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


@dataclass
class RuntimeConfig:
    """One typed view of every runtime/XLA knob the framework touches.

    platform: "cpu" | "tpu" | None (None = jax default resolution)
    host_device_count: virtual CPU device count (the in-process multi-chip
        simulation; SURVEY.md §4 implication 3)
    matmul_precision: "default" | "high" | "highest" — "highest" forces
        full fp32 MXU passes (needed by the gradient-check harness,
        SURVEY.md §7 "Numerics")
    deterministic: force deterministic op lowering where available
    debug_nans / debug_infs: jax-level NaN/Inf panic (reference:
        OpProfiler NAN_PANIC / INF_PANIC, SURVEY.md §2.3)
    disable_jit: run ops eagerly for debugging (reference: the synchronous
        debug mode, SURVEY.md §5 "Race detection")
    extra_xla_flags: appended verbatim to XLA_FLAGS
    """

    platform: str | None = None
    host_device_count: int | None = None
    matmul_precision: str | None = None
    deterministic: bool = False
    debug_nans: bool = False
    debug_infs: bool = False
    disable_jit: bool = False
    extra_xla_flags: list[str] = field(default_factory=list)

    def apply(self) -> "RuntimeConfig":
        flags = os.environ.get("XLA_FLAGS", "")
        parts = [f for f in flags.split() if f]
        if self.host_device_count is not None:
            parts = [p for p in parts
                     if "xla_force_host_platform_device_count" not in p]
            parts.append("--xla_force_host_platform_device_count="
                         f"{self.host_device_count}")
        for f in self.extra_xla_flags:
            if f not in parts:
                parts.append(f)
        if parts:
            os.environ["XLA_FLAGS"] = " ".join(parts)

        import jax

        # jax may be pre-imported (.pth hook) -> env vars are latched;
        # jax.config.update works until the backend initializes
        if self.platform is not None:
            try:
                jax.config.update("jax_platforms", self.platform)
            except RuntimeError as e:  # backend already up
                raise RuntimeError(
                    "RuntimeConfig.apply() must run before the first "
                    "device access (jax backend already initialized)"
                ) from e
        if self.matmul_precision is not None:
            jax.config.update("jax_default_matmul_precision",
                              self.matmul_precision)
        if self.debug_nans:
            jax.config.update("jax_debug_nans", True)
        if self.debug_infs:
            jax.config.update("jax_debug_infs", True)
        if self.disable_jit:
            jax.config.update("jax_disable_jit", True)
        return self

    @staticmethod
    def enable_compile_cache() -> str:
        """Place JAX's persistent compilation cache; returns its path.

        Called by the entry points (chip_smoke.py, fleet/worker.py
        main) before their first compile — never at
        package import and never from tests/conftest.py: tier-1 tests
        count backend compiles, and a warm cache would change the counts.

        ``JAX_COMPILATION_CACHE_DIR`` wins: when it is set this function
        sets nothing (JAX reads the variable itself), so whoever runs the
        program decides where the cache lives. Unset, the cache goes to
        ``<checkout>/.jax_cache``, derived from this file's location —
        a fixed path, because the directory is part of what a later run
        must find again."""
        env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env:
            return env
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
        return path

    @staticmethod
    def cpu_mesh(n_devices: int = 8,
                 matmul_precision: str = "highest") -> "RuntimeConfig":
        """The in-process multi-chip simulation used by tests and the
        driver's dryrun: n virtual CPU devices, full-precision matmuls."""
        return RuntimeConfig(platform="cpu", host_device_count=n_devices,
                             matmul_precision=matmul_precision)

    @staticmethod
    def environment() -> dict:
        """Runtime environment dump (reference: Nd4jEnvironment /
        Nd4j.getExecutioner().getEnvironmentInformation())."""
        import jax

        devs = jax.devices()
        return {
            "backend": jax.default_backend(),
            "device_count": len(devs),
            "devices": [str(d) for d in devs],
            "process_count": jax.process_count(),
            "jax_version": jax.__version__,
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
        }
