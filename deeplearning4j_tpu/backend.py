"""Backend discovery / selection seam.

Reference capability: org.nd4j.linalg.factory.Nd4jBackend.load() —
classpath-scanned backend priority selection between nd4j-native and
nd4j-cuda (SURVEY.md §2.2 "Backend discovery"). Here the backends are
jax platforms ("tpu", "cpu"); discovery enumerates what is loadable and
orders it by priority.

This is an API-parity seam only: no training, serving or benchmark path
consults it. Those run on whatever jax resolved at start-up, and the
entry points that need a chip (chip_smoke.py, benchmark/run.py) check
``jax.devices()[0].platform`` themselves and refuse anything else —
``load()`` offering the CPU at priority 0 must never become "carry on
without the chip".

Selection can be forced with the DL4J_TPU_BACKEND env var ("tpu"/"cpu")
— the analog of ND4J's priority system properties.
"""

from __future__ import annotations

import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Backend:
    """One loadable execution backend."""

    name: str              # "tpu" | "cpu"
    platform: str          # jax platform string ("tpu"/"cpu")
    priority: int          # higher wins (reference: backend priority)
    device_count: int

    def isAvailable(self):
        return self.device_count > 0


class Nd4jBackend:
    """Reference: Nd4jBackend.load() — pick the highest-priority
    available backend exactly once per process."""

    _loaded: Backend | None = None
    _forced: dict = {}

    @classmethod
    def _discover(cls) -> list[Backend]:
        import jax

        found = []
        for plat, priority in (("tpu", 100), ("cpu", 0)):
            try:
                devs = jax.devices(plat)
            except RuntimeError:   # platform not loadable in this process
                continue
            if devs:
                found.append(Backend(plat, plat, priority, len(devs)))
        return found

    @classmethod
    def availableBackends(cls) -> list[Backend]:
        return sorted(cls._discover(), key=lambda b: -b.priority)

    @classmethod
    def load(cls, force: str | None = None) -> Backend:
        """Highest-priority available backend (memoized). `force` or the
        DL4J_TPU_BACKEND env var pin a specific backend name; an
        unavailable forced backend raises instead of silently falling
        back (reference: NoAvailableBackendException)."""
        force = force or os.environ.get("DL4J_TPU_BACKEND")
        if force is not None:
            name = str(force).lower()
            if name in cls._forced:
                return cls._forced[name]
            backends = cls.availableBackends()
            for b in backends:
                if b.name == name:
                    cls._forced[name] = b
                    return b
            raise RuntimeError(
                f"backend {force!r} requested but not available (found: "
                f"{[b.name for b in backends]})")
        if cls._loaded is None:
            backends = cls.availableBackends()
            if not backends:
                raise RuntimeError("no jax backend available")
            cls._loaded = backends[0]
        return cls._loaded

    @classmethod
    def devices(cls, force: str | None = None):
        import jax

        return jax.devices(cls.load(force).platform)

    @classmethod
    def reset(cls):
        """Testing hook: forget the memoized selections."""
        cls._loaded = None
        cls._forced = {}
