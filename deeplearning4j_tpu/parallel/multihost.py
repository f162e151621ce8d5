"""Multi-host (multi-process) distributed initialization.

Reference capability: the reference's distributed transport — NCCL/MPI +
Aeron UDP parameter serving behind `VoidConfiguration`
(controllerAddress/networkMask/unicastPort, SURVEY.md §2.6/§5). On TPU
pods the transport tier is JAX's distributed runtime: every host runs
the same program, `jax.distributed.initialize` wires the processes
together, and from then on `jax.devices()` spans the whole pod — the
SAME MeshConfig/ShardedTrainer code paths used single-host compile to
collectives that ride ICI within a slice and DCN across slices. No
in-framework transport exists to configure, which is the design the
survey prescribes ("the transport layer is deleted, not ported").

Single-host processes (and the CI environment, which has one chip) can
exercise the full code path with num_processes=1.
"""

from __future__ import annotations

import jax


class VoidConfiguration:
    """Facade with the reference's field names. controllerAddress maps to
    the JAX coordinator address; networkMask/ports collapse (the JAX
    runtime multiplexes one coordinator endpoint)."""

    _FIELDS = ("controllerAddress", "networkMask", "unicastPort",
               "streamId")

    def __init__(self, controllerAddress="127.0.0.1:8476",
                 networkMask=None, unicastPort=None, streamId=None):
        self.controllerAddress = controllerAddress
        if networkMask is not None or unicastPort is not None \
                or streamId is not None:
            from deeplearning4j_tpu.parallel.trainer import _warn_noop_knob

            _warn_noop_knob(
                "VoidConfiguration.networkMask/unicastPort/streamId",
                "the JAX distributed runtime uses one coordinator "
                "endpoint")

    @staticmethod
    def builder():
        class _B:
            def __init__(self):
                self._kw = {}

            def __getattr__(self, item):
                if item not in VoidConfiguration._FIELDS:
                    raise AttributeError(
                        f"VoidConfiguration has no field {item!r} "
                        f"(known: {VoidConfiguration._FIELDS})")

                def setter(v):
                    self._kw[item] = v
                    return self

                return setter

            def build(self):
                return VoidConfiguration(**self._kw)

        return _B()


class MultiHost:
    """Process-group lifecycle for pod-scale training."""

    _initialized = False
    _init_args = None

    @staticmethod
    def initialize(void_config: VoidConfiguration | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None):
        """Wire this process into the pod's process group. Call once per
        process BEFORE any device access; afterwards jax.devices() spans
        all hosts and every existing mesh/trainer scales transparently.

        With num_processes=1 (or under a TPU runtime that provides the
        topology, where all args may be None) this is a no-op beyond
        marking the group initialized."""
        args = ((void_config or VoidConfiguration()).controllerAddress,
                num_processes, process_id)
        if MultiHost._initialized:
            if MultiHost._init_args is not None \
                    and args != MultiHost._init_args \
                    and any(a is not None for a in args[1:]):
                raise RuntimeError(
                    f"MultiHost already initialized with "
                    f"{MultiHost._init_args}; cannot re-initialize with "
                    f"{args} — call shutdown() first")
            return MultiHost.topology()
        coord = args[0]
        if num_processes is not None and num_processes > 1:
            # the CPU backend needs an explicit cross-process
            # collectives implementation (TPU/GPU wire theirs up in
            # PJRT); without it every multi-process CPU computation
            # fails with "Multiprocess computations aren't
            # implemented on the CPU backend". Must be set BEFORE
            # backend init, so no jax.devices()/default_backend()
            # probing here — harmless for non-CPU backends.
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=num_processes,
                                       process_id=process_id)
        MultiHost._initialized = True
        MultiHost._init_args = args
        return MultiHost.topology()

    @staticmethod
    def topology() -> dict:
        return {
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "local_devices": len(jax.local_devices()),
            "global_devices": len(jax.devices()),
        }

    @staticmethod
    def shutdown():
        if MultiHost._initialized and jax.process_count() > 1:
            jax.distributed.shutdown()
        MultiHost._initialized = False
        MultiHost._init_args = None
