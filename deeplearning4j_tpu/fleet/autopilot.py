"""Closed-loop fleet: capture-driven fine-tuning, checkpoint rollouts,
and a self-driving fleet (ISSUE 20 tentpole).

PR 15 built the fleet's open loop: a router in front of N worker
processes, traffic capture, canary rollouts. This module closes it —
three cooperating controllers that turn the fleet from *operated* into
*self-operating*, each reusing an existing subsystem rather than
growing a new one:

- :class:`FleetFineTuner` — train **from** the fleet's own traffic,
  **on** the serving host, **back into** the fleet. A saved
  :class:`~deeplearning4j_tpu.fleet.capture.TrafficCapture` replays
  through ``CaptureReplayIterator`` (the served predictions are the
  distillation labels), the fit runs under the PR-5
  :class:`~deeplearning4j_tpu.resilience.supervisor.Supervisor` (crash
  = resume from checkpoint, not a lost job), and every training step
  holds a ``train``-class admission ticket — the PR-8 controller
  arbitrates trainer-vs-serving on the shared host, shedding the
  trainer FIRST so serving p99 degradation is bounded (on the chip:
  not measured, no cell yet). On completion the newest checkpoint
  auto-publishes through ``router.start_rollout`` with the
  ``from_checkpoint`` spec kind, so the PR-15 canary machinery judges
  the fine-tuned model against its own parent before clients see it.

- :class:`Respawner` — a spawned worker that dies is restarted from
  its recorded spawn command with bounded exponential backoff (the
  supervisor's restart shape at process granularity). Every attempt is
  a ``worker_respawn`` flight event and a
  ``dl4j_fleet_respawns_total{worker,outcome}`` tick; the budget is
  TOTAL per worker (never reset on success), so a crash-looping binary
  gives up instead of flapping forever.

- :class:`Autoscaler` — desired fleet size from a sustained windowed
  request rate (the PR-16 timeseries ring) against per-worker
  capacity, gated by the PR-14 capacity planner
  (``memledger.plan_capacity`` — never spawn a worker the device
  cannot hold), with hysteresis (a direction must persist
  ``sustain_ticks`` consecutive ticks) and a post-action cooldown so
  flapping load does not flap workers. Decisions are ``autoscale``
  flight events; the target is the ``dl4j_fleet_target_workers``
  gauge.

:class:`Autopilot` owns the control loop: ONE daemon thread
(``dl4j:fleet:autopilot``) ticking the respawner and autoscaler;
``router.autopilot`` surfaces every controller's state on
``GET /debug/fleet``. Controllers also expose explicit ``tick()`` so
tests drive them deterministically without the thread.
"""

from __future__ import annotations

import logging
import math
import os
import threading
import time

from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.serving.admission import ShedError
from deeplearning4j_tpu.telemetry import flight

log = logging.getLogger(__name__)

__all__ = ["Autopilot", "Autoscaler", "FleetFineTuner", "Respawner",
           "ThrottledIterator"]

FINETUNE_STATES = ("idle", "training", "publishing", "complete",
                   "failed")


class ThrottledIterator(DataSetIterator):
    """A DataSetIterator that holds a ``train``-class admission ticket
    for the duration of every batch it hands out: the ticket is
    admitted before a batch is returned and released when the NEXT one
    is requested (or the epoch ends), so each in-flight training step
    occupies exactly one standing slot of the model's admission budget
    — the same ledger serving requests are admitted against. When the
    ``train`` class is over its share (serving load holds the budget),
    ``admit`` sheds and the iterator SLEEPS the computed retry_after
    and retries: training pauses, serving proceeds. That is the whole
    arbitration — no second scheduler."""

    def __init__(self, inner, admission, model, sleep=time.sleep,
                 max_wait=60.0):
        super().__init__(inner.batch())
        self.inner = inner
        self.admission = admission
        self.model = model
        self.sleep = sleep
        self.max_wait = float(max_wait)
        self.sheds = 0
        self._ticket = None

    def _release(self):
        if self._ticket is not None:
            self._ticket.release()
            self._ticket = None

    def _admit(self):
        deadline = time.monotonic() + self.max_wait
        while True:
            try:
                self._ticket = self.admission.admit(self.model,
                                                    priority="train")
                return
            except ShedError as e:
                self.sheds += 1
                if time.monotonic() >= deadline:
                    raise
                self.sleep(min(e.retry_after, 1.0))

    def reset(self):
        self._release()
        self._peek = None
        self.inner.reset()

    def _next_batch(self):
        self._release()
        batch = self.inner._next_batch()
        if batch is None:
            return None
        self._admit()
        return batch

    def close(self):
        self._release()


def _incumbent_version(router, model) -> int:
    """Highest served version of ``model`` across live workers (the
    rollout's own incumbent-discovery rule)."""
    with router._lock:
        return max((m.get("version") or 0
                    for w in router.workers if w.up for m in w.models
                    if m.get("name") == model), default=0)


class FleetFineTuner:
    """Capture → fine-tune → publish, one job per instance.

    factory: zero-arg callable building the net to fine-tune when no
        checkpoint exists yet — typically loads the serving model's
        weights (first attempt only; restarts resume from checkpoint);
    capture_path: a saved TrafficCapture (rotated sets replay whole);
    checkpoint_dir: where the supervised fit checkpoints — its newest
        checkpoint is what gets published;
    admission: the worker-host AdmissionController to arbitrate
        against (None trains unthrottled — off-host training);
    spec_extra: merged into the published ``from_checkpoint`` spec
        (``example_shape`` etc.);
    rollout_kw: forwarded to ``router.start_rollout``. Fine-tuning
        legitimately CHANGES outputs, so ``min_agreement`` defaults to
        0.0 here — the canary is judged on errors and p99 (and SLO
        burn when configured), not on bit-agreement with its parent.
    """

    def __init__(self, router, model, capture_path, factory,
                 checkpoint_dir, admission=None, epochs=1,
                 batch_size=32, supervisor_config=None, spec_extra=None,
                 rollout_kw=None, sleep=time.sleep, **trainer_kw):
        self.router = router
        self.model = model
        self.capture_path = capture_path
        self.factory = factory
        self.checkpoint_dir = str(checkpoint_dir)
        self.admission = admission
        self.epochs = int(epochs)
        self.batch_size = int(batch_size)
        self.supervisor_config = supervisor_config
        self.spec_extra = dict(spec_extra or {})
        self.rollout_kw = dict(rollout_kw or {})
        self.sleep = sleep
        self.trainer_kw = trainer_kw
        self.state = "idle"
        self.error = None
        self.checkpoint = None
        self.published_version = None
        self.sheds = 0
        self._thread = threading.Thread(
            target=self._run_thread, daemon=True,
            name=f"dl4j:fleet:finetune-{model}")

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        """Run the job on its own daemon thread; ``join()`` to wait."""
        self._thread.start()
        return self

    def join(self, timeout=None):
        self._thread.join(timeout)
        return self

    def close(self):
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def _run_thread(self):
        try:
            self.run()
        except Exception:
            log.exception("fine-tune job for %s failed", self.model)

    # -- the job -------------------------------------------------------------
    def run(self):
        """Synchronous capture → fit → publish. Returns the started
        RolloutController (the canary judges the result); raises and
        flips to ``failed`` when any stage does."""
        from deeplearning4j_tpu.fleet.capture import (
            CaptureReplayIterator)
        from deeplearning4j_tpu.parallel.elastic import ElasticTrainer
        from deeplearning4j_tpu.resilience.supervisor import Supervisor

        self.state = "training"
        flight.record("finetune_start", model=self.model,
                      capture=str(self.capture_path),
                      epochs=self.epochs,
                      checkpoint_dir=self.checkpoint_dir)
        try:
            data = CaptureReplayIterator(self.capture_path,
                                         batch_size=self.batch_size,
                                         model=self.model)
            if data.totalExamples() == 0:
                raise ValueError(
                    f"capture {self.capture_path!r} holds no examples "
                    f"for model {self.model!r}")
            throttled = None
            if self.admission is not None:
                data = throttled = ThrottledIterator(
                    data, self.admission, self.model, sleep=self.sleep)
            sup = Supervisor(self.factory, self.checkpoint_dir,
                             config=self.supervisor_config,
                             sleep=self.sleep, **self.trainer_kw)
            try:
                sup.run(data, epochs=self.epochs)
            finally:
                if throttled is not None:
                    self.sheds = throttled.sheds
                    throttled.close()
            ckpt = ElasticTrainer.latest(self.checkpoint_dir)
            if ckpt is None:
                raise RuntimeError(
                    f"fine-tune finished but {self.checkpoint_dir!r} "
                    f"holds no checkpoint")
            self.checkpoint = ckpt
            self.state = "publishing"
            ctl = self._publish(ckpt)
        except BaseException as e:
            self.state = "failed"
            self.error = f"{type(e).__name__}: {e}"
            flight.record("finetune_complete", model=self.model,
                          outcome="failed", error=self.error)
            raise
        self.state = "complete"
        flight.record("finetune_complete", model=self.model,
                      outcome="ok", checkpoint=self.checkpoint,
                      version=self.published_version,
                      train_sheds=self.sheds)
        return ctl

    def _publish(self, ckpt):
        version = _incumbent_version(self.router, self.model) + 1
        spec = {"kind": "from_checkpoint", "checkpoint": ckpt,
                **self.spec_extra}
        kw = dict(self.rollout_kw)
        kw.setdefault("min_agreement", 0.0)
        flight.record("finetune_publish", model=self.model,
                      checkpoint=ckpt, version=version)
        ctl = self.router.start_rollout(self.model, spec, version, **kw)
        self.published_version = version
        return ctl

    def describe(self) -> dict:
        return {"model": self.model, "state": self.state,
                "capture": str(self.capture_path),
                "checkpoint": self.checkpoint,
                "published_version": self.published_version,
                "train_sheds": self.sheds, "error": self.error}


class Respawner:
    """Restart dead SPAWNED workers from their recorded spawn command.

    Only workers carrying a spawn record (``WorkerHandle.spawn``, set
    by ``spawn_local_workers``) are eligible — an adopted URL has no
    process to restart. Backoff follows the supervisor's shape
    (``SupervisorConfig.backoff``); the attempt budget is TOTAL per
    worker and never resets, so a binary that keeps crashing is given
    up on (outcome ``gave_up``) rather than respawned forever. The
    router's existing poll loop readmits a respawned worker once its
    /healthz answers — respawning and readmission stay two separate
    judgements, same as startup."""

    def __init__(self, router, config=None, max_respawns=3,
                 spawn_timeout=30.0, clock=time.monotonic, popen=None):
        from deeplearning4j_tpu.resilience.supervisor import (
            SupervisorConfig)

        self.router = router
        self.config = config or SupervisorConfig()
        self.max_respawns = int(max_respawns)
        self.spawn_timeout = float(spawn_timeout)
        self.clock = clock
        self._popen = popen
        self._state: dict = {}   # worker -> {attempts, next_at, gave_up}

    def _worker_state(self, name):
        st = self._state.get(name)
        if st is None:
            st = self._state[name] = {"attempts": 0, "next_at": 0.0,
                                      "gave_up": False}
        return st

    def tick(self) -> list:
        """One control round: respawn every eligible dead worker whose
        backoff has elapsed. Returns [(worker, outcome)] for the
        attempts made this round."""
        out = []
        if self.router._stop.is_set():
            # the router is tearing down: close() is terminating the
            # very processes a respawn would resurrect — a revived
            # worker here outlives the fleet as an orphan
            return out
        for w in list(self.router.workers):
            if w.proc is None or w.spawn is None:
                continue
            if w.proc.poll() is None:
                continue   # alive
            st = self._worker_state(w.name)
            if st["gave_up"] or self.clock() < st["next_at"]:
                continue
            if st["attempts"] >= self.max_respawns:
                st["gave_up"] = True
                self._note(w, "gave_up", st["attempts"])
                out.append((w.name, "gave_up"))
                continue
            st["attempts"] += 1
            try:
                self._respawn(w)
                outcome = "ok"
            except Exception as e:
                outcome = "failed"
                log.warning("respawn of %s failed: %s", w.name, e)
            st["next_at"] = self.clock() \
                + self.config.backoff(st["attempts"])
            self._note(w, outcome, st["attempts"])
            out.append((w.name, outcome))
        return out

    def _respawn(self, w):
        import subprocess

        spawn = w.spawn
        try:
            os.remove(spawn["port_file"])
        except OSError:
            pass
        popen = self._popen or subprocess.Popen
        proc = popen(spawn["cmd"], env=spawn["env"])
        deadline = time.monotonic() + self.spawn_timeout
        port = None
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"respawned worker {w.name} exited "
                    f"rc={proc.returncode} before binding a port")
            try:
                with open(spawn["port_file"]) as f:
                    port = int(f.read().strip())
                break
            except (OSError, ValueError):
                time.sleep(0.05)
        if port is None:
            proc.kill()
            raise TimeoutError(
                f"respawned worker {w.name} never bound a port "
                f"within {self.spawn_timeout}s")
        with self.router._lock:
            w.proc = proc
            w.url = f"http://127.0.0.1:{port}"

    def _note(self, w, outcome, attempt):
        flight.record("worker_respawn", worker=w.name, outcome=outcome,
                      attempt=attempt, max_respawns=self.max_respawns)
        inst = self.router._inst()
        if inst is not None:
            inst.respawn(w.name, outcome)
        lvl = log.info if outcome == "ok" else log.warning
        lvl("fleet worker %s respawn attempt %d: %s", w.name, attempt,
            outcome)

    def describe(self) -> dict:
        return {"max_respawns": self.max_respawns,
                "workers": {n: dict(st)
                            for n, st in self._state.items()}}


class Autoscaler:
    """Spawn/retire workers from sustained load.

    load_fn: zero-arg callable returning the current fleet request
        rate (requests/second); the default reads the PR-16 timeseries
        ring's windowed rate of ``load_key`` (None — sampler cold —
        reads as 0.0);
    worker_rps: one worker's capacity; the target size is
        ``ceil(load / worker_rps)`` clamped to [min_workers,
        max_workers];
    sustain_ticks: a target differing from the current size must hold
        for this many CONSECUTIVE ticks before any action (hysteresis);
    cooldown: seconds after an action during which no further action
        is taken (the just-changed fleet must show up in the window
        before being judged again);
    need_bytes: estimated device footprint of one more worker — gated
        through ``memledger.plan_capacity`` before every spawn, so the
        autoscaler never spawns what cannot be placed (decision
        ``blocked``);
    spawn_fn: ``(spec, name) -> WorkerHandle`` override for tests; the
        default shells out through ``spawn_local_workers``.

    One action per tick (a single spawn or retire) — small blast
    radius; convergence to a far target takes several sustained ticks
    by design. Scale-down prefers the newest autoscaler-spawned
    worker and never retires below ``min_workers``.
    """

    def __init__(self, router, spec, load_key, worker_rps,
                 min_workers=1, max_workers=4, sustain_ticks=3,
                 cooldown=10.0, window=None, need_bytes=0,
                 load_fn=None, spawn_fn=None, base_dir=None,
                 clock=time.monotonic):
        self.router = router
        self.spec = spec
        self.load_key = load_key
        self.worker_rps = float(worker_rps)
        self.min_workers = int(min_workers)
        self.max_workers = int(max_workers)
        self.sustain_ticks = int(sustain_ticks)
        self.cooldown = float(cooldown)
        self.window = window
        self.need_bytes = int(need_bytes)
        self.load_fn = load_fn or self._timeseries_load
        self.spawn_fn = spawn_fn
        self.base_dir = base_dir
        self.clock = clock
        self.last_load = 0.0
        self.last_desired = None
        self.last_decision = None
        self._pending = None
        self._pending_ticks = 0
        self._cooldown_until = 0.0
        self._spawned = 0
        # ticks arrive from the autopilot thread AND direct callers;
        # two racing scale-ups would each spawn a same-named worker
        # and the loser's process would leak (add_worker refuses dupes)
        self._tick_lock = threading.Lock()

    def _timeseries_load(self) -> float:
        from deeplearning4j_tpu.telemetry import timeseries

        return timeseries.rate(self.load_key, self.window) or 0.0

    def desired(self, load) -> int:
        return max(self.min_workers,
                   min(self.max_workers,
                       int(math.ceil(load / self.worker_rps))))

    def tick(self):
        """One control round. Returns the decision taken this round
        (``scale_up`` / ``scale_down`` / ``blocked``) or None when the
        round held steady (satisfied, sustaining, or cooling down)."""
        with self._tick_lock:
            return self._tick()

    def _tick(self):
        load = float(self.load_fn())
        target = self.desired(load)
        self.last_load, self.last_desired = load, target
        inst = self.router._inst()
        if inst is not None:
            inst.target_workers.set(float(target))
        current = len(self.router.workers)
        if target == current:
            self._pending, self._pending_ticks = None, 0
            return None
        if self.clock() < self._cooldown_until:
            return None
        if self._pending != target:
            # direction (or magnitude) changed: restart the sustain
            # count — flapping load keeps resetting this and never acts
            self._pending, self._pending_ticks = target, 1
        else:
            self._pending_ticks += 1
        if self._pending_ticks < self.sustain_ticks:
            return None
        decision = self._act(target, current, load)
        if decision is not None and decision != "blocked":
            self._cooldown_until = self.clock() + self.cooldown
            self._pending, self._pending_ticks = None, 0
        self.last_decision = decision
        return decision

    def _act(self, target, current, load):
        from deeplearning4j_tpu.telemetry import memledger

        if target > current:
            name = f"auto{self._spawned}"
            try:
                memledger.plan_capacity(
                    "fleet:autoscale", self.need_bytes,
                    detail={"worker": name})
            except memledger.CapacityError as e:
                flight.record("autoscale", decision="blocked",
                              worker=name, load=round(load, 3),
                              desired=target, current=current,
                              error=str(e))
                log.warning("autoscale blocked by capacity planner: %s",
                            e)
                return "blocked"
            try:
                w = self._spawn(name)
            except Exception as e:
                flight.record("autoscale", decision="blocked",
                              worker=name, load=round(load, 3),
                              desired=target, current=current,
                              error=f"{type(e).__name__}: {e}")
                log.warning("autoscale spawn failed: %s", e)
                return "blocked"
            self._spawned += 1
            try:
                self.router.add_worker(w)
            except Exception:
                # never orphan the process we just spawned: a handle
                # the router refused has no owner to terminate it
                if w.proc is not None and w.proc.poll() is None:
                    w.proc.kill()
                raise
            flight.record("autoscale", decision="scale_up",
                          worker=w.name, load=round(load, 3),
                          desired=target, current=current + 1)
            return "scale_up"
        victim = self._victim()
        if victim is None:
            return None
        self.router.retire_worker(victim.name)
        flight.record("autoscale", decision="scale_down",
                      worker=victim.name, load=round(load, 3),
                      desired=target, current=current - 1)
        return "scale_down"

    def _spawn(self, name):
        if self.spawn_fn is not None:
            return self.spawn_fn(self.spec, name)
        from deeplearning4j_tpu.fleet.router import spawn_local_workers

        idx = int(name[len("auto"):])
        return spawn_local_workers(
            1, self.spec, base_dir=self.base_dir,
            name_prefix="auto", start_index=idx)[0]

    def _victim(self):
        with self.router._lock:
            if len(self.router.workers) <= self.min_workers:
                return None
            auto = [w for w in self.router.workers
                    if w.name.startswith("auto")]
            return (auto or self.router.workers)[-1]

    def describe(self) -> dict:
        return {"load": round(self.last_load, 3),
                "desired": self.last_desired,
                "current": len(self.router.workers),
                "min_workers": self.min_workers,
                "max_workers": self.max_workers,
                "worker_rps": self.worker_rps,
                "sustain_ticks": self.sustain_ticks,
                "pending": self._pending,
                "pending_ticks": self._pending_ticks,
                "cooldown_until": self._cooldown_until,
                "last_decision": self.last_decision}


class Autopilot:
    """The control loop that makes the fleet self-driving: one daemon
    thread ticking the :class:`Respawner` and :class:`Autoscaler` at
    ``interval``; fine-tune jobs run on their own threads and are only
    tracked here. ``start()`` attaches the autopilot to the router, so
    ``GET /debug/fleet`` shows every controller's live state."""

    def __init__(self, router, respawner=None, autoscaler=None,
                 interval=0.5):
        self.router = router
        self.respawner = respawner
        self.autoscaler = autoscaler
        self.interval = float(interval)
        self.finetuners: list = []
        self.ticks = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="dl4j:fleet:autopilot")

    def start(self):
        self.router.autopilot = self
        self._thread.start()
        flight.record("autopilot_start",
                      respawner=self.respawner is not None,
                      autoscaler=self.autoscaler is not None,
                      interval=self.interval)
        return self

    def close(self, timeout=5.0):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)
        for ft in self.finetuners:
            ft.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def fine_tune(self, *args, **kw) -> FleetFineTuner:
        """Start a :class:`FleetFineTuner` job (its own thread) and
        track it for /debug/fleet."""
        ft = FleetFineTuner(self.router, *args, **kw)
        self.finetuners.append(ft)
        return ft.start()

    def tick(self):
        """One explicit control round (what the thread does each
        interval) — deterministic handle for tests."""
        self.ticks += 1
        if self.respawner is not None:
            try:
                self.respawner.tick()
            except Exception:
                log.exception("respawner tick failed")
        if self.autoscaler is not None:
            try:
                self.autoscaler.tick()
            except Exception:
                log.exception("autoscaler tick failed")

    def _loop(self):
        while not self._stop.wait(self.interval):
            self.tick()

    def describe(self) -> dict:
        out = {"interval": self.interval, "ticks": self.ticks,
               "running": self._thread.is_alive()}
        if self.respawner is not None:
            out["respawner"] = self.respawner.describe()
        if self.autoscaler is not None:
            out["autoscaler"] = self.autoscaler.describe()
        if self.finetuners:
            out["finetune"] = [ft.describe() for ft in self.finetuners]
        return out
