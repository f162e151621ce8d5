"""Device-memory observability: the HBM ownership ledger (ISSUE 14
tentpole).

The stack can attribute every second (ISSUE 10 spans), every FLOP
(costmodel), and every compile (ISSUE 11 ledger) — but before this
module, not a single byte of device memory: ``collect_device_memory``
publishes raw per-device ``bytes_in_use``, and an allocation failure
surfaces as an opaque XLA ``RESOURCE_EXHAUSTED`` with no record of who
owned the HBM. This module is the missing instrument, in three layers:

1. **The claims registry.** Every subsystem that pins device memory
   registers a named, categorized :class:`Claim` — train params /
   updater state / loss-scale state (category ``train``), the paged
   decode KV pools including the speculative draft lane (``kv_cache``),
   serving executables per bucket from the ISSUE-11 ``memory_analysis``
   capture (``executable``), ``DevicePrefetcher`` staged DeviceBatches
   (``prefetch``), ``AsyncCheckpointer`` snapshot clones
   (``checkpoint``), and ``ReplicaSet`` pinned placed-args
   (``replica_args``). Claims reconcile against
   ``device.memory_stats()`` (falling back to live-array accounting on
   backends that report none, e.g. CPU) into
   ``dl4j_device_memory_claimed_bytes{category,device}`` plus an
   explicit ``unattributed`` residual — exported at ``GET
   /debug/memory`` and in the ``/healthz`` ``memory`` section
   (headroom below the configured floor ⇒ degraded, still 200).

2. **OOM forensics.** The instrumented seams (train-step loops,
   ``run_batch``, the decode-engine boundary, prefetch ``device_put``,
   the snapshot clone) catch ``RESOURCE_EXHAUSTED``, emit a flight
   ``oom`` event carrying the requested bytes, the site, and the top-N
   claims at failure, and re-raise a typed :class:`DeviceOomError` —
   an allocation failure now names its neighborhood instead of dying
   anonymously.

3. **Admission-time capacity planning.** ``ModelRegistry`` warmup sums
   the ladder's estimated footprint against live headroom *before*
   compiling anything, and ``DecodeEngine.__init__`` validates its KV
   pool bytes the same way — a structured :class:`CapacityError`
   instead of a mid-ladder OOM (``dl4j_compile_total`` provably flat,
   ledger-asserted in tests). cuDNN (PAPERS.md) is the precedent for
   making the workspace-vs-algorithm memory budget an explicit,
   queryable contract; Dragon-Alpha for pool-based ownership
   accounting in a lean runtime.

Steady-state cost contract (the PR-3/PR-9/PR-11 line): one gauge-set
per training step (``Claim.touch``), and ``telemetry.disable()``
compiles it all out — the loops guard on the claim handle exactly like
they guard on ``loop_instruments`` (CountingStub-asserted,
bit-identical params).
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import re
import threading
import time
import weakref

from deeplearning4j_tpu.telemetry import registry as _registry

log = logging.getLogger("deeplearning4j_tpu")

CLAIMED_HELP = ("Device bytes claimed by each subsystem category in the "
                "HBM ownership ledger (category=unattributed is the "
                "residual against the device's measured bytes_in_use)")

# categories with a fixed meaning (free-form ones are allowed; these are
# the ones the shipped registrars use — docs/OBSERVABILITY.md taxonomy)
CATEGORIES = ("train", "kv_cache", "executable", "prefetch",
              "checkpoint", "replica_args")

_state = {
    "ledger": None,
    # capacity budget for backends that do not report memory_stats
    # (CPU): headroom() treats it as bytes_limit, with live-array
    # accounting standing in for bytes_in_use
    "budget": None,
    "budget_resolved": False,
    # /healthz degradation floor: headroom below this many bytes marks
    # the memory section degraded (still 200); None = fraction of limit
    "min_headroom_bytes": None,
    "min_headroom_fraction": 0.02,
    "top_n": 8,              # claims named in an oom flight event
    "provider": False,       # /healthz provider registered?
}
_lock = threading.Lock()


def configure(budget_bytes=..., min_headroom_bytes=...,
              min_headroom_fraction=None, top_n=None):
    """Tune the ledger: ``budget_bytes`` is the assumed device capacity
    where the backend reports no ``memory_stats`` (None forgets an
    override and re-reads ``DL4J_DEVICE_BUDGET_BYTES``);
    ``min_headroom_bytes`` / ``min_headroom_fraction`` set the /healthz
    degradation floor; ``top_n`` bounds the claims an ``oom`` flight
    event names."""
    with _lock:
        if budget_bytes is not ...:
            _state["budget"] = (None if budget_bytes is None
                                else int(budget_bytes))
            _state["budget_resolved"] = budget_bytes is not None
        if min_headroom_bytes is not ...:
            _state["min_headroom_bytes"] = (
                None if min_headroom_bytes is None
                else int(min_headroom_bytes))
        if min_headroom_fraction is not None:
            _state["min_headroom_fraction"] = float(min_headroom_fraction)
        if top_n is not None:
            _state["top_n"] = int(top_n)


def budget_bytes():
    """The configured capacity assumption for stat-less backends:
    explicit :func:`configure` override > ``DL4J_DEVICE_BUDGET_BYTES``
    > None (capacity unknown — the planner passes)."""
    with _lock:
        if _state["budget_resolved"]:
            return _state["budget"]
    env = os.environ.get("DL4J_DEVICE_BUDGET_BYTES")
    budget = None
    if env:
        try:
            budget = int(float(env))
        except ValueError:
            log.warning("DL4J_DEVICE_BUDGET_BYTES=%r is not a number; "
                        "ignored", env)
    with _lock:
        if not _state["budget_resolved"]:
            _state["budget"] = budget
    return budget


# ---------------------------------------------------------------------------
# typed errors
# ---------------------------------------------------------------------------

class DeviceOomError(RuntimeError):
    """A device allocation failure, enriched at the seam that caught
    it: ``site`` names the instrumented boundary, ``requested_bytes``
    the allocation XLA reported (None when unparseable), ``claims`` the
    top HBM owners at failure (``[{category, name, device, bytes}]``)."""

    def __init__(self, message, site=None, requested_bytes=None,
                 claims=None):
        super().__init__(message)
        self.site = site
        self.requested_bytes = requested_bytes
        self.claims = list(claims or ())


class CapacityError(RuntimeError):
    """Structured admission-time rejection: a prospective allocation
    (`need_bytes` at `site`) exceeds the live device headroom. Raised
    BEFORE any XLA compile / pool allocation — ``detail`` carries the
    planner's per-component breakdown."""

    def __init__(self, message, site=None, need_bytes=None,
                 headroom_bytes=None, detail=None):
        super().__init__(message)
        self.site = site
        self.need_bytes = need_bytes
        self.headroom_bytes = headroom_bytes
        self.detail = dict(detail or {})


# ---------------------------------------------------------------------------
# byte accounting helpers
# ---------------------------------------------------------------------------

def tree_bytes(tree) -> int:
    """Total bytes of a pytree's array leaves. Works for jax arrays,
    numpy arrays, and ShapeDtypeStructs (shape x dtype — the planner's
    eval_shape path); non-array leaves count zero."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
            continue
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            try:
                total += int(np.prod(shape, dtype=np.int64)
                             * np.dtype(dtype).itemsize)
            except Exception:
                pass
    return total


def device_label(device=None) -> str:
    """The ledger's label for a jax device (default: the first local
    device — where unpinned allocations land)."""
    if device is not None:
        return f"{device.platform}:{device.id}"
    try:
        import jax

        d = jax.local_devices()[0]
        return f"{d.platform}:{d.id}"
    except Exception:
        return "unknown:0"


_device_label = device_label


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

class Claim:
    """One subsystem's registered ownership of device bytes. The handle
    is what the owner keeps: ``update(nbytes)`` when the footprint
    changes, ``touch()`` once per step (the one-gauge-set steady-state
    contract), ``release()`` when the memory is handed back."""

    __slots__ = ("category", "name", "device", "bytes", "meta",
                 "created_at", "_ledger", "released")

    def __init__(self, ledger, category, name, nbytes, device, meta):
        self.category = str(category)
        self.name = str(name)
        self.device = device
        self.bytes = int(nbytes)
        self.meta = meta or {}
        self.created_at = time.time()
        self._ledger = ledger
        self.released = False

    def update(self, nbytes=None, tree=None, **meta):
        """Re-state the claim's footprint (and refresh its category
        gauge — one gauge-set)."""
        if tree is not None:
            nbytes = tree_bytes(tree)
        if meta:
            self.meta.update(meta)
        self._ledger.restate(self, int(nbytes) if nbytes is not None
                             else self.bytes)
        return self

    def touch(self):
        """Refresh the (category, device) gauge from the ledger total:
        exactly ONE gauge-set — the per-step steady-state cost."""
        self._ledger.publish_total(self.category, self.device)
        return self

    def release(self):
        self._ledger.release_claim(self)

    def describe(self) -> dict:
        return {"category": self.category, "name": self.name,
                "device": self.device, "bytes": self.bytes,
                "age_seconds": round(time.time() - self.created_at, 3),
                **({"meta": self.meta} if self.meta else {})}


class MemLedger:
    """The process-wide claims table: ``(category, name)`` -> Claim,
    with per-``(category, device)`` running totals so a gauge refresh
    is one dict read + one set."""

    def __init__(self):
        self._claims: dict = {}
        self._totals: dict = {}       # (category, device) -> bytes
        self._lock = threading.Lock()
        self._collected = collections.deque()   # keys, see release_soon

    # -- mutation ------------------------------------------------------------
    def release_soon(self, category, name):
        """For an owner's finalizer, and nothing else. The collector runs
        it between any two instructions of any thread, this ledger's own
        critical sections included, and `_lock` is not re-entrant: a
        finalizer that took it (or the registry's, through the gauge)
        under a collection that began inside `claims()` waited for its
        own thread for ever. So it takes no lock: the key is queued, and
        the claim goes at the ledger's next call (`_reap`), before
        anything is read or published."""
        self._collected.append((str(category), str(name)))

    def _reap(self):
        while self._collected:
            try:
                key = self._collected.popleft()
            except IndexError:               # another thread took it
                break
            self.release(*key)

    def claim(self, category, name, nbytes, device, meta=None) -> Claim:
        self._reap()
        key = (str(category), str(name))
        with self._lock:
            existing = self._claims.get(key)
            if existing is not None:
                self._totals[(existing.category, existing.device)] -= \
                    existing.bytes
                existing.bytes = int(nbytes)
                existing.device = device
                existing.released = False
                if meta:
                    existing.meta.update(meta)
                c = existing
            else:
                c = Claim(self, category, name, nbytes, device, meta)
                self._claims[key] = c
            tkey = (c.category, c.device)
            self._totals[tkey] = self._totals.get(tkey, 0) + c.bytes
        self.publish_total(c.category, c.device)
        return c

    def restate(self, c: Claim, nbytes: int):
        with self._lock:
            if self._claims.get((c.category, c.name)) is not c:
                return                       # already released/replaced
            tkey = (c.category, c.device)
            self._totals[tkey] = \
                self._totals.get(tkey, 0) - c.bytes + nbytes
            c.bytes = nbytes
        self.publish_total(c.category, c.device)

    def release_claim(self, c: Claim):
        with self._lock:
            if self._claims.get((c.category, c.name)) is not c:
                return
            del self._claims[(c.category, c.name)]
            tkey = (c.category, c.device)
            self._totals[tkey] = self._totals.get(tkey, 0) - c.bytes
            c.released = True
        self.publish_total(c.category, c.device)

    def release(self, category, name):
        with self._lock:
            c = self._claims.get((str(category), str(name)))
        if c is not None:
            self.release_claim(c)

    def release_prefix(self, category, name_prefix) -> int:
        """Release every claim in ``category`` whose name starts with
        ``name_prefix`` (rolling-update sweeps). Returns the count."""
        self._reap()
        with self._lock:
            hits = [c for (cat, name), c in self._claims.items()
                    if cat == category and name.startswith(name_prefix)]
        for c in hits:
            self.release_claim(c)
        return len(hits)

    # -- reads ---------------------------------------------------------------
    def claims(self, category=None) -> list:
        self._reap()
        with self._lock:
            out = list(self._claims.values())
        if category is not None:
            out = [c for c in out if c.category == category]
        return sorted(out, key=lambda c: -c.bytes)

    def get(self, category, name):
        self._reap()
        with self._lock:
            return self._claims.get((str(category), str(name)))

    def total(self, category=None, device=None) -> int:
        self._reap()
        with self._lock:
            return sum(v for (cat, dev), v in self._totals.items()
                       if (category is None or cat == category)
                       and (device is None or dev == device))

    def top(self, n=None) -> list:
        n = n if n is not None else _state["top_n"]
        return [c.describe() for c in self.claims()[:n]]

    # -- gauge publication ---------------------------------------------------
    def _gauge(self):
        if not _registry.enabled():
            return None
        fam = _registry.get_registry().gauge(
            "dl4j_device_memory_claimed_bytes", CLAIMED_HELP,
            ("category", "device"))
        # scrape-only, like dl4j_device_mem_bytes: device labels are
        # host-specific and would break cross-host aggregation
        fam.local = True
        return fam

    def publish_total(self, category, device):
        """ONE gauge-set: the running (category, device) total. The
        per-step `touch()` lands here; zero registry calls when
        telemetry is disabled."""
        fam = self._gauge()
        if fam is None:
            return
        with self._lock:
            val = self._totals.get((category, device), 0)
        fam.labels(category=category, device=device).set(max(0, val))

    def publish_all(self, census_rows=None):
        """Refresh every (category, device) gauge plus the
        ``unattributed`` residual per device (scrape-time; see
        :func:`refresh_metrics`)."""
        self._reap()
        fam = self._gauge()
        if fam is None:
            return
        with self._lock:
            totals = dict(self._totals)
        for (category, device), val in sorted(totals.items()):
            fam.labels(category=category, device=device).set(max(0, val))
        for device, row in (census_rows or {}).items():
            resid = row.get("unattributed")
            if resid is not None:
                fam.labels(category="unattributed",
                           device=device).set(max(0, resid))


def get_memledger() -> MemLedger:
    """The process-wide ledger (created lazily). Raw handle — hot-path
    callers outside ``telemetry/`` must gate on ``enabled()`` (or use
    :func:`claim`, which gates internally): the dl4jlint
    telemetry-gate rule enforces it."""
    led = _state["ledger"]
    if led is None:
        with _lock:
            led = _state["ledger"]
            if led is None:
                led = MemLedger()
                _state["ledger"] = led
    return led


def set_ledger(ledger):
    """Swap the process ledger (tests: counting stubs). Returns the
    previous one."""
    prev = _state["ledger"]
    _state["ledger"] = ledger
    return prev


def enabled() -> bool:
    """The ledger follows the one telemetry switch (PR-1 contract)."""
    return _registry.enabled()


def claim(category, name, nbytes=None, tree=None, device=None,
          **meta):
    """Register (or re-state) a claim; the gated high-level entry
    point — returns None when telemetry is disabled, so registrars
    call it unconditionally and hot loops guard on the handle (the
    ``loop_instruments`` idiom)."""
    if not enabled():
        return None
    if tree is not None:
        nbytes = tree_bytes(tree)
    dev = device if isinstance(device, str) else _device_label(device)
    _ensure_provider()
    return get_memledger().claim(category, name, int(nbytes or 0), dev,
                                 meta or None)


_owner_tags = itertools.count(1)


def claim_for_owner(owner, category, prefix, nbytes=None, tree=None,
                    **meta):
    """A claim keyed to one OWNER object (a net, a trainer): the name
    is ``<prefix>#<serial>``, memoized on the owner, so two nets
    training through the same loop label hold two claims instead of
    silently re-stating one (which would misattribute the first net's
    bytes to the unattributed residual). The claim is auto-released
    when the owner is garbage-collected — its memory goes with it."""
    if not enabled():
        return None
    attr = f"_memledger_tag_{prefix}"
    tag = getattr(owner, attr, None)
    fresh = tag is None
    if fresh:
        tag = f"{prefix}#{next(_owner_tags)}"
        try:
            setattr(owner, attr, tag)
        except Exception:
            pass
    c = claim(category, tag, nbytes=nbytes, tree=tree, **meta)
    if c is not None and fresh:
        try:
            weakref.finalize(owner, _release_collected, category, tag)
        except TypeError:
            pass   # unweakrefable owner: the claim simply persists
    return c


def _release_collected(category, name):
    """`claim_for_owner`'s finalizer: queues the key and touches no lock
    (`MemLedger.release_soon`)."""
    led = _state["ledger"]
    if led is not None:
        led.release_soon(category, name)


def release(category, name):
    """Drop a claim by key (idempotent; works whether or not telemetry
    is currently enabled — an owner releasing memory must always be
    able to say so)."""
    led = _state["ledger"]
    if led is not None:
        led.release(category, name)


def release_prefix(category, name_prefix) -> int:
    led = _state["ledger"]
    if led is None:
        return 0
    return led.release_prefix(category, name_prefix)


# ---------------------------------------------------------------------------
# census: claims vs the device's own accounting
# ---------------------------------------------------------------------------

def _device_usage():
    """Per-device {label: {"in_use", "limit", "source"}} from
    ``memory_stats()`` where the backend reports it, else from summing
    live jax arrays (CPU fallback — approximate but honest: it counts
    exactly the buffers the process can still reach)."""
    import jax

    out = {}
    try:
        devices = jax.local_devices()
    except Exception:
        return out
    stat_less = []
    for d in devices:
        label = f"{d.platform}:{d.id}"
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            out[label] = {"in_use": int(stats["bytes_in_use"]),
                          "limit": int(stats.get("bytes_limit", 0)) or None,
                          "source": "memory_stats"}
        else:
            stat_less.append(d)
            out[label] = {"in_use": 0, "limit": budget_bytes(),
                          "source": "live_arrays"}
    if stat_less:
        labels = {d: f"{d.platform}:{d.id}" for d in stat_less}
        try:
            for arr in jax.live_arrays():
                try:
                    devs = list(arr.devices())
                except Exception:
                    continue
                if not devs:
                    continue
                label = labels.get(devs[0])
                if label is not None:
                    # sharded arrays: attribute the per-device share
                    out[label]["in_use"] += int(arr.nbytes) // len(devs)
        except Exception:
            log.debug("live-array census failed", exc_info=True)
    return out


def census() -> dict:
    """Reconcile the claims table against the devices' own accounting:
    per device, claimed bytes by category, measured ``in_use``, and the
    ``unattributed`` residual (``in_use - claimed``, floored at 0).
    Scrape-time only — never on a step path."""
    led = get_memledger()
    usage = _device_usage()
    devices: dict = {}
    for c in led.claims():
        row = devices.setdefault(
            c.device, {"claimed": {}, "claimed_bytes": 0})
        row["claimed"][c.category] = \
            row["claimed"].get(c.category, 0) + c.bytes
        row["claimed_bytes"] += c.bytes
    for label, u in usage.items():
        row = devices.setdefault(
            label, {"claimed": {}, "claimed_bytes": 0})
        row["in_use"] = u["in_use"]
        row["limit"] = u["limit"]
        row["source"] = u["source"]
        row["unattributed"] = max(0, u["in_use"] - row["claimed_bytes"])
        if u["limit"]:
            row["headroom"] = max(0, u["limit"] - u["in_use"])
    return {"devices": devices,
            "claims": [c.describe() for c in led.claims()]}


def refresh_metrics():
    """Refresh every claimed-bytes gauge (incl. the unattributed
    residual) — called by the /metrics and /debug/memory handlers so
    scrapes see a live reconciliation, never on a step path."""
    if not _registry.enabled():
        return
    try:
        snap = census()
    except Exception:
        log.debug("memory census failed", exc_info=True)
        return
    get_memledger().publish_all(snap["devices"])


def describe() -> dict:
    """The GET /debug/memory payload: the full census (claims table,
    per-device reconciliation) plus the planner's view (headroom,
    budget, degradation floor). Served whether or not telemetry is
    currently enabled — incident dumps outlive a disable()."""
    snap = census()
    snap["headroom_bytes"] = _headroom_from(snap)
    snap["budget_bytes"] = budget_bytes()
    snap["min_headroom_bytes"] = _min_headroom(snap)
    return snap


# ---------------------------------------------------------------------------
# headroom + /healthz
# ---------------------------------------------------------------------------

def capacity_known(device=None) -> bool:
    """Whether ANY device has a known capacity (memory_stats limit or
    a configured budget) — cheap: no live-array walk. False means the
    planner will admit regardless, so callers can skip footprint
    estimation entirely (unconfigured deployments pay nothing)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return False
    for d in devices:
        if device is not None and f"{d.platform}:{d.id}" != device:
            continue
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_limit"):
            return True
    return budget_bytes() is not None


def headroom(device=None) -> int | None:
    """Free device bytes the planner can admit against: ``bytes_limit
    - bytes_in_use`` where the backend reports stats; on stat-less
    backends the configured budget minus live-array usage. None =
    capacity unknown (the planner passes — a made-up limit would turn
    the planner into a random request killer). ``device`` restricts
    the judgement to one device label — a servable pinned to an empty
    device must not be rejected for a busy neighbor's sake."""
    if not capacity_known(device=device):
        return None   # skip the live-array walk: nothing to learn
    usage = _device_usage()
    if device is not None:
        usage = {k: v for k, v in usage.items() if k == device}
    best = None
    for row in usage.values():
        if not row["limit"]:
            continue
        free = max(0, row["limit"] - row["in_use"])
        best = free if best is None else min(best, free)
    return best


def _headroom_from(snap) -> int | None:
    """Headroom derived from an already-computed census snapshot —
    the scrape paths (describe / healthz_section) must not walk the
    live arrays a second time just to re-learn it."""
    best = None
    for row in snap.get("devices", {}).values():
        if "headroom" in row:
            best = (row["headroom"] if best is None
                    else min(best, row["headroom"]))
    return best


def _min_headroom(snap=None) -> int | None:
    """The degradation floor in bytes: explicit configure() override,
    else ``min_headroom_fraction`` of the smallest known device
    limit."""
    floor = _state["min_headroom_bytes"]
    if floor is not None:
        return floor
    limits = []
    devices = (snap or {}).get("devices") or census()["devices"]
    for row in devices.values():
        if row.get("limit"):
            limits.append(row["limit"])
    if not limits:
        return None
    return int(min(limits) * _state["min_headroom_fraction"])


def _ensure_provider():
    """Register the /healthz ``memory`` section once (first claim)."""
    with _lock:
        if _state["provider"]:
            return
        _state["provider"] = True
    from deeplearning4j_tpu.telemetry import health

    health.register_healthz_provider("memory", healthz_section)


def healthz_section():
    """The /healthz ``memory`` readiness detail: claimed totals, the
    per-device reconciliation, and the headroom judgement — headroom
    below the floor is ``degraded`` (still HTTP 200: low memory
    informs operators and admission control, it does not stop
    traffic)."""
    snap = census()
    hr = _headroom_from(snap)
    floor = _min_headroom(snap)
    led = get_memledger()
    out = {
        "claimed_bytes": led.total(),
        "claims": len(snap["claims"]),
        "devices": {
            label: {k: row[k] for k in
                    ("claimed_bytes", "in_use", "unattributed",
                     "limit", "headroom") if k in row}
            for label, row in snap["devices"].items()},
        "headroom_bytes": hr,
        "min_headroom_bytes": floor,
    }
    if hr is not None and floor is not None and hr < floor:
        out["degraded"] = True
        out["detail"] = (f"device headroom {hr} bytes below the "
                         f"{floor}-byte floor")
    return out


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

# XLA: "RESOURCE_EXHAUSTED: Out of memory allocating N bytes." /
# "... while trying to allocate N bytes"; host MemoryError has no count
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_BYTES_RE = re.compile(
    r"(?:allocat\w+\s+|allocate\s+)(\d+)\s*(?:bytes|B)\b")


def is_oom(exc) -> bool:
    """Is this exception a device/host allocation failure? (Typed
    DeviceOomErrors are excluded — already converted.)"""
    if isinstance(exc, DeviceOomError):
        return False
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


def requested_bytes(exc) -> int | None:
    m = _BYTES_RE.search(str(exc))
    return int(m.group(1)) if m else None


def oom_error(exc, site, **context) -> DeviceOomError | None:
    """When ``exc`` is an allocation failure: record the flight ``oom``
    event (site, requested bytes, the top-N claims at failure) and
    return the typed :class:`DeviceOomError` for the seam to raise
    (``raise err from exc``) or fail requests with. None when ``exc``
    is not an OOM — the seam re-raises the original. Error path only,
    never steady state."""
    if not is_oom(exc):
        return None
    req = requested_bytes(exc)
    led = _state["ledger"]
    top = led.top() if isinstance(led, MemLedger) else []
    try:
        from deeplearning4j_tpu.telemetry import flight

        flight.record("oom", site=site, requested_bytes=req,
                      error=f"{type(exc).__name__}: {exc}",
                      claims=top, **context)
    except Exception:       # forensics must never mask the failure
        pass
    log.error("device OOM at %s (requested %s bytes); top claims: %s",
              site, req, [(c["category"], c["name"], c["bytes"])
                          for c in top[:3]])
    detail = f" requesting {req} bytes" if req is not None else ""
    return DeviceOomError(
        f"device out of memory at {site}{detail}: "
        f"{type(exc).__name__}: {exc}",
        site=site, requested_bytes=req, claims=top)


def raise_if_oom(exc, site, **context):
    """Seam helper: convert-and-raise when ``exc`` is an OOM, else
    return (the caller re-raises the original)."""
    err = oom_error(exc, site, **context)
    if err is not None:
        raise err from exc


# ---------------------------------------------------------------------------
# admission-time capacity planning
# ---------------------------------------------------------------------------

def plan_capacity(site, need_bytes, detail=None, device=None,
                  per_device=None):
    """Admit or reject a prospective allocation of ``need_bytes`` at
    ``site`` against live headroom. Raises :class:`CapacityError`
    (structured — BEFORE any compile or pool allocation) when headroom
    is known and exceeded; returns the plan dict otherwise. Unknown
    headroom admits: the planner refuses to guess.

    ``per_device`` upgrades the judgement from admitting to PLACING
    (ISSUE 19): a ``{device_label: share_bytes}`` shard layout is
    checked device by device — each device's share against that
    device's own headroom, never the sharded total against any single
    device — and the layout rides the ``capacity_plan`` flight event
    as the placement decision. Rejection carries the full per-device
    breakdown in ``CapacityError.detail["per_device"]``."""
    if per_device:
        return _plan_placement(site, need_bytes, per_device,
                               detail=detail)
    need = int(need_bytes)
    hr = headroom(device=device)
    plan = {"site": site, "need_bytes": need, "headroom_bytes": hr,
            "fits": hr is None or need <= hr,
            **({"detail": dict(detail)} if detail else {})}
    try:
        from deeplearning4j_tpu.telemetry import flight

        flight.record("capacity_plan", **{k: v for k, v in plan.items()
                                          if k != "detail"})
    except Exception:
        pass
    if not plan["fits"]:
        raise CapacityError(
            f"capacity planner rejected {site}: needs {need} bytes, "
            f"only {hr} bytes of device headroom "
            f"(breakdown: {detail or {}})",
            site=site, need_bytes=need, headroom_bytes=hr,
            detail=detail)
    return plan


def _plan_placement(site, need_bytes, per_device, detail=None):
    """The sharded half of :func:`plan_capacity`: judge a shard layout
    (``{device_label: share_bytes}``) against the headroom of exactly
    the mesh's device set. A device with unknown headroom admits its
    share (same refuse-to-guess rule as the scalar path)."""
    need = int(need_bytes)
    layout = {}
    worst = None          # tightest violated device, for the message
    for label, share in sorted(per_device.items()):
        share = int(share)
        hr = headroom(device=label)
        fits = hr is None or share <= hr
        layout[label] = {"share_bytes": share, "headroom_bytes": hr,
                         "fits": fits}
        if not fits and (worst is None
                         or hr - share < worst[2] - worst[1]):
            worst = (label, share, hr)
    plan = {"site": site, "need_bytes": need,
            "sharded": True, "devices": len(layout),
            "fits": worst is None, "per_device": layout,
            **({"detail": dict(detail)} if detail else {})}
    try:
        from deeplearning4j_tpu.telemetry import flight

        flight.record("capacity_plan",
                      **{k: v for k, v in plan.items()
                         if k != "detail"})
    except Exception:
        pass
    if worst is not None:
        label, share, hr = worst
        full = dict(detail or {})
        full["per_device"] = layout
        raise CapacityError(
            f"capacity planner rejected {site}: sharded placement over "
            f"{len(layout)} devices does not fit — {label} needs "
            f"{share} bytes against {hr} bytes of headroom "
            f"(per-device breakdown in detail)",
            site=site, need_bytes=need, headroom_bytes=hr,
            detail=full)
    return plan


def reset_state():
    """Forget claims and configuration (tests)."""
    with _lock:
        _state["ledger"] = None
        _state["budget"] = None
        _state["budget_resolved"] = False
        _state["min_headroom_bytes"] = None
        _state["min_headroom_fraction"] = 0.02
        _state["top_n"] = 8
