"""Compile ledger + recompile forensics (ISSUE 11 tentpole).

PR 9 instrumented *runtime*; compilation stayed a black box: the
``dl4j_compile_total`` counter says a backend compile happened, not
*which site* compiled, *why* (new bucket? dtype flip? donation
mismatch? policy change?), or *what XLA produced*. This module is the
missing register:

- **executable ledger**: every train-step compile (fit / graph /
  sharded step sites) and every serving AOT warmup registers a record —
  site label, abstract argument signature (shapes / dtypes / sharding /
  donation / precision+health policy), compile seconds (attributed from
  the ``jax.monitoring`` backend-compile events the PR-1 hook already
  listens to), HLO fingerprint, cost-model FLOPs — bounded ring, read
  at ``GET /debug/compiles``;
- **recompile forensics**: on a cache miss at a previously-seen site
  the new signature is diffed against the last one and a structured
  *cause* is recorded — ``first_compile``, ``new_bucket`` (serving
  ladder growth), ``shape_change(dim=N)``, ``dtype_change``,
  ``donation_change``, ``policy_change`` (precision policy or health
  build plan compiled into the step), ``sharding_change``, ``rewarm``
  (identical signature rebuilt, e.g. a re-registered servable), or
  ``unknown`` — as a ``dl4j_compile_cause_total{site,cause}`` counter,
  a ``compile_ledger`` flight event, and a ``compile.lower`` span in
  the PR-9 trace tree when the step is inside a sampled trace;
- **HLO audit hookup**: AOT serving executables are audited eagerly at
  warmup (the Compiled object is in hand); train-step records keep a
  weakref + abstract args so ``GET /debug/hlo/<key>`` can lower,
  compile (cached by jax's AOT cache after the first ask), and audit
  on demand — the forensic hot path never pays an extra compile.

Hot-path contract (the PR-1/9 rule): ``note_step`` is called once per
recorded step by the instrumented loops, but its steady-state body is
ONE thread-local read — the ``jax.monitoring`` hook marks the thread
when a backend compile fires, and a step with no pending compile event
returns before touching the ledger, the signature, or anything else.
``telemetry.disable()`` removes the call entirely (the loops guard on
their instrument bundle), so a CountingStub ledger observes ZERO calls
per step and the jitted math is bit-identical.

/healthz gains a ``compile`` section (degraded-not-503, the PR-5/9
convention): sites currently inside a warmup ladder and their progress
fraction, via the standard healthz-provider seam.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict, deque, namedtuple

from deeplearning4j_tpu.telemetry import hlo_audit
from deeplearning4j_tpu.telemetry import registry as _registry

DEFAULT_CAPACITY = 512

CAUSE_HELP = ("Compile-ledger records by step/serving site and "
              "forensic cause (first_compile|new_bucket|"
              "shape_change(dim=N)|shape_change(rank)|dtype_change|"
              "donation_change|policy_change|sharding_change|rewarm|"
              "cache_hit|cache_reject|unknown). cache_hit = the "
              "executable was deserialized from the persistent store "
              "(zero XLA compiles); cache_reject = a corrupt/stale "
              "store entry was dropped and the site recompiled")

_state = {"enabled": True, "ledger": None}
_lock = threading.Lock()
_tls = threading.local()


def enabled() -> bool:
    """Ledger is live: the telemetry master switch AND the ledger flag
    (``telemetry.disable()`` compiles the ledger out with the rest)."""
    return _state["enabled"] and _registry.enabled()


def configure(enabled=None, capacity=None):
    if enabled is not None:
        _state["enabled"] = bool(enabled)
    if capacity is not None:
        get_ledger().resize(int(capacity))


def get_ledger() -> "CompileLedger":
    """The process-wide ledger (created lazily)."""
    led = _state["ledger"]
    if led is None:
        with _lock:
            led = _state["ledger"]
            if led is None:
                led = CompileLedger()
                _state["ledger"] = led
    return led


def set_ledger(ledger):
    """Swap the process ledger (tests: counting stubs). Returns the
    previous ledger."""
    prev = _state["ledger"]
    _state["ledger"] = ledger
    return prev


# ---------------------------------------------------------------------------
# compile-event attribution (fed by the jax.monitoring hook in
# telemetry.registry): backend compiles run synchronously on the
# dispatching thread, so a per-thread buffer attributes them to the
# step/warmup that is live on that thread
# ---------------------------------------------------------------------------

def note_backend_compile(seconds):
    """Called from the PR-1 jit-cache-miss hook: stash this thread's
    compile seconds for the next note_step/record on the same thread.
    Bounded (deque) so a thread nobody ledgers on cannot grow it."""
    if not enabled():
        return
    buf = getattr(_tls, "compiles", None)
    if buf is None:
        buf = _tls.compiles = deque(maxlen=256)
    buf.append(float(seconds))


def consume_backend_compiles():
    """Total backend-compile seconds on this thread since the last
    consume, or None when no compile fired — the note_step fast path."""
    buf = getattr(_tls, "compiles", None)
    if not buf:
        return None
    total = sum(buf)
    buf.clear()
    return total


# ---------------------------------------------------------------------------
# signatures and forensic classification
# ---------------------------------------------------------------------------

# args: tuple of (shape tuple, dtype str) per flattened leaf; donation:
# donated argnums; policy: the caller's compiled-in policy label
# (precision policy + health build plan); sharding: device/mesh label
Signature = namedtuple("Signature", ("args", "donation", "policy",
                                     "sharding"))


def signature_of(args, donation=(), policy=None, sharding=None
                 ) -> Signature:
    """Abstract signature of a concrete argument pytree — exactly the
    identity the jit cache keys on, in hashable/diffable form."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return Signature(
        args=tuple(
            (tuple(getattr(x, "shape", ())),
             str(getattr(x, "dtype", type(x).__name__)))
            for x in leaves),
        donation=tuple(donation or ()),
        policy=str(policy or ""),
        sharding=str(sharding or ""))


def classify(prev, new, bucketed=False):
    """(cause, changed-field list) for a recompile whose signature went
    ``prev`` -> ``new``. ``changed`` names every difference
    ("args[3].shape[0]: 8 -> 16"); ``cause`` is the highest-priority
    one. ``bucketed`` (serving ladders) turns a leading-dim-only shape
    change into ``new_bucket``."""
    if prev is None:
        return "first_compile", []
    changed = []
    shape_dims = []
    dtype_diff = False
    if new.policy != prev.policy:
        changed.append(f"policy: {prev.policy!r} -> {new.policy!r}")
    if new.donation != prev.donation:
        changed.append(
            f"donation: {list(prev.donation)} -> {list(new.donation)}")
    if new.sharding != prev.sharding:
        changed.append(
            f"sharding: {prev.sharding!r} -> {new.sharding!r}")
    arity_changed = len(new.args) != len(prev.args)
    if arity_changed:
        # a different leaf count means the step function's own pytree
        # signature changed — not any one argument's shape; falls
        # through to "unknown" unless a named cause also applies
        changed.append(f"n_args: {len(prev.args)} -> {len(new.args)}")
    else:
        for i, ((ps, pd), (ns, nd)) in enumerate(zip(prev.args,
                                                     new.args)):
            if pd != nd:
                dtype_diff = True
                changed.append(f"args[{i}].dtype: {pd} -> {nd}")
            if ps != ns:
                if len(ps) != len(ns):
                    shape_dims.append(-1)
                else:
                    shape_dims.extend(d for d in range(len(ps))
                                      if ps[d] != ns[d])
                changed.append(
                    f"args[{i}].shape: {list(ps)} -> {list(ns)}")
    if new.policy != prev.policy:
        cause = "policy_change"
    elif dtype_diff:
        cause = "dtype_change"
    elif new.donation != prev.donation:
        cause = "donation_change"
    elif shape_dims:
        dims = sorted(set(shape_dims))
        if bucketed and dims == [0]:
            cause = "new_bucket"
        elif dims[0] < 0:
            cause = "shape_change(rank)"
        else:
            cause = f"shape_change(dim={dims[0]})"
    elif new.sharding != prev.sharding:
        cause = "sharding_change"
    elif changed:
        cause = "unknown"
    else:
        cause = "rewarm"
    return cause, changed


# ---------------------------------------------------------------------------
# the ledger (swappable: set_ledger(CountingStub) in tests)
# ---------------------------------------------------------------------------

def _abstract_args(args):
    """ShapeDtypeStruct pytree for lazy re-lowering (non-array leaves —
    python ints like the step counter — ride through as themselves, so
    nothing pins donated device buffers)."""
    import jax

    def one(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        return jax.ShapeDtypeStruct(shape, dtype)

    return jax.tree_util.tree_map(one, args)


class CompileLedger:
    """Bounded, site-keyed register of compiled executables. All entry
    points are host-side and lock-scoped; nothing here touches a
    device (the lazy audit compiles only when /debug/hlo asks)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._records: OrderedDict = OrderedDict()
        self._sites: dict = {}
        self._lazy: dict = {}
        self._lock = threading.Lock()

    def resize(self, capacity: int):
        with self._lock:
            self.capacity = int(capacity)
            self._trim()

    def _trim(self):
        while len(self._records) > self.capacity:
            key, _ = self._records.popitem(last=False)
            self._lazy.pop(key, None)

    def _site(self, site):
        st = self._sites.get(site)
        if st is None:
            st = self._sites[site] = {
                "last": None, "seen": {}, "fn_ref": None, "seq": 0}
        return st

    # -- recording -----------------------------------------------------------
    def _new_record(self, st, site, sig, cause, changed, kind, seconds,
                    fingerprint, flops, mode="compile", store=None):
        st["seq"] += 1
        # ':' not '#': these keys ride in /debug/hlo/<key> URLs, and a
        # '#' would be stripped client-side as a fragment
        key = f"{site}:{st['seq']}"
        rec = {
            "key": key, "site": site, "seq": st["seq"],
            "ts": round(time.time(), 6), "kind": kind, "cause": cause,
            "mode": mode, "store": store,
            "changed": list(changed),
            "compile_seconds": (round(seconds, 6)
                                if seconds is not None else None),
            "hlo_fingerprint": fingerprint,
            "flops": flops,
            "signature": {
                "n_args": len(sig.args),
                "args": [[list(s), d] for s, d in sig.args[:64]],
                "donation": list(sig.donation),
                "policy": sig.policy,
                "sharding": sig.sharding,
            },
            "audit": None,
        }
        st["seen"][sig] = key
        st["last"] = sig
        self._records[key] = rec
        self._trim()
        return rec

    def observe_step(self, site, jitted, args, sig, seconds=None,
                     window=None):
        """One train-step compile observed at ``site`` (the loops call
        this only after the monitoring hook flagged a backend compile
        on their thread). Returns the new record, or None when the
        compile was a stray (signature already ledgered for this
        function — e.g. a listener's inference executable compiling
        mid-fit)."""
        with self._lock:
            st = self._site(site)
            ref = st["fn_ref"]
            if ref is None or ref() is not jitted:
                # a rebuilt step function starts from empty jit caches:
                # every signature will compile again, and each should
                # be diffed against the site's last, not dropped. The
                # weakref (not a bare id()) makes a GC'd-then-recycled
                # address read as "changed" instead of silently
                # matching — the PR-8 _placed_args lesson
                st["seen"] = {}
                st["fn_ref"] = weakref.ref(jitted)
            if sig in st["seen"]:
                return None
            cause, changed = classify(st["last"], sig, bucketed=False)
            fingerprint = flops = None
            rec = self._new_record(st, site, sig, cause, changed,
                                   "step", seconds, fingerprint, flops)
        # outside the lock: lowering is host-side and cached by jax,
        # but still ~ms — never serialize other sites behind it
        try:
            lowered = jitted.lower(*args)
            rec["hlo_fingerprint"] = hlo_audit.fingerprint(
                lowered.as_text())
            analysis = lowered.cost_analysis()
            if analysis is not None:
                rec["flops"] = float(analysis.get("flops", 0.0))
        except Exception:
            pass
        try:
            self._lazy[rec["key"]] = (weakref.ref(jitted),
                                      _abstract_args(args))
        except Exception:
            pass
        self._emit(rec, window)
        return rec

    def observe_store(self, site, jitted, args, sig, cause, mode,
                      seconds=None, fingerprint=None):
        """One store-resolved train-step executable (StoredJit seam):
        a ``cache_hit`` fires no backend-compile event, so the loop's
        ``note_step`` stays silent and the forensic record is written
        here; a ``cache_reject`` records the recompile under its store
        cause (the StoredJit caller has already claimed the compile
        seconds off the thread buffer, so ``note_step`` cannot
        double-record it)."""
        with self._lock:
            st = self._site(site)
            ref = st["fn_ref"]
            if ref is None or ref() is not jitted:
                st["seen"] = {}
                st["fn_ref"] = weakref.ref(jitted)
            store = "hit" if cause == "cache_hit" else "reject"
            rec = self._new_record(st, site, sig, cause, [], "step",
                                   seconds, fingerprint, None,
                                   mode=mode, store=store)
        try:
            self._lazy[rec["key"]] = (weakref.ref(jitted),
                                      _abstract_args(args))
        except Exception:
            pass
        self._emit(rec)
        return rec

    def record_executable(self, site, compiled, sig, seconds=None,
                          bucketed=True, window=None, store=None,
                          mode="compile", fingerprint=None):
        """One AOT-compiled executable (serving warmup seam, hloaudit
        CLI): the Compiled object is in hand, so the audit and the
        optimized-HLO fingerprint are captured eagerly. ``store``/
        ``mode`` carry the executable-store outcome: a ``hit`` is
        recorded as ``cache_hit`` (the rewarm/new-bucket taxonomy
        names *re*compiles — a deserialize is neither), a ``reject``
        as ``cache_reject``. Store hits skip the eager HLO audit —
        parsing the module text would put compile-scale work back on
        the warm path the store exists to remove; /debug/hlo audits
        the retained executable on demand instead."""
        audit = None
        if store != "hit":
            try:
                audit = hlo_audit.audit_compiled(compiled)
            except Exception:
                audit = None
        with self._lock:
            st = self._site(site)
            if store == "hit":
                cause, changed = "cache_hit", []
            elif store == "reject":
                cause, changed = "cache_reject", []
            elif sig in st["seen"]:
                cause, changed = "rewarm", []
                st["last"] = sig
            else:
                cause, changed = classify(st["last"], sig,
                                          bucketed=bucketed)
            rec = self._new_record(
                st, site, sig, cause, changed, "aot", seconds,
                (audit or {}).get("hlo_fingerprint") or fingerprint,
                (audit or {}).get("flops"), mode=mode, store=store)
            rec["audit"] = audit
            if audit is None:
                try:
                    # lazy direct-audit handle (store hits): avals=None
                    # marks "audit the retained executable itself"
                    self._lazy[rec["key"]] = (weakref.ref(compiled),
                                              None)
                except Exception:
                    pass
        self._emit(rec, window)
        return rec

    def _emit(self, rec, window=None):
        """Metric + flight event + (sampled) trace span for one new
        ledger record."""
        if _registry.enabled():
            try:
                fam = _registry.get_registry().counter(
                    "dl4j_compile_cause_total", CAUSE_HELP,
                    ("site", "cause"))
                fam.local = True   # per-host compile history: scrape-only
                fam.labels(site=rec["site"], cause=rec["cause"]).inc()
            except Exception:
                pass  # stub registries must not break a fit loop
        try:
            from deeplearning4j_tpu.telemetry import flight

            flight.record("compile_ledger", key=rec["key"],
                          site=rec["site"], cause=rec["cause"],
                          seconds=rec["compile_seconds"],
                          fingerprint=rec["hlo_fingerprint"])
        except Exception:
            pass
        try:
            from deeplearning4j_tpu.telemetry import tracing

            ctx = tracing.current()
            if ctx is not None and window is not None:
                tracing.emit("compile.lower", ctx, window[0], window[1],
                             site=rec["site"], cause=rec["cause"],
                             key=rec["key"])
        except Exception:
            pass

    # -- reading -------------------------------------------------------------
    def get(self, key):
        with self._lock:
            return self._records.get(key)

    def describe(self, site=None) -> list:
        """Record dicts, newest first (the GET /debug/compiles
        payload). Eager audits are summarized down to their fingerprint
        here — the full audit lives at /debug/hlo/<key>."""
        with self._lock:
            recs = list(self._records.values())
        out = []
        for r in reversed(recs):
            if site is not None and r["site"] != site:
                continue
            r = dict(r)
            r["audited"] = r.pop("audit") is not None or \
                r["key"] in self._lazy
            out.append(r)
        return out

    def causes(self, site=None) -> dict:
        """{cause: count} over the ledger (tests, quick triage)."""
        out: dict = {}
        for r in self.describe(site=site):
            out[r["cause"]] = out.get(r["cause"], 0) + 1
        return out

    def audit(self, key):
        """The HLO audit for one ledgered executable: eager for AOT
        records, computed on demand for step records (lower + compile
        from the stored abstract signature — cached by jax's AOT cache
        after the first ask). None for an unknown key."""
        with self._lock:
            rec = self._records.get(key)
            lazy = self._lazy.get(key)
        if rec is None:
            return None
        if rec["audit"] is not None:
            return rec["audit"]
        if lazy is None:
            return {"error": "no executable retained for this record"}
        fn_ref, avals = lazy
        jitted = fn_ref()
        if jitted is None:
            return {"error": "step function was garbage-collected"}
        try:
            if avals is None:
                # store-hit AOT record: the retained executable is
                # audited directly (no relowering to do)
                audit = hlo_audit.audit_compiled(jitted)
            else:
                audit = hlo_audit.audit_compiled(
                    jitted.lower(*avals).compile())
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}
        with self._lock:
            if key in self._records:
                self._records[key]["audit"] = audit
        return audit

    def clear(self):
        with self._lock:
            self._records.clear()
            self._sites.clear()
            self._lazy.clear()

    def __len__(self):
        return len(self._records)


# ---------------------------------------------------------------------------
# module-level emission API (every entry checks enabled() FIRST: a
# disabled process makes zero ledger-object calls — the CountingStub
# contract the loops' tele-bundle guard already enforces upstream)
# ---------------------------------------------------------------------------

def note_step(site, jitted, args, policy=None, donation=(0, 1, 2),
              window=None):
    """The fit-loop seam (multilayer / graph / sharded): called per
    recorded step; steady state (no backend compile since the last
    step on this thread) is one thread-local read. On a pending
    compile, the signature is built, diffed, and ledgered."""
    if not enabled():
        return None
    seconds = consume_backend_compiles()
    if seconds is None:
        return None
    sig = signature_of(args, donation=donation, policy=policy)
    return get_ledger().observe_step(site, jitted, args, sig,
                                     seconds=seconds, window=window)


def note_store(site, jitted, args, sig, store, mode, seconds=None,
               fingerprint=None):
    """The StoredJit seam (compilestore): a store ``hit`` writes the
    ``cache_hit`` forensic record the silent monitoring hook cannot
    (deserializing fires no backend compile); a ``reject`` claims the
    recompile's thread-local seconds and records ``cache_reject`` —
    so the loop's later ``note_step`` finds an empty buffer and one
    event yields exactly one ledger record."""
    if not enabled():
        return None
    if store == "reject":
        consumed = consume_backend_compiles()
        if consumed is not None:
            seconds = consumed
        cause = "cache_reject"
    else:
        cause = "cache_hit"
    return get_ledger().observe_store(site, jitted, args, sig, cause,
                                      mode, seconds=seconds,
                                      fingerprint=fingerprint)


def record_executable(site, compiled, args_sig, seconds=None,
                      donation=(), policy=None, sharding=None,
                      bucketed=True, store=None, mode="compile",
                      fingerprint=None):
    """The AOT seam (Servable.compile_shape, tools/hloaudit.py):
    ``args_sig`` is the abstract input signature as ((shape, dtype),
    ...) leaves. Backend-compile events pending on this thread are
    consumed and preferred over the caller's wall-clock ``seconds``
    (the wall includes lowering; a cache-hit rebuild has no events and
    keeps the tiny wall, which is the honest number). ``store``/
    ``mode``/``fingerprint`` carry the executable-store outcome when
    the site resolved through compilestore."""
    if not enabled():
        return None
    consumed = consume_backend_compiles()
    if consumed is not None:
        seconds = consumed
    sig = Signature(
        args=tuple((tuple(s), str(d)) for s, d in args_sig),
        donation=tuple(donation or ()),
        policy=str(policy or ""),
        sharding=str(sharding or ""))
    return get_ledger().record_executable(site, compiled, sig,
                                          seconds=seconds,
                                          bucketed=bucketed,
                                          store=store, mode=mode,
                                          fingerprint=fingerprint)


# ---------------------------------------------------------------------------
# /healthz "compile" section: sites currently compiling + warmup-ladder
# progress (degraded-not-503 — a mid-warmup process informs operators,
# it does not leave rotation beyond what serving readiness already says)
# ---------------------------------------------------------------------------

_active: dict = {}
_active_lock = threading.Lock()


class _WarmupScope:
    """Progress handle for one warmup ladder: ``step()`` after each
    compiled shape; context exit clears the site from /healthz."""

    __slots__ = ("site",)

    def __init__(self, site):
        self.site = site

    def step(self):
        with _active_lock:
            st = _active.get(self.site)
            if st is not None:
                st["done"] += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        with _active_lock:
            _active.pop(self.site, None)
        return False


class _NullScope:
    __slots__ = ()

    def step(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SCOPE = _NullScope()


def warmup_scope(site, total):
    """Mark ``site`` as compiling its warmup ladder of ``total`` shapes
    for the /healthz compile section. No-op handle when telemetry is
    disabled."""
    if not enabled():
        return NULL_SCOPE
    with _active_lock:
        _active[site] = {"t0": time.time(), "done": 0,
                         "total": int(total)}
    return _WarmupScope(site)


def _healthz_section():
    """The /healthz provider payload: {} (section omitted) unless a
    site is mid-compile right now."""
    with _active_lock:
        snap = {site: dict(st) for site, st in _active.items()}
    if not snap:
        return {}
    now = time.time()
    return {
        "compiling": {site: round(now - st["t0"], 3)
                      for site, st in sorted(snap.items())},
        "warmup": {site: {"done": st["done"], "total": st["total"],
                          "fraction": round(st["done"]
                                            / max(1, st["total"]), 3)}
                   for site, st in sorted(snap.items())},
        "degraded": True,
    }


def _install_healthz_provider():
    from deeplearning4j_tpu.telemetry import health

    health.register_healthz_provider("compile", _healthz_section)


_install_healthz_provider()
