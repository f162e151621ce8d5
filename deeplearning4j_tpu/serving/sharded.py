"""GSPMD-sharded serving (ISSUE 19): models bigger than one chip.

Every prior serving path — ReplicaSet, the bucket ladder, the paged KV
decode engine — replicates per device, so the largest servable model is
one device's HBM. This module partitions the serving executables across
a ``jax.sharding.Mesh`` instead, while flowing through the SAME
BucketLadder / ModelRegistry / warmup / compile-ledger machinery:

- :class:`ShardedServable` — a :class:`~.servable.Servable` whose
  params carry per-leaf ``NamedSharding`` (GSPMD) and whose inputs are
  replicated (or batch-sharded over the ``data`` axis when the bucket
  divides). Lowering commits to the mesh, so the AOT executables ARE
  the mesh programs — all collectives live inside XLA, dispatched from
  the batcher thread like any single-device call (the host-side
  off-math-path rule from PAPERS.md: shard orchestration never rides
  the per-request path, and no collective is ever issued from a
  router/poll thread — the dl4jlint collective-thread rule can prove
  it, because the Python source contains none);

- :func:`column_parallel_mlp` — the bit-exactness construction: every
  weight is sharded on its OUTPUT dimension over the ``model`` axis
  and activations are constrained back to replicated after each
  matmul. Every reduction (matmul K-loop, layernorm, softmax) is then
  computed full-length on every device — identical operand order to
  the single-device program — so sharded serving is bit-identical
  per row to the unsharded reference, not merely close (asserted in
  tests/test_sharded_serving.py);

- :class:`ShardedTransformerDecodeModel` — the mesh-sharded
  ``PagedKVCache``: :class:`~.decode.TransformerDecodeModel`'s
  attention gathers whole pages out of the pool and reduces each on
  its own, so pages-as-shards is the natural extension — the device
  pools ``[L, n_pages+1, page, H*D]`` are sharded on the PAGE axis
  over the ``model`` axis while the host-side refcounted page table
  (and with it prefix caching and speculative decoding) rides
  unchanged on top. A page's partial and the order in which a slot
  combines its pages are the same whichever device held the page, so
  decode is bit-identical too.

Capacity planning is upgraded from admitting to *placing* (ISSUE 19
satellite): a sharded registration is judged per device — each
device's share of the sharded footprint against THAT device's
headroom (``memledger.plan_capacity(per_device=...)``) — and the
shard layout rides the ``capacity_plan`` flight event as the placement
decision. Rejection carries the per-device breakdown in
``CapacityError.detail["per_device"]``.

The PR-13 compile store is explicitly scoped OUT for sharded entries
(store-reject cause ``sharded_executable``): a serialized SPMD
executable bakes in its device assignment, and this module does not
yet re-bind it at load — a deserialized entry could silently pin a
different device set. ``compile_shape`` therefore always compiles and
ledgers the reject, visible in /debug/compiles forensics.

Testable on CPU: ``--xla_force_host_platform_device_count=N`` makes
the mesh, ``DL4J_DEVICE_BUDGET_BYTES`` makes per-device capacity real.
"""

from __future__ import annotations

import math

import numpy as np

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, spec_for)
from deeplearning4j_tpu.serving.decode import TransformerDecodeModel
from deeplearning4j_tpu.serving.servable import Servable

# the store-reject cause for sharded entries (documented scope-out,
# see module docstring + docs/SERVING.md)
STORE_REJECT_SHARDED = ("sharded_executable: serialized device "
                        "assignment is not re-bound at load")


def mesh_shape(mesh) -> dict:
    """{axis: size} for a mesh — the sharding description the compile
    ledger, /healthz, and the flight placement decision all share."""
    return {a: int(mesh.shape[a]) for a in mesh.axis_names}


def mesh_device_labels(mesh) -> list:
    from deeplearning4j_tpu.telemetry import memledger

    return [memledger.device_label(d) for d in mesh.devices.flat]


def _spec_divisor(mesh, spec) -> int:
    """How many ways a leaf with PartitionSpec ``spec`` splits over
    ``mesh`` — the product of the named axis sizes (a replicated leaf
    divides by 1)."""
    div = 1
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            if a in mesh.shape:
                div *= int(mesh.shape[a])
    return div


def per_device_tree_bytes(tree) -> dict:
    """{device_label: bytes} a placed (possibly sharded) pytree pins
    per device, exact via each array's addressable shards. Replicated
    leaves charge their full bytes to every holding device — this is
    the PHYSICAL footprint, which is what capacity is about."""
    from deeplearning4j_tpu.telemetry import memledger

    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for sh in shards:
            label = memledger.device_label(sh.device)
            out[label] = out.get(label, 0) + int(sh.data.nbytes)
    return out


class ShardedServable(Servable):
    """A mesh-partitioned servable: ``fn(params, x) -> y`` lowered with
    GSPMD ``NamedSharding`` on the params and replicated (or
    batch-sharded) inputs, through the standard bucket-ladder AOT path.

    ``param_specs`` is a pytree of ``PartitionSpec`` matching
    ``params`` (default: fully replicated). ``batch_axis="data"``
    shards bucket inputs over the mesh's data axis when the bucket's
    batch dimension divides it; other buckets fall back to replicated
    inputs — either way the executable commits to the sharding, so the
    ledger's abstract signature carries it and a mesh-shape change
    classifies as ``sharding_change``.
    """

    def __init__(self, fn, params, example_shape, mesh,
                 param_specs=None, dtype=np.float32, batch_axis=None,
                 program_digest=None):
        super().__init__(example_shape, dtype)
        import jax
        from jax.sharding import PartitionSpec as P

        self.mesh = mesh
        self.params = params
        self._fn = fn
        self._jitted = jax.jit(fn)
        if param_specs is None:
            param_specs = jax.tree_util.tree_map(lambda _: P(), params)
        self.param_specs = param_specs
        self.batch_axis = batch_axis
        self._digest = program_digest

    # -- placement ----------------------------------------------------------
    def _param_shardings(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.param_specs,
            is_leaf=lambda s: isinstance(s, P))

    def _placed_args(self) -> tuple:
        """Params placed with their NamedShardings (identity-keyed like
        the base class), with the HBM claims split per mesh device —
        /debug/memory attributes each device's actual shard bytes
        instead of lumping the sharded tree on one label."""
        args = self._call_args()
        key = tuple(map(id, args))
        cached_key, _pinned, cached = self._placed
        if key != cached_key:
            import jax

            placed = jax.device_put(self.params,
                                    self._param_shardings())
            cached = (placed,)
            self._placed = (key, args, cached)
            from deeplearning4j_tpu.telemetry import memledger

            for label, share in sorted(
                    per_device_tree_bytes(placed).items()):
                c = memledger.claim(
                    "replica_args",
                    f"{self._ledger_site()}@{label}",
                    nbytes=share, device=label, sharded=True)
                if c is not None and c not in self._mem_claims:
                    self._mem_claims.append(c)
        return cached

    # -- subclass surface ---------------------------------------------------
    def _jit_fn(self):
        return self._jitted

    def _call_args(self):
        return (self.params,)

    def _program_digest(self):
        return self._digest

    def _batch_spec(self, shape):
        from jax.sharding import PartitionSpec as P

        if (self.batch_axis
                and self.batch_axis in self.mesh.shape
                and shape and shape[0]
                and shape[0] % int(self.mesh.shape[self.batch_axis])
                == 0):
            return spec_for(self.mesh, self.batch_axis)
        return P()

    def _input_spec(self, shape):
        import jax
        from jax.sharding import NamedSharding

        return jax.ShapeDtypeStruct(
            shape, self.dtype,
            sharding=NamedSharding(self.mesh, self._batch_spec(shape)))

    def _sharding_desc(self, shape=None) -> str:
        mesh_s = ",".join(f"{a}={n}" for a, n in
                          mesh_shape(self.mesh).items())
        if shape is None:
            in_s = self.batch_axis or "replicated"
        else:
            spec = self._batch_spec(shape)
            in_s = "replicated" if spec == type(spec)() else str(spec)
        return f"mesh({mesh_s}):in={in_s}"

    # -- compile store: scoped out with an explicit reject cause ------------
    def compile_shape(self, shape: tuple):
        """Always lower + compile: sharded entries never consult the
        persistent executable store (see STORE_REJECT_SHARDED — the
        serialized device assignment is not re-bound at load). When the
        store is otherwise enabled the skip is an explicit, ledgered
        reject, not a silent miss."""
        import time as _time

        from deeplearning4j_tpu import compilestore

        shape = tuple(shape)
        if shape in self._compiled:
            return self._compiled[shape]
        info = None
        if compilestore.enabled():
            info = {"store": "reject", "mode": "compile",
                    "reject_reason": STORE_REJECT_SHARDED}
            from deeplearning4j_tpu import telemetry

            if telemetry.enabled():
                from deeplearning4j_tpu.telemetry import flight

                flight.record("compile_store_reject",
                              site=self._ledger_site(),
                              key=None, reason=STORE_REJECT_SHARDED)
        t0 = _time.perf_counter()
        exe = self._lower_shape(shape).compile()
        self._note_compiled(shape, exe, _time.perf_counter() - t0,
                            info)
        with self._lock:
            self._compiled.setdefault(shape, exe)
        return self._compiled[shape]

    # -- placement planning -------------------------------------------------
    def placement_bytes(self, est) -> dict:
        """The shard layout the capacity planner judges: each mesh
        device's share of the warmup estimate ``est`` (from
        ``estimate_warmup_bytes``). Param leaves divide by their
        spec's mesh-axis product (a replicated leaf is physically full
        on every device); bucket input/output activations are charged
        in full — replicated inputs are the default, and the
        overcharge for batch-sharded buckets errs on the safe side."""
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        leaves = zip(
            jax.tree_util.tree_leaves(self.params),
            jax.tree_util.tree_leaves(
                self.param_specs,
                is_leaf=lambda s: isinstance(s, P)))
        param_share = 0
        for leaf, spec in leaves:
            nbytes = getattr(leaf, "nbytes", 0)
            param_share += int(nbytes) // _spec_divisor(mesh, spec)
        bucket_bytes = sum((est.get("buckets") or {}).values())
        per_dev = param_share + bucket_bytes
        return {label: per_dev for label in mesh_device_labels(self.mesh)}

    def sharded_health(self) -> dict:
        """The /healthz ``sharded`` row for this servable: mesh shape,
        the device set, and the per-device param shard bytes once
        placed."""
        out = {"mesh": mesh_shape(self.mesh),
               "devices": mesh_device_labels(self.mesh),
               "batch_axis": self.batch_axis}
        _key, _host, cached = self._placed
        if cached is not None:
            out["params_per_device_bytes"] = per_device_tree_bytes(
                cached)
        return out


# ---------------------------------------------------------------------------
# bit-exact column-parallel builders
# ---------------------------------------------------------------------------

def _dense_params(sizes, seed):
    rng = np.random.RandomState(seed)
    layers = []
    for d_in, d_out in zip(sizes[:-1], sizes[1:]):
        scale = 1.0 / math.sqrt(d_in)
        layers.append({
            "w": (rng.randn(d_in, d_out) * scale).astype(np.float32),
            "b": np.zeros((d_out,), np.float32)})
    return {"layers": layers}


def column_parallel_mlp(mesh, sizes, seed=0):
    """A tanh MLP whose every weight is column-sharded (output dim)
    over the mesh's ``model`` axis, with activations constrained back
    to replicated after each matmul.

    Returns ``(fn, ref_fn, params, param_specs)``: ``fn`` is the
    sharded program (serve it through :class:`ShardedServable`),
    ``ref_fn`` the same math without sharding constraints (the
    single-device reference) — bit-identical per row by construction:
    every reduction runs full-length on every device, the constraints
    add only all-gathers (exact data movement, no arithmetic)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    params = _dense_params(sizes, seed)
    col = spec_for(mesh, None, MODEL_AXIS)      # [in, out] -> cols
    vec = spec_for(mesh, MODEL_AXIS)
    specs = {"layers": [{"w": col, "b": vec}
                        for _ in params["layers"]]}
    repl = NamedSharding(mesh, P())
    n_layers = len(params["layers"])

    def fn(p, x):
        h = x
        for i, lp in enumerate(p["layers"]):
            h = h @ lp["w"] + lp["b"]
            h = jax.lax.with_sharding_constraint(h, repl)
            if i + 1 < n_layers:
                h = jnp.tanh(h)
        return h

    def ref_fn(p, x):
        h = x
        for i, lp in enumerate(p["layers"]):
            h = h @ lp["w"] + lp["b"]
            if i + 1 < n_layers:
                h = jnp.tanh(h)
        return h

    return fn, ref_fn, params, specs


def sharded_mlp_servable(mesh, sizes, example_shape=None, seed=0,
                         batch_axis=None) -> ShardedServable:
    """The one-call builder the ``"sharded"`` fleet worker kind uses:
    a column-parallel MLP as a ShardedServable on ``mesh``."""
    fn, _ref, params, specs = column_parallel_mlp(mesh, sizes,
                                                  seed=seed)
    return ShardedServable(
        fn, params, example_shape or (int(sizes[0]),), mesh,
        param_specs=specs, batch_axis=batch_axis,
        program_digest=(f"sharded_mlp:{tuple(int(s) for s in sizes)}"
                        f":seed={seed}:mesh={mesh_shape(mesh)}"))


# ---------------------------------------------------------------------------
# the mesh-sharded paged KV cache
# ---------------------------------------------------------------------------

class ShardedTransformerDecodeModel(TransformerDecodeModel):
    """:class:`~.decode.TransformerDecodeModel` with the KV pools
    sharded over the mesh — pages-as-shards.

    The pools ``[L, n_pages+1, page, H*D]`` get
    ``PartitionSpec(None, "model")``: each device owns a contiguous
    block of PAGES. The attention loop gathers whole pages and reduces
    each on its own, so the page axis is the natural shard axis: a
    page's partial and the order in which a slot combines its pages do
    not depend on where the page lay, which is what keeps sharded
    decode bit-identical to the single-device reference. The host-side
    :class:`~.decode.PagedKVCache` (refcounts, page tables, prefix
    caching, speculative adoption) never sees device layout — it
    hands out page NUMBERS — so ISSUE 12's layers ride unchanged.

    ``n_pages`` is rounded up so ``n_pages + 1`` (page 0 is scratch)
    divides the model-axis size — every device owns whole pages.
    Params are placed replicated on the mesh; the per-device footprint
    that matters (and that the engine plans + claims per device) is
    the pool share: ``pool_bytes / model_axis_size`` per device.
    """

    def __init__(self, params, n_heads, mesh, **kw):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        super().__init__(params, n_heads, **kw)
        shard = int(mesh.shape.get(MODEL_AXIS, 1))
        self.pool_shards = shard
        rem = (self.n_pages + 1) % shard
        if rem:
            self.n_pages += shard - rem
        self._pool_sharding = NamedSharding(
            mesh, spec_for(mesh, None, MODEL_AXIS))
        self._repl_sharding = NamedSharding(mesh, P())
        # params replicated ON THE MESH (committed): a jit call mixing
        # mesh-sharded pools with uncommitted host params would re-place
        # the params per dispatch
        self.params = jax.device_put(params, self._repl_sharding)

    def init_state(self):
        import jax
        import jax.numpy as jnp

        zeros = jnp.zeros(self._pool_shape(), jnp.float32)
        return {"k": jax.device_put(zeros, self._pool_sharding),
                "v": jax.device_put(zeros, self._pool_sharding)}

    def _constrain_state(self, state):
        import jax

        return {k: jax.lax.with_sharding_constraint(
                    v, self._pool_sharding)
                for k, v in state.items()}

    def _fn(self, params, state, tokens, pos, table):
        nxt, new_state = super()._fn(params, state, tokens, pos,
                                     table)
        return nxt, self._constrain_state(new_state)

    def masked_fn(self, params, state, tokens, pos, table, active):
        out, new_state = super().masked_fn(params, state, tokens, pos,
                                           table, active)
        return out, self._constrain_state(new_state)

    def pool_device_bytes(self) -> dict:
        """{device_label: bytes} of the KV pools per mesh device — the
        shard layout the engine's capacity plan judges and the
        per-device ``kv_cache`` claims state. Devices that differ only
        along non-model axes hold replicas of the same page block, so
        every device's share is ``total / model_axis_size``."""
        pool = 2 * math.prod(self._pool_shape()) * 4     # k+v, fp32
        per = pool // self.pool_shards
        return {label: per for label in mesh_device_labels(self.mesh)}

    def sharded_health(self) -> dict:
        return {"mesh": mesh_shape(self.mesh),
                "devices": mesh_device_labels(self.mesh),
                "pool_shards": self.pool_shards,
                "kv_pool_per_device_bytes": self.pool_device_bytes()}
