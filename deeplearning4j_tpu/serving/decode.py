"""Continuous (iteration-level) batching for autoregressive decode
(ISSUE 8 tentpole b).

The PR-2 serving path batches at REQUEST granularity: a batch executes
start-to-finish, so a 5-token completion waits for the 200-token one it
shares a batch with, and a request arriving mid-batch waits for the
whole batch to drain. Token streams need iteration-level batching (the
Orca/vLLM scheduling insight): the device executes ONE token step for
every in-flight sequence per iteration, new sequences join the batch at
any token boundary, and finished sequences free their slot immediately.

Fixed shapes everywhere: the step function is compiled ONCE for
``[max_slots]`` token vectors and a preallocated paged KV pool — joins
and leaves change the CONTENT of slots, never a shape, so the steady
state adds nothing to ``dl4j_compile_total`` (the PR-2 contract,
asserted in tests).

The KV cache is PAGED (`PagedKVCache`): a pool of fixed-size
``[page]``-token blocks with a per-slot page table. A joining sequence
reserves ``ceil(total_len / page)`` pages up front (no mid-flight
eviction), a leaving one returns them; page 0 is a scratch page that
idle slots write into so the step function stays branch-free.
Attention visits the batch's LIVE pages only: one list of (slot, page)
pairs a step, made on the device from ``pos`` and the page table, one
loop a layer over chunks of it whose trip count is read from the data,
each page reduced on its own to a softmax partial ``(m, l, o)`` and a
slot's partials combined in its page table's order (split-K over
pages; no collectives: a decode replica is single-device; the engine
thread must stay collective-free per the dl4jlint collective-thread
rule).

Two shipped models:

- `RnnDecodeModel`: wraps a real `MultiLayerNetwork` with recurrent
  layers — slot state is the per-slot ``{h, c}`` carry rows (the
  repo's `rnnTimeStep` streaming state, batched over slots). Params
  are read live from the net: train-and-serve keeps working.
- `TransformerDecodeModel`: causal decode-only transformer over the
  paged KV pool, mirroring `models/bert.py`'s post-LN block so
  `from_bert()` can lift a trained BERT encoder's weights into a
  token-stream servable (tied LM head).

Per-sequence determinism: every op along a slot's compute path is
row-wise (LSTM carries, masked paged attention, layer norm, argmax),
so a sequence's tokens are BIT-IDENTICAL whether it decodes alone or
wedged between strangers — asserted by tests, and the property that
makes continuous batching safe to enable by default.
"""

from __future__ import annotations

import contextlib
import math
import queue as _queue
import threading
import time
from concurrent.futures import Future

import numpy as np

from deeplearning4j_tpu.telemetry import compile_ledger, tracing
from deeplearning4j_tpu.telemetry.registry import (DispatchAccount,
                                                   startup_done)


class DecodeError(RuntimeError):
    pass


class DecodeShutdown(RuntimeError):
    """Engine closed with this request still pending."""


def _phase(inst, phase, account):
    """The Timer over one phase of a boundary (histogram and
    `dl4j.decode.<phase>` span), or nothing when telemetry is off. The
    five phases are leaves, each observed once a delivered boundary, and
    no span stands around the whole of an iteration, so that a reader of
    a device trace can give every idle gap to the phase that covers it.
    An iteration of the engine's loop runs `admit`, `build` and
    `dispatch` of one boundary and then `readback` and `emit`: of the
    same boundary where the engine is serial, of the boundary before it
    where one token step is in flight (`DecodeEngine._step_boundary`).
    Each Timer also adds its seconds to the engine's ``account``, from
    which the next token-step dispatch takes what the interval since the
    last one spent under no span (`ServingInstruments.dispatched`)."""
    if inst is None:
        return contextlib.nullcontext()
    return inst.phase(phase, account)


# ---------------------------------------------------------------------------
# paged KV bookkeeping (host side)
# ---------------------------------------------------------------------------

class PagedKVCache:
    """Host-side page accounting for a preallocated device KV pool.

    `n_pages` counts the usable pool (page 0 is reserved scratch for
    idle slots, so the device pool must hold ``n_pages + 1`` pages).
    Allocation is all-up-front per sequence: `reserve()` either grants
    every page the sequence can ever touch or refuses — admission
    control at the slot boundary instead of mid-decode eviction.

    Pages are REFCOUNTED (ISSUE 12): a slot's reservation holds one
    reference per page, and the cross-request `PrefixCache`
    (serving/prefix_cache.py) holds its own reference on pages it has
    published. A page returns to the free pool only when its last
    reference drops — so a finished request's shared-prefix pages
    stay resident for the next request to adopt, and `release()`
    after `clear()`-ing the cache provably returns the pool to fully
    free (the leak assertion in tests)."""

    def __init__(self, n_pages, page, max_pages_per_slot, max_slots):
        if page < 1 or n_pages < 1:
            raise ValueError(f"need page >= 1 and n_pages >= 1, got "
                             f"page={page} n_pages={n_pages}")
        self.page = int(page)
        self.n_pages = int(n_pages)
        self.max_pages_per_slot = int(max_pages_per_slot)
        # page 0 = scratch; usable pages are 1..n_pages
        self._free = list(range(self.n_pages, 0, -1))
        self.table = np.zeros((max_slots, self.max_pages_per_slot),
                              np.int32)
        self._owned: dict[int, list[int]] = {}
        self._ref: dict[int, int] = {}

    def pages_for(self, total_len: int) -> int:
        return math.ceil(total_len / self.page)

    def can_reserve(self, total_len: int) -> bool:
        need = self.pages_for(total_len)
        return need <= len(self._free) and \
            need <= self.max_pages_per_slot

    def reserve(self, slot: int, total_len: int, adopted=()):
        """Grant every page ``slot`` can ever touch: ``adopted`` pages
        (shared, refcount bumped — the prefix-cache hit) fill the
        leading table entries in position order, fresh pages cover the
        suffix. Refuses rather than partially grants."""
        need = self.pages_for(total_len)
        adopted = list(adopted)
        if need > self.max_pages_per_slot:
            raise DecodeError(
                f"sequence of {total_len} tokens needs {need} pages > "
                f"max_pages_per_slot={self.max_pages_per_slot}")
        if len(adopted) > need:
            raise DecodeError(
                f"adopting {len(adopted)} pages for a {need}-page "
                f"sequence")
        fresh_need = need - len(adopted)
        if fresh_need > len(self._free):
            raise DecodeError(
                f"KV pool exhausted: need {fresh_need} fresh pages, "
                f"{len(self._free)} free")
        if 0 in adopted:
            raise DecodeError("scratch page 0 is never sharable")
        fresh = [self._free.pop() for _ in range(fresh_need)]
        pages = adopted + fresh
        for p in pages:
            self._ref[p] = self._ref.get(p, 0) + 1
        self._owned[slot] = pages
        self.table[slot, :] = 0
        self.table[slot, :need] = pages
        return pages

    def release(self, slot: int):
        pages = self._owned.pop(slot, [])
        for p in reversed(pages):
            self.decref(p)
        self.table[slot, :] = 0

    def retain(self, page: int):
        """An extra reference (the prefix cache publishing a page)."""
        if page == 0:
            raise DecodeError("scratch page 0 is never sharable")
        self._ref[page] = self._ref.get(page, 0) + 1

    def decref(self, page: int) -> bool:
        """Drop one reference; the page returns to the free pool when
        nobody holds it anymore. Returns True when freed."""
        n = self._ref.get(page, 0) - 1
        if n > 0:
            self._ref[page] = n
            return False
        self._ref.pop(page, None)
        self._free.append(page)
        return True

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def owned(self, slot: int) -> list:
        """The slot's page list in position order (adopted prefix
        first) — what the prefix cache publishes from."""
        return list(self._owned.get(slot, ()))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)


def _boundary_error(e, site, what):
    """The engine-boundary failure an affected request sees: a typed
    DeviceOomError (plus a flight ``oom`` event naming the site and the
    top HBM claims) when the dispatch died on an allocation, else the
    usual RuntimeError wrapper."""
    from deeplearning4j_tpu.telemetry import memledger

    err = memledger.oom_error(e, site=site)
    if err is not None:
        return err
    return RuntimeError(f"{what}: {type(e).__name__}: {e}")


def _pool_bytes_estimate(model):
    """Bytes a decode model's state (KV pool / carries) will pin, via
    ``jax.eval_shape`` over ``init_state`` — a host-side trace, nothing
    allocated yet. None when the model cannot be shape-evaluated (the
    ISSUE 14 planner then refuses to guess)."""
    import jax

    from deeplearning4j_tpu.telemetry import memledger

    try:
        return memledger.tree_bytes(jax.eval_shape(model.init_state))
    except Exception:
        return None


# ---------------------------------------------------------------------------
# decode models
# ---------------------------------------------------------------------------

def _maybe_store(jitted, site, model, lane, donation=(), program=None):
    """Route a decode-model jit through the PR-13 persistent executable
    store (ISSUE 20 satellite: the remaining cold-start gap). Warm
    engine construction then deserializes every step/prefill/verify
    executable instead of compiling — ledger-asserted zero XLA
    compiles. The sharded lane stays scoped out (ISSUE 19: serialized
    SPMD executables bake in a device assignment), and the wrapper is
    the identity when the store is off. ``donation`` is the jit's own
    ``donate_argnums``: the store keys on it and owns a donated argument
    before a deserialized executable's first call. ``program`` stands in
    for the model's own digest where the executable does not depend on
    the model's math."""
    from deeplearning4j_tpu import compilestore

    if getattr(model, "mesh", None) is not None:
        return jitted
    if not compilestore.enabled():
        return jitted
    return compilestore.StoredJit(
        jitted, site,
        program=program or f"{model._store_program()}:{lane}",
        donation=donation)


class RnnDecodeModel:
    """Token-step decode over a MultiLayerNetwork with recurrent
    layers (the graves_lstm char-RNN workload as a token stream).

    Slot state = the network's streaming rnn carry, batched over
    ``max_slots`` rows; one engine iteration feeds every slot its next
    token id as a one-hot [S, nIn, 1] timestep through the net's own
    `_forward` — the same math `rnnTimeStep` runs, so a served stream
    matches an offline `rnnTimeStep` loop bit for bit. Params are read
    live from the net at every step (never captured)."""

    uses_pages = False
    page = None
    # nothing donated: the state lists the net's own arrays (running
    # statistics of non-recurrent layers) beside a few KB of carries
    state_donation = ()

    def __init__(self, net, max_slots=8, vocab=None):
        import jax

        net._check_init()
        self.net = net
        self.max_slots = int(max_slots)
        self._rec = set(net._recurrent_indices(forbid_bidirectional=True))
        if not self._rec:
            raise DecodeError("RnnDecodeModel needs at least one "
                              "recurrent layer")
        self.n_in = net.layers[0].nIn
        self.vocab = int(vocab) if vocab is not None else int(self.n_in)
        self._dtype = net.conf.dtype
        self._jit_step = _maybe_store(jax.jit(self._fn),
                                      "decode:step", self, "step")
        self._jit_masked = _maybe_store(jax.jit(self.masked_fn),
                                        "decode:step", self, "masked")
        # slot is a TRACED scalar: one reset executable serves every
        # slot (a static slot arg would compile per slot index and
        # break the zero-steady-state-recompiles contract)
        self._jit_reset = _maybe_store(jax.jit(self._reset_fn),
                                       "decode:reset", self, "reset")

    def _store_program(self):
        """Store program digest (the servable.py idiom): the math is a
        pure function of the net's conf plus the engine geometry, so
        identical digests guarantee identical lowered programs and a
        warm process never pays a fingerprint re-trace."""
        return (f"decode:RnnDecodeModel:{self.net.conf.to_json()}"
                f":slots={self.max_slots}:vocab={self.vocab}")

    # state: the full per-layer states list with recurrent carries
    # seeded to [max_slots] rows
    def init_state(self):
        return self.net._seed_rnn_states(self.net._states,
                                         self.max_slots)

    def _fn(self, params, state, tokens, pos, table):
        import jax
        import jax.numpy as jnp

        x = jax.nn.one_hot(tokens, self.n_in,
                           dtype=self._dtype)[:, :, None]
        y, new_state = self.net._forward(params, state, x, False, None)
        logits = y[:, :, 0].astype(jnp.float32)
        nxt = jnp.argmax(logits[:, :self.vocab], axis=-1) \
            .astype(jnp.int32)
        return nxt, new_state

    def masked_fn(self, params, state, tokens, pos, table, active):
        """The step math gated per slot: inactive rows keep their
        recurrent carries bitwise (``jnp.where`` on the carry rows).
        Active rows compute exactly ``_fn`` — the chunk-prefill loop
        body (serving/prefill.py) composes this, which is what makes
        chunked prefill bit-identical to the per-token path."""
        import jax.numpy as jnp

        nxt, new_state = self._fn(params, state, tokens, pos, table)
        out = list(new_state)
        for i in self._rec:
            out[i] = {
                k: jnp.where(
                    active.reshape((-1,) + (1,) * (v.ndim - 1)),
                    v, state[i][k])
                for k, v in new_state[i].items()}
        return jnp.where(active, nxt, -1), out

    def _reset_fn(self, state, slot):
        import jax.numpy as jnp

        out = list(state)
        for i in self._rec:
            out[i] = {k: v.at[slot].set(jnp.zeros_like(v[slot]))
                      for k, v in state[i].items()}
        return out

    def params_for_step(self):
        # read live from the net at every dispatch (never captured)
        return self.net._params

    def step(self, state, tokens, pos, table, site=None):
        args = (self.net._params, state, tokens, pos, table)
        out = self._jit_step(*args)
        if site is not None:
            compile_ledger.note_step(site, self._jit_step, args,
                                     donation=())
        return out

    def step_masked(self, state, tokens, pos, table, active, site=None):
        args = (self.net._params, state, tokens, pos, table,
                np.ascontiguousarray(active, dtype=bool))
        out = self._jit_masked(*args)
        if site is not None:
            compile_ledger.note_step(site, self._jit_masked, args,
                                     donation=())
        return out

    def reset_slot(self, state, slot):
        return self._jit_reset(state, np.int32(slot))


# entries of the live-page list that one iteration of the attention loop
# reduces: a constant of the code, settled on the chip (PERF.md, PR 29)
LIVE_CHUNK = 64


def _dot_01(x, ones):
    """``x @ ones`` in float32 for a 0/1 matrix given in bfloat16. ``x``
    goes through the matrix unit as three bfloat16 pieces that add up to
    it bit for bit (8 + 8 + 8 bits of its significand); a piece times 0
    or 1 is exact, and the pieces' products are summed in float32. It
    is what ``Precision.HIGHEST`` computes in six passes, because that
    splits both operands; ``ones`` needs no splitting (on the chip
    3.18 ms a step against 3.75: PERF.md, PR 29)."""
    import jax.numpy as jnp

    out, rest = 0.0, x
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        out = out + jnp.dot(piece, ones,
                            preferred_element_type=jnp.float32)
        rest = rest - piece.astype(jnp.float32)
    return out


def live_pages(pos, table, page):
    """The step's list of live (slot, page) pairs, from what the step is
    given: slot ``s`` at position ``pos[s]`` has ``n[s] = pos[s] // page
    + 1`` live pages, an exclusive prefix sum of ``n`` gives its offset,
    and flat entry ``j < n_live = sum(n)`` belongs to the slot whose
    range holds ``j``, as its page index ``i = j - offset[slot]``. The
    list is as long as the page table, ``S * P``, rounded up to whole
    chunks of ``LIVE_CHUNK`` entries (one chunk where the table is
    shorter than that).

    For the loop: ``slot``, ``page`` (the pool page ``table[slot, i]``;
    the scratch page past ``n_live``), ``last`` (``pos[slot]`` counted
    from that page's first row: the rows up to it are seen; -1 past
    ``n_live``, where none is) and ``n_live``. For the combination:
    ``own [S, P]``, entry ``offset[s] + i`` of slot ``s``, and ``dead
    [S, P]``, True where ``i >= n[s]``."""
    import jax.numpy as jnp

    S, P = table.shape
    chunk = min(LIVE_CHUNK, S * P)
    n = pos // page + 1
    ends = jnp.cumsum(n)
    offset = ends - n
    j = jnp.arange(-(-S * P // chunk) * chunk)
    alive = j < ends[-1]
    slot = jnp.minimum(jnp.sum(j[:, None] >= ends[None, :], axis=1),
                       S - 1)
    i = jnp.where(alive, j - offset[slot], 0)
    i_own = jnp.arange(P)
    dead = i_own[None, :] >= n[:, None]
    return {
        "slot": slot,
        "page": jnp.where(alive, table[slot, i], 0),
        "last": jnp.where(alive, pos[slot] - i * page, -1),
        "n_live": ends[-1],
        "own": jnp.where(dead, 0, offset[:, None] + i_own[None, :]),
        "dead": dead}


def live_page_attention(live, partial, spread, n_heads, o_shape):
    """Each slot's attention over its own context, from the step's list of
    live pages (``live_pages``): a loop over chunks of ``LIVE_CHUNK``
    entries, ``ceil(n_live / LIVE_CHUNK)`` of them, hands ``partial`` a
    chunk's ``slot``, pool ``page`` and ``last`` seen row (each ``[C]``)
    and gets back every entry's page reduced ON ITS OWN: its scores'
    maximum ``m [C, H]`` (-inf where no row is seen), their sum ``l [C,
    H]`` and the weighted values ``o [C, *o_shape]``, which are written at
    the entry's place. A slot then combines its entries in its page table's
    order, dead ones as exact zeros (split-K over pages); ``spread`` lays
    a per-head ``[..., H]`` out against ``o_shape``. An entry's partial is
    row-wise math on its own page and the combination runs over a fixed
    range, so a slot's output depends neither on what its neighbours hold
    nor on where a chunk's edge falls. What a model brings is the
    partial: how a row of its pool is scored and what of it is a value.
    -> ``[S, *o_shape]`` float32."""
    import jax.numpy as jnp
    from jax import lax

    N = live["slot"].shape[0]       # whole chunks: ``live_pages``
    C = min(LIVE_CHUNK, N)

    def body(c, bufs):
        at = c * C
        slot, pg, last = (lax.dynamic_slice_in_dim(live[k], at, C)
                          for k in ("slot", "page", "last"))
        return tuple(
            lax.dynamic_update_slice_in_dim(buf, x, at, axis=0)
            for buf, x in zip(bufs, partial(slot, pg, last)))

    bufs = (jnp.full((N, n_heads), -jnp.inf, jnp.float32),
            jnp.zeros((N, n_heads), jnp.float32),
            jnp.zeros((N, *o_shape), jnp.float32))
    ms, ls, os_ = lax.fori_loop(0, (live["n_live"] + C - 1) // C,
                                body, bufs)
    # slot s's entries are offset[s] + i, i = 0..P-1; those past its
    # last live page weigh exp(-inf) = 0
    own, dead = live["own"], live["dead"]
    m_i = jnp.where(dead[:, :, None], -jnp.inf, ms[own])   # [S, P, H]
    w = jnp.exp(m_i - jnp.max(m_i, axis=1, keepdims=True))
    l = jnp.sum(ls[own] * w, axis=1)                       # [S, H]
    o = jnp.sum(os_[own] * spread(w), axis=1)              # [S, *o_shape]
    return o / spread(l)


class TransformerDecodeModel:
    """Causal single-token decode over a paged KV pool.

    Mirrors `models/bert.py`'s post-LN encoder block (qkv/out/ln1/ffn/
    ln2 naming, gelu FFN, tied LM head), so `from_bert()` serves a
    trained encoder's weights as a token stream. Attention reads the
    batch's live pages and no others (`live_pages`, `_paged_attention`):
    a step over short contexts costs what they hold, a full pool what
    it always did, through one executable (the loop's trip count is
    data, not shape)."""

    uses_pages = True
    # the KV pool is donated to every executable over it (``state`` is
    # argument 1 of ``_fn``, ``masked_fn`` and the block executable) and
    # written in place: a step CONSUMES the state it is given, the
    # caller goes on with the one returned
    state_donation = (1,)

    def __init__(self, params, n_heads, max_slots=8, page=16,
                 max_pages_per_slot=8, n_pages=None, eps=1e-12):
        import jax

        self.params = params
        self.n_heads = int(n_heads)
        hidden = int(np.asarray(params["tok_emb"]).shape[1])
        if hidden % self.n_heads:
            raise DecodeError(f"hidden {hidden} not divisible by "
                              f"{n_heads} heads")
        self.hidden = hidden
        self.head_dim = hidden // self.n_heads
        self.vocab = int(np.asarray(params["tok_emb"]).shape[0])
        self.max_len = int(np.asarray(params["pos_emb"]).shape[0])
        self.max_slots = int(max_slots)
        self.page = int(page)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.n_pages = (int(n_pages) if n_pages is not None
                        else max_slots * max_pages_per_slot)
        self.eps = eps
        self.n_layers = len(params["layers"])
        self._jit_step = _maybe_store(
            jax.jit(self._fn, donate_argnums=self.state_donation),
            "decode:step", self, "step", donation=self.state_donation)
        self._jit_masked = _maybe_store(
            jax.jit(self.masked_fn, donate_argnums=self.state_donation),
            "decode:step", self, "masked", donation=self.state_donation)

    def _store_program(self):
        """Store program digest: the transformer step is determined by
        the structural geometry below (param SHAPES ride in the
        per-signature key, and the values never shape the program)."""
        return (f"decode:TransformerDecodeModel:L={self.n_layers}"
                f":heads={self.n_heads}:hidden={self.hidden}"
                f":vocab={self.vocab}:max_len={self.max_len}"
                f":slots={self.max_slots}:page={self.page}"
                f":pages={self.n_pages}"
                f":pps={self.max_pages_per_slot}:eps={self.eps}")

    @classmethod
    def from_bert(cls, params, cfg, **kw):
        """Lift a `models/bert.py` param tree into a decode servable
        (cfg: BertConfig — supplies head count)."""
        kw.setdefault("page", 16)
        return cls(params, n_heads=cfg.num_heads,
                   eps=cfg.layer_norm_eps, **kw)

    @classmethod
    def init(cls, vocab=64, hidden=32, n_layers=2, n_heads=2,
             max_len=128, seed=0, **kw):
        """Standalone random init (bert-style param naming)."""
        from deeplearning4j_tpu.models.bert import (BertConfig,
                                                    init_params)
        import jax

        cfg = BertConfig(vocab_size=vocab, hidden=hidden,
                         num_layers=n_layers, num_heads=n_heads,
                         ffn=4 * hidden, max_len=max_len)
        params = init_params(cfg, jax.random.key(seed))
        return cls(params, n_heads=n_heads, **kw)

    def _pool_shape(self):
        """[L, n_pages + 1, page, H*D]; page 0 is scratch. A position's
        row is the H*D floats the step writes, heads side by side: with
        (H, D) as the two minor dimensions the device would pad them to
        its (8, 128) tile, so it stores such a pool page-minor instead
        and every executable converts what it touches there and back
        (PERF.md, PR 27). (page, H*D) fills the tile as written."""
        return (self.n_layers, self.n_pages + 1, self.page, self.hidden)

    def init_state(self):
        import jax.numpy as jnp

        return {"k": jnp.zeros(self._pool_shape(), jnp.float32),
                "v": jnp.zeros(self._pool_shape(), jnp.float32)}

    def _paged_attention(self, q, kpool, vpool, li, live):
        """q [S, H*D] against each slot's own context in layer ``li``,
        over the step's list of live pages (``live_page_attention``,
        which holds the loop and the combination). The partial of a
        chunk gathers each entry's K and V page out of the whole pools
        (no layer of a pool is ever a value of its own).

        A row is scored as it lies, H*D wide: the product with q summed
        over each head's D lanes by a constant 0/1 ``[H*D, H]`` matrix,
        the probabilities spread back over the same lanes by its
        transpose, both in float32 (``_dot_01``; reshaping a gathered
        block into heads costs the device a relayout: PERF.md, PR 27's
        trace)."""
        import jax.numpy as jnp

        hd = q.shape[1]
        H = self.n_heads
        heads = jnp.asarray(np.repeat(np.eye(H), hd // H, axis=0),
                            jnp.bfloat16)                    # [H*D, H]
        lanes = heads.T
        scale = 1.0 / math.sqrt(self.head_dim)
        rows = jnp.arange(self.page)

        def partial(slot, pg, last):
            kb, vb = kpool[li, pg], vpool[li, pg]       # [C, page, H*D]
            s = _dot_01(kb * q[slot][:, None, :], heads) * scale
            seen = rows[None, :] <= last[:, None]       # causal + length
            s = jnp.where(seen[:, :, None], s, -jnp.inf)
            m = jnp.max(s, axis=1)                      # [C, H]
            p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[:, None])
            o = jnp.sum(_dot_01(p, lanes) * vb, axis=1)  # [C, H*D]
            return m, jnp.sum(p, axis=1), o

        return live_page_attention(
            live, partial, lambda a: _dot_01(a, lanes), H, (hd,))

    def _fn(self, params, state, tokens, pos, table):
        import jax.numpy as jnp

        S = self.max_slots
        pidx = table[jnp.arange(S), pos // self.page]   # [S] write page
        return self._apply(params, state, tokens, pos, table, pidx)

    def masked_fn(self, params, state, tokens, pos, table, active):
        """The step math with inactive slots routed to scratch: their
        pool writes land on page 0 and their outputs are -1, while an
        active row computes bit-exactly what ``_fn`` computes (same
        [S]-shaped row-wise math) — the property the chunk-prefill /
        verify block executable (serving/prefill.py) is built on."""
        import jax.numpy as jnp

        S = self.max_slots
        pos = jnp.where(active, pos, 0)
        pidx = jnp.where(active,
                         table[jnp.arange(S), pos // self.page], 0)
        nxt, new_state = self._apply(params, state, tokens, pos, table,
                                     pidx)
        return jnp.where(active, nxt, -1), new_state

    def _apply(self, params, state, tokens, pos, table, pidx):
        import jax
        import jax.numpy as jnp

        ln = lambda x, p: _layer_norm(x, p["g"], p["b"], self.eps)  # noqa: E731
        h = params["tok_emb"][tokens] + params["pos_emb"][pos]
        h = ln(h, params["emb_ln"])
        off = pos % self.page
        live = live_pages(pos, table, self.page)    # once a step
        kpool, vpool = state["k"], state["v"]
        for li, lp in enumerate(params["layers"]):
            qkv = h @ lp["qkv_w"] + lp["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            # S rows a layer into the donated pools, in place, before
            # the layer's attention reads them
            kpool = kpool.at[li, pidx, off].set(k)
            vpool = vpool.at[li, pidx, off].set(v)
            att = self._paged_attention(q, kpool, vpool, li, live)
            att = att @ lp["out_w"] + lp["out_b"]
            h = ln(h + att, lp["ln1"])
            ffn = jax.nn.gelu(h @ lp["ffn_in_w"] + lp["ffn_in_b"])
            ffn = ffn @ lp["ffn_out_w"] + lp["ffn_out_b"]
            h = ln(h + ffn, lp["ln2"])
        logits = h @ params["tok_emb"].T + params["mlm_bias"]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, {"k": kpool, "v": vpool}

    def params_for_step(self):
        return self.params

    def step(self, state, tokens, pos, table, site=None):
        args = (self.params, state, tokens, pos, table)
        out = self._jit_step(*args)
        if site is not None:
            compile_ledger.note_step(site, self._jit_step, args,
                                     donation=self.state_donation)
        return out

    def step_masked(self, state, tokens, pos, table, active, site=None):
        args = (self.params, state, tokens, pos, table,
                np.ascontiguousarray(active, dtype=bool))
        out = self._jit_masked(*args)
        if site is not None:
            compile_ledger.note_step(site, self._jit_masked, args,
                                     donation=self.state_donation)
        return out

    def reset_slot(self, state, slot):
        # stale page contents are unreachable once the page table drops
        # them (the length mask covers in-page staleness): no wipe
        return state


def _layer_norm(x, g, b, eps):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _pick_token(feed, nxt):
    """The tokens of a step dispatched while the one before it is still
    in flight: the host's ``feed`` where it holds a token, and where it
    holds the marker -1 (a slot that answers) the token that the launch
    in flight chose for the slot, which never leaves the device."""
    import jax.numpy as jnp

    return jnp.where(feed < 0, nxt, feed)


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "future", "stream",
                 "slot", "ptr", "generated", "t_submit", "req_id",
                 "trace", "spans_emitted", "t_suppressed",
                 "ttft_boundaries", "published", "t_first")
    _END = object()

    def __init__(self, prompt, max_new, eos_id, req_id):
        self.prompt = [int(t) for t in prompt]
        if not self.prompt:
            raise ValueError("decode needs at least one prompt token")
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.future: Future = Future()
        self.stream: _queue.Queue = _queue.Queue()
        self.slot = None
        self.ptr = 0            # next position to feed (dispatched)
        self.generated: list[int] = []
        self.t_submit = time.perf_counter()
        self.req_id = req_id
        # sampled-trace context captured at submit (None = unsampled):
        # the engine thread emits per-token-boundary child spans to it
        self.trace = tracing.current()
        self.spans_emitted = 0     # per-boundary spans so far
        self.t_suppressed = None   # first boundary past the span cap
        # TTFT accounting (ISSUE 12): engine boundaries this request
        # rode before its first token — the number chunked prefill
        # and prefix adoption exist to shrink
        self.ttft_boundaries = 0
        self.published = False     # prompt pages in the prefix cache
        self.t_first = None        # wall time of the first token

    def tokens(self, timeout=None):
        """Generator of tokens as they decode (terminates with the
        sequence; raises if the engine failed the request)."""
        while True:
            item = self.stream.get(timeout=timeout)
            if item is self._END:
                exc = self.future.exception()
                if exc is not None:
                    raise exc
                return
            yield item

    def result(self, timeout=None) -> list:
        return self.future.result(timeout=timeout)


class DecodeEngine:
    """Continuous batcher: one worker thread advancing every in-flight
    sequence one token per iteration.

    - `submit(prompt, max_new_tokens)` joins at the next token
      boundary if a slot (and, for paged models, enough KV pages) is
      free, else waits in the pending queue;
    - prompt PREFILL runs through the same step executable, one token
      per iteration — a joining sequence interleaves with in-flight
      decodes from its first token (no separate prefill executable,
      no second compiled shape);
    - a finished sequence (max_new reached or eos) frees its slot and
      pages at the boundary that DELIVERS its last token, and the next
      pending request takes them over at the next admission;
    - ONE TOKEN STEP IN FLIGHT: the engine dispatches the token step of
      boundary t+1 before it reads boundary t's tokens, and reads,
      emits and retires boundary t while the device runs t+1. A
      request's position (``ptr``) advances when a launch is
      dispatched; its tokens are appended, streamed and counted when
      that launch is read, from the record the launch left. A slot that
      answers is fed the marker -1 and takes its token from the launch
      in flight, on the device (``_pick_token``). Who is fed at t+1 is
      known by count (prompt length, ``max_new``); a request with an
      ``eos_id`` rides t+1 on the device's token, and that row is
      discarded when t's token turns out to be ``eos`` (its K/V row
      lies inside the request's own pages, above every length mask).
      Greedy output is token for token the serial engine's. What
      drains the launch in flight before the next dispatch: an engine
      with a block executable or a draft (``chunk``, ``speculative``:
      their boundaries need the tokens on the host, so every boundary
      of theirs is dispatched, read and emitted in that order), and a
      boundary after which no active request has a position left to
      feed;
    - `warmup()` runs throwaway steps + slot reset so every executable
      exists before traffic; after it, `dl4j_compile_total` stays flat
      (asserted in tests).

    ISSUE 12 layers (all default-off, composable):

    - ``chunk=N``: chunked prefill — prompts retire in N-token blocks
      through a second ``[max_slots, N]`` executable at each boundary
      (serving/prefill.py), cutting TTFT boundaries from
      O(prompt_len) to O(prompt_len / N) while decoding slots keep
      streaming; bit-identical to the per-token path by construction;
    - ``prefix_cache=True``: completed full prompt pages are
      refcounted and published under a rolling token-prefix hash
      (serving/prefix_cache.py); a request with a matching prefix
      adopts the pages and prefills only its suffix. Admission counts
      cache-idle pages as reclaimable — the PR-8 head-of-line wedge
      fix;
    - ``speculative=SpeculativeConfig(draft, k)``: a draft model
      proposes k tokens per boundary, verified in one call through
      the block executable, with acceptance-EWMA fallback to plain
      decode (serving/speculative.py). Greedy output is identical to
      target-only decode.
    """

    def __init__(self, model, name="decode", pending_size=64,
                 max_new_limit=1024, instruments=None,
                 wedge_timeout=30.0, chunk=None, prefix_cache=False,
                 speculative=None, backlog_timeout=120.0):
        self.model = model
        self.name = name
        # /healthz wedge detection (ISSUE 10 satellite): with sequences
        # in flight, a token boundary is expected at least this often —
        # an engine stuck inside one step longer than this reports the
        # decoder section "degraded" (still 200)
        self.wedge_timeout = float(wedge_timeout)
        self._last_boundary = None
        # hard per-request generation cap, enforced for EVERY model:
        # paged models are also bounded by max_len/pool, but a
        # page-less RNN model has no natural ceiling — without this an
        # HTTP client asking for 10**6 tokens wedges a slot for hours
        self.max_new_limit = int(max_new_limit)
        self._instruments_fn = (instruments if callable(instruments)
                                else lambda: instruments)
        # a model that holds state by slot beside its pages (a recurrent
        # state no page table names: serving/hybrid.py) starts a request's
        # state at position 0 and cannot take a position back, so what
        # treats a position as a row of a page is refused, not served wrongly
        self._slot_state_bytes = 0
        if getattr(model, "slot_state", False):
            if prefix_cache or speculative is not None:
                raise DecodeError(
                    "a model that holds state by slot serves neither "
                    "prefix_cache (a hit starts a request past position 0, "
                    "on a state that no page holds) nor speculative (a "
                    "rejected draft's positions cannot be taken out of the "
                    "state)")
            self._slot_state_bytes = int(model.slot_state_bytes())
        self._pending: _queue.Queue = _queue.Queue(maxsize=pending_size)
        self._waiting: list = []   # engine-side FIFO (page head-block)
        self._active: dict[int, _DecodeRequest] = {}
        self._free_slots = list(range(model.max_slots - 1, -1, -1))
        # admission-time capacity planning (ISSUE 14): validate the KV
        # pool bytes against live device headroom BEFORE allocating it
        # — a structured CapacityError beats an opaque mid-init OOM.
        # eval_shape is a host-side trace: nothing is allocated yet;
        # both it and the plan are skipped when no device capacity is
        # knowable (the engine allocates on the default device, so
        # that is the device the judgement scopes to)
        from deeplearning4j_tpu.telemetry import memledger

        self._plan_device = memledger.device_label()
        # a mesh-sharded model (serving/sharded.py) is planned as a
        # PLACEMENT: each mesh device's pool share against that
        # device's own headroom — the whole point of sharding the pool
        # is that the total never has to fit one device
        self._sharded_mesh = getattr(model, "mesh", None)
        if getattr(model, "uses_pages", False) and \
                self._sharded_mesh is not None and \
                memledger.capacity_known():
            pool_est = _pool_bytes_estimate(model)
            if pool_est is not None:
                memledger.plan_capacity(
                    f"decode:{name}:kv", pool_est,
                    detail={"lane": "target", "pages": model.n_pages,
                            "page": model.page,
                            "slots": model.max_slots,
                            "pool_shards": getattr(
                                model, "pool_shards", None)},
                    per_device=model.pool_device_bytes())
        elif getattr(model, "uses_pages", False) and \
                memledger.capacity_known(device=self._plan_device):
            pool_est = _pool_bytes_estimate(model)
            if pool_est is not None:
                memledger.plan_capacity(
                    f"decode:{name}:kv", pool_est,
                    detail={"lane": "target", "pages": model.n_pages,
                            "page": model.page,
                            "slots": model.max_slots},
                    device=self._plan_device)
        try:
            self._state = model.init_state()
        except Exception as e:
            memledger.raise_if_oom(e, site=f"decode:{name}:kv",
                                   lane="target")
            raise
        self._kv = None
        self._pool_bytes = memledger.tree_bytes(self._state)
        self._mem_claim = None   # registered at the END of __init__
        if getattr(model, "uses_pages", False):
            self._kv = PagedKVCache(model.n_pages, model.page,
                                    model.max_pages_per_slot,
                                    model.max_slots)
        self._table = (self._kv.table if self._kv is not None
                       else np.zeros((model.max_slots, 1), np.int32))
        # -- decode v2 layers (ISSUE 12), all default-off ------------------
        self._spec = None
        self._draft_mem_claim = None
        if speculative is not None:
            from deeplearning4j_tpu.serving.speculative import (
                SpeculativeConfig, SpeculativeDecoder)

            cfg = (speculative if isinstance(speculative,
                                             SpeculativeConfig)
                   else SpeculativeConfig(draft=speculative))
            if self._kv is None:
                raise DecodeError("speculative decoding needs a paged "
                                  "target model (the verifier rides "
                                  "the block executable over the "
                                  "paged pool)")
            if getattr(cfg.draft, "vocab", None) != model.vocab:
                raise DecodeError(
                    f"draft vocab {getattr(cfg.draft, 'vocab', None)} "
                    f"!= target vocab {model.vocab}")
            if cfg.draft.max_slots != model.max_slots:
                raise DecodeError(
                    f"draft max_slots {cfg.draft.max_slots} != target "
                    f"max_slots {model.max_slots}")
            # the draft lane mirrors the target's page accounting:
            # equal page size keeps adoption depths in one unit, and a
            # pool at least as roomy keeps every submit-side limit
            # check (which consults only the target) valid for the
            # draft too — a smaller draft pool would re-introduce the
            # head-of-line wedge on the mirror lane
            if cfg.draft.page != model.page:
                raise DecodeError(
                    f"draft page {cfg.draft.page} != target page "
                    f"{model.page}")
            if cfg.draft.max_pages_per_slot < model.max_pages_per_slot \
                    or cfg.draft.n_pages < model.n_pages:
                raise DecodeError(
                    f"draft pool (max_pages_per_slot="
                    f"{cfg.draft.max_pages_per_slot}, n_pages="
                    f"{cfg.draft.n_pages}) smaller than the target's "
                    f"({model.max_pages_per_slot}, {model.n_pages})")
            if chunk is None:
                # verify width doubles as the prefill block: ONE block
                # executable total (the lean-kernel default)
                chunk = cfg.k + 1
            # the draft lane's mirror pool is validated and claimed
            # exactly like the target's (ISSUE 14)
            if memledger.capacity_known(device=self._plan_device):
                draft_est = _pool_bytes_estimate(cfg.draft)
                if draft_est is not None:
                    memledger.plan_capacity(
                        f"decode:{name}:kv", draft_est,
                        detail={"lane": "draft",
                                "pages": cfg.draft.n_pages,
                                "page": cfg.draft.page,
                                "slots": cfg.draft.max_slots},
                        device=self._plan_device)
            try:
                self._spec = SpeculativeDecoder(
                    cfg, chunk, name, prefix_cache=bool(prefix_cache))
            except Exception as e:
                memledger.raise_if_oom(e, site=f"decode:{name}:kv",
                                       lane="draft")
                raise
        self._block = None
        if chunk is not None:
            from deeplearning4j_tpu.serving.prefill import ChunkedPrefill

            self._block = ChunkedPrefill(model, chunk)
        self._pcache = None
        if prefix_cache:
            from deeplearning4j_tpu.serving.prefix_cache import (
                PrefixCache)

            if self._kv is None:
                raise DecodeError("prefix caching needs a paged model "
                                  "(KV pages are what gets shared)")
            self._pcache = (prefix_cache if isinstance(prefix_cache,
                                                       PrefixCache)
                            else PrefixCache(self._kv.page))
        # one token step in flight (class docstring): the launch whose
        # tokens are still on the device, as (nxt, fed, t_b0, counts) with
        # fed = [(slot, request, position fed)] and counts what the step
        # returned beside its tokens, or None. The choice between the host's
        # feed and that launch's tokens exists only where the engine
        # overlaps: with a block executable or a draft it is serial. It
        # goes through the executable store under a key of its own (it
        # does not depend on the model's math), because a warm engine
        # compiles nothing: tests/test_compilestore.py's
        # test_warm_decode_engine_zero_compiles counts this one too
        self._flight = None
        # what the engine's thread spent under its phase spans since the
        # last token-step dispatch (telemetry: the dispatch intervals)
        self._account = DispatchAccount()
        self._pick = None
        if self._block is None and self._spec is None:
            import jax

            self._pick = _maybe_store(
                jax.jit(_pick_token), "decode:pick", model, "pick",
                program=f"decode:pick:slots={model.max_slots}")
        self.backlog_timeout = float(backlog_timeout)
        # duck-typed models (tests, foreign adapters) may predate the
        # ledger-site kwarg on step() — detect once, not per boundary
        import inspect

        try:
            self._step_takes_site = "site" in inspect.signature(
                model.step).parameters
        except (TypeError, ValueError):
            self._step_takes_site = False
        self._closed = False
        self._warmed = False
        self._ids = 0
        # HBM ledger claims registered LAST (ISSUE 14): any validation
        # raise above must not leak a claim for an engine that never
        # existed — the pools are only pinned once this line is reached.
        # A mesh-sharded pool (ISSUE 19) splits its claim per device —
        # one `name:target@<device>` row per mesh device so
        # /debug/memory attributes each device's actual share, instead
        # of one total that no single device holds
        self._shard_mem_claims = []
        if self._sharded_mesh is not None and \
                callable(getattr(model, "pool_device_bytes", None)):
            for label, share in sorted(
                    model.pool_device_bytes().items()):
                self._shard_mem_claims.append(memledger.claim(
                    "kv_cache", f"{name}:target@{label}",
                    nbytes=share, device=label, sharded=True,
                    slots=model.max_slots,
                    pages=getattr(model, "n_pages", None)))
        else:
            self._mem_claim = memledger.claim(
                "kv_cache", f"{name}:target", nbytes=self._pool_bytes,
                slots=model.max_slots,
                pages=getattr(model, "n_pages", None))
        if self._spec is not None:
            self._draft_mem_claim = memledger.claim(
                "kv_cache", f"{name}:draft",
                nbytes=self._spec.pool_bytes,
                slots=self._spec.model.max_slots,
                pages=self._spec.model.n_pages)
        # serializes submit(): the capacity check and the req-id
        # counter both race under concurrent HTTP handler threads
        self._submit_lock = threading.Lock()
        self._wake = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name=f"dl4j:decode:engine-{name}", daemon=True)
        self._thread.start()

    # -- client side ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens, eos_id=None,
               timeout=None) -> _DecodeRequest:
        if self._closed:
            raise DecodeShutdown(f"decode engine {self.name!r} closed")
        if int(max_new_tokens) > self.max_new_limit:
            raise DecodeError(
                f"max_new_tokens={max_new_tokens} exceeds the "
                f"engine's limit of {self.max_new_limit} "
                f"(max_new_limit=)")
        total = len(list(prompt)) + int(max_new_tokens)
        max_len = getattr(self.model, "max_len", None)
        if max_len is not None and total > max_len:
            raise DecodeError(
                f"prompt + max_new_tokens = {total} exceeds the "
                f"model's max_len {max_len}")
        if self._kv is not None:
            need = self._kv.pages_for(total)
            # validate against BOTH per-slot max and the pool total:
            # a request that could never reserve would head-block the
            # strict-FIFO waiting line forever
            limit = min(self.model.max_pages_per_slot,
                        self._kv.n_pages)
            if need > limit:
                raise DecodeError(
                    f"sequence of {total} tokens needs {need} KV "
                    f"pages > the engine's limit of {limit} "
                    f"(max_pages_per_slot="
                    f"{self.model.max_pages_per_slot}, pool="
                    f"{self._kv.n_pages})")
        with self._submit_lock:
            # backpressure bound spans the submit queue AND the
            # engine's head-blocking FIFO (requests parked waiting for
            # KV pages) — without counting _waiting, the engine
            # draining the queue each token boundary would make
            # pending_size meaningless
            if self._pending.qsize() + len(self._waiting) >= \
                    self._pending.maxsize:
                from deeplearning4j_tpu.serving.batcher import (
                    QueueFullError)

                raise QueueFullError(
                    f"decode pending queue for {self.name!r} full "
                    f"({self._pending.maxsize} waiting)")
            self._ids += 1
            req = _DecodeRequest(prompt, max_new_tokens, eos_id,
                                 self._ids)
            self._pending.put_nowait(req)
        self._wake.set()
        return req

    def decode(self, prompt, max_new_tokens, eos_id=None,
               timeout=None) -> list:
        """Synchronous decode: the generated token ids."""
        return self.submit(prompt, max_new_tokens,
                           eos_id=eos_id).result(timeout=timeout)

    def warmup(self):
        """Compile the full executable set with throwaway iterations
        that write the scratch page only (slot 0's carry is re-reset
        afterwards; block warmups run with all counts zero). A launch
        consumes the state it is given, so each one's result is carried
        on. Every executable lands in the compile ledger under a
        ``decode:<name>:*`` site, so the zero-steady-state-recompile
        invariant is ledger-assertable for the whole set: token step +
        chunk prefill + verify + draft step + draft prefill, or token
        step + the choice of its tokens on the device (tests)."""
        if compile_ledger.enabled():
            # the jax.monitoring hook installs on first registry use;
            # without it the warmup compiles below would never be
            # attributed to their decode:* ledger sites
            from deeplearning4j_tpu.telemetry import (registry
                                                      as _registry)

            _registry.get_registry()
        self._state = self.model.reset_slot(self._state, 0)
        tokens = np.zeros((self.model.max_slots,), np.int32)
        pos = np.zeros((self.model.max_slots,), np.int32)
        # a REAL copy, not ascontiguousarray (which aliases an
        # already-contiguous table): admission mutates the table
        # between boundaries, and jax may zero-copy numpy inputs
        table = self._table.copy()
        nxt, self._state, _ = self._model_step(self._state, tokens, pos,
                                               table)
        if self._pick is not None:
            # the step as a boundary with one in flight calls it: its
            # tokens a device array, chosen where the last ones live
            feed = np.full((self.model.max_slots,), -1, np.int32)
            tokens = self._pick(feed, nxt)
            compile_ledger.note_step(
                f"decode:{self.name}:pick", self._pick, (feed, nxt),
                donation=())
            _, self._state, _ = self._model_step(self._state, tokens,
                                                 pos, table)
        if self._block is not None:
            self._state = self._block.warmup(
                self._state, table, site=f"decode:{self.name}:prefill")
            if self._spec is not None and \
                    self._spec.k + 1 != self._block.chunk:
                self._state = self._block.warmup(
                    self._state, table, widths=(self._spec.k + 1,),
                    site=f"decode:{self.name}:verify")
        if self._spec is not None:
            self._spec.warmup()
        self._state = self.model.reset_slot(self._state, 0)
        self._warmed = True
        return self

    @property
    def active_slots(self) -> int:
        return len(self._active)

    def _backlog_age(self):
        """Age of the oldest request still waiting for its first token
        (queued, head-blocked, or mid-prefill) — the chunked-prefill
        backlog signal for /healthz."""
        oldest = None
        for req in list(self._waiting):
            if oldest is None or req.t_submit < oldest:
                oldest = req.t_submit
        for req in list(self._active.values()):
            if not req.generated and (oldest is None
                                      or req.t_submit < oldest):
                oldest = req.t_submit
        return (time.perf_counter() - oldest) if oldest is not None \
            else None

    def health(self) -> dict:
        """Liveness detail for /healthz: active/waiting counts plus
        wedge detection — sequences in flight but no token boundary
        for longer than ``wedge_timeout`` means a slot is stuck inside
        a device step (or the engine thread died mid-decode). ISSUE 12
        adds prefix-cache occupancy/hit-rate, the prefill backlog age
        (degraded past ``backlog_timeout`` — boundaries may be
        advancing while a starved request never reaches its first
        token), KV-page occupancy, and speculation state — all
        degraded-not-503, the PR-9 contract."""
        active = len(self._active)
        last = self._last_boundary
        age = (time.monotonic() - last) if last is not None else None
        wedged = bool(active and age is not None
                      and age > self.wedge_timeout)
        backlog = self._backlog_age()
        starved = bool(backlog is not None
                       and backlog > self.backlog_timeout)
        out = {"active": active,
               "waiting": self._pending.qsize() + len(self._waiting),
               "boundary_age_seconds": (round(age, 3)
                                        if age is not None else None),
               "wedged": wedged,
               "degraded": (wedged or starved
                            or not self._thread.is_alive())}
        if self._block is not None:
            out["prefill"] = {
                "chunk": self._block.chunk,
                "backlog": sum(
                    1 for r in list(self._active.values())
                    if not r.generated) + len(self._waiting),
                "oldest_age_seconds": (round(backlog, 3)
                                       if backlog is not None
                                       else None),
                "starved": starved}
        if self._kv is not None:
            # the pool in BYTES beside page occupancy (ISSUE 14
            # satellite): the device pool holds n_pages + 1 pages
            # (page 0 = scratch), so per-page bytes divide by that
            per_page = (self._pool_bytes - self._slot_state_bytes) \
                // (self._kv.n_pages + 1)
            out["kv_pages"] = {"total": self._kv.n_pages,
                               "free": self._kv.free_pages,
                               "occupancy": round(
                                   self._kv.used_pages
                                   / self._kv.n_pages, 4),
                               "pool_bytes": self._pool_bytes,
                               "used_bytes": per_page
                               * self._kv.used_pages}
            if self._sharded_mesh is not None and \
                    callable(getattr(self.model,
                                     "pool_device_bytes", None)):
                out["kv_pages"]["per_device_bytes"] = \
                    self.model.pool_device_bytes()
        if self._sharded_mesh is not None and \
                callable(getattr(self.model, "sharded_health", None)):
            out["sharded"] = self.model.sharded_health()
        if self._slot_state_bytes:
            out["slot_state_bytes"] = self._slot_state_bytes
        if self._pcache is not None:
            out["prefix_cache"] = self._pcache.stats()
        if self._spec is not None:
            out["speculative"] = self._spec.health()
        return out

    def close(self, timeout=5.0):
        self._closed = True
        self._wake.set()
        self._thread.join(timeout)
        # the pools die with the engine: release their HBM claims
        if self._mem_claim is not None:
            self._mem_claim.release()
        for c in self._shard_mem_claims:
            c.release()
        self._shard_mem_claims = []
        if self._draft_mem_claim is not None:
            self._draft_mem_claim.release()
        # fail everything still pending or active
        leftovers = list(self._active.values()) + list(self._waiting)
        self._active.clear()
        self._waiting = []
        while True:
            try:
                leftovers.append(self._pending.get_nowait())
            except _queue.Empty:
                break
        for req in leftovers:
            if not req.future.done():
                req.future.set_exception(
                    DecodeShutdown("decode engine closed"))
            req.stream.put(_DecodeRequest._END)

    # -- engine side ---------------------------------------------------------
    def _page_plan(self, req):
        """Admission plan for the head-of-line request, or None when
        it must wait. Consults the prefix cache twice over (ISSUE 12
        satellite: the PR-8 head-of-line wedge): matched pages are
        ADOPTED instead of reserved, and pages held only by the cache
        (refcount==1, idle) count as reclaimable — a request that fits
        the pool no longer blocks the FIFO just because idle cached
        pages are sitting on the free list's budget."""
        from deeplearning4j_tpu.serving.prefix_cache import (
            plan_admission)

        total = len(req.prompt) + req.max_new
        plan = plan_admission(self._kv, self._pcache, req.prompt, total)
        if plan is None:
            return None
        if self._spec is not None:
            # the draft lane must never adopt DEEPER than the target
            # skips (the suffix prefill would write into shared draft
            # pages); shallower is fine — quality cost only
            dplan = self._spec.plan(req.prompt, total,
                                    max_adopt=len(plan["adopt"]))
            if dplan is None:
                return None
            return plan, dplan
        return plan, None

    def _admit(self, inst):
        """Move pending requests into free slots at this token
        boundary. The submit queue drains into an engine-private FIFO
        first, so a request that can't get its KV pages yet
        head-blocks (fairness) without races against submit()."""
        from deeplearning4j_tpu.serving.prefix_cache import (
            apply_admission)

        while True:
            try:
                self._waiting.append(self._pending.get_nowait())
            except _queue.Empty:
                break
        admitted = 0
        while self._free_slots and self._waiting:
            req = self._waiting[0]
            plan = None
            if self._kv is not None:
                plan = self._page_plan(req)
                if plan is None:
                    break   # head-of-line waits for pages: strict FIFO
            self._waiting.pop(0)
            slot = self._free_slots.pop()
            req.slot = slot
            adopted = 0
            if self._kv is not None:
                tplan, dplan = plan
                total = len(req.prompt) + req.max_new
                try:
                    adopted = apply_admission(self._kv, self._pcache,
                                              tplan, slot, total)
                    if dplan is not None:
                        self._spec.admit(slot, total, dplan,
                                         target_adopted=adopted)
                except Exception as e:
                    # defensive: a lane-accounting failure must fail
                    # THIS request, never the engine thread (a dead
                    # loop wedges every queued request silently)
                    self._kv.release(slot)
                    if self._spec is not None:
                        self._spec.release(slot)
                    self._free_slots.append(slot)
                    req.slot = None
                    if not req.future.done():
                        req.future.set_exception(DecodeError(
                            f"admission failed: "
                            f"{type(e).__name__}: {e}"))
                    req.stream.put(_DecodeRequest._END)
                    continue
                if adopted:
                    # the adopted pages already hold this prefix's KV:
                    # prefill starts at the suffix (>= 1 prompt token
                    # always remains — match() never covers the last)
                    req.ptr = adopted * self._kv.page
                if self._pcache is not None:
                    if adopted:
                        self._pcache.hits += 1
                        if inst is not None:
                            inst.prefix_hits.inc()
                    else:
                        self._pcache.misses += 1
                        if inst is not None:
                            inst.prefix_misses.inc()
            self._state = self.model.reset_slot(self._state, slot)
            self._active[slot] = req
            admitted += 1
            if self._slot_state_bytes and inst is not None:
                inst.state_start(self._slot_state_bytes)
            # submit -> slot join: the decode analog of queue-wait
            t_join = time.perf_counter()
            if inst is not None:
                inst.decode_queue_wait.observe(t_join - req.t_submit)
            if req.trace is not None:
                tracing.emit("decode.queue", req.trace, req.t_submit,
                             t_join, slot=slot, req_id=req.req_id)
        return admitted

    # per-request ceiling on per-boundary spans; the remainder folds
    # into one aggregate decode.tokens span at finish
    boundary_span_cap = 64

    def _finish(self, req, error=None):
        slot = req.slot
        if req.trace is not None and req.t_suppressed is not None:
            tracing.emit("decode.tokens", req.trace, req.t_suppressed,
                         time.perf_counter(), slot=slot,
                         boundaries=(len(req.prompt) + len(req.generated)
                                     - 1 - req.spans_emitted))
        self._active.pop(slot, None)
        if self._kv is not None:
            self._kv.release(slot)
        if self._spec is not None:
            self._spec.release(slot)
        self._free_slots.append(slot)
        if error is not None:
            if not req.future.done():
                req.future.set_exception(error)
        elif not req.future.done():
            req.future.set_result(list(req.generated))
        req.stream.put(_DecodeRequest._END)

    def _fail_boundary(self, err):
        """A launch raised: the engine goes on from fresh pools with
        nothing cached over them, and every active request ends with
        ``err`` (last, so a caller that tries again finds the engine
        ready). The pool a launch was given is consumed whether or not
        the launch came back, so neither it nor a page the prefix
        caches published from it can be used again. The error may have
        surfaced at the read-back of one launch with the next already
        dispatched on the consumed pool: that one's record goes with
        the state, nothing of it is delivered."""
        self._flight = None
        self._account.clear()
        try:
            self._state = None      # let the old pool go before the new
            self._state = self.model.init_state()
            if self._spec is not None:
                self._spec.reset_state()
        finally:
            self.clear_prefix_cache()
            for req in list(self._active.values()):
                self._finish(req, error=err)

    def _model_step(self, state, tokens, pos, table):
        """(tokens, state, beside) of one launch of the token step.
        ``beside`` is what the step returns after its state, as a tuple,
        which ``_deliver`` reads with the tokens and publishes; None where
        the step returns tokens and state alone. First the router's
        counts of a model whose step routes tokens to experts: float32
        ``[len(model.moe_layers), 5]``, a row (every choice, held choices,
        dropped, the fullest held expert over the mean, held experts
        touched: what ``MoeInstruments.step`` takes) a sparse layer over
        the rows the launch fed. After them, from a model whose residual
        path has more than one stream, that path's health over the same
        rows: float32 ``[2]``, the largest distance of a mixing map's row
        or column sum from 1 and the largest gain of a row's streams from
        entry to exit (``ServingInstruments.residual_health``)."""
        kw = ({"site": f"decode:{self.name}:step"}
              if self._step_takes_site else {})
        nxt, state, *beside = self.model.step(state, tokens, pos, table,
                                              **kw)
        return nxt, state, tuple(beside) or None

    def clear_prefix_cache(self):
        """Drop every cached prefix chain (both lanes), releasing the
        cache's page references — after every request has finished,
        the pool provably returns to fully free (the leak test)."""
        n = 0
        if self._pcache is not None and self._kv is not None:
            n = self._pcache.clear(self._kv)
        if self._spec is not None:
            n += self._spec.clear_prefix_cache()
        return n

    def _publish(self, req, slot, written):
        """Put the request's full prompt pages into the prefix cache —
        once, at the boundary that delivers a launch after which
        ``written`` positions, the whole prompt, are known written."""
        if self._pcache is None or req.published or \
                written < len(req.prompt):
            return
        req.published = True
        n_full = len(req.prompt) // self._kv.page
        if not n_full:
            return
        owned = self._kv.owned(slot)
        if len(owned) >= n_full:
            self._pcache.publish(self._kv, req.prompt, owned[:n_full])
        if self._spec is not None:
            self._spec.publish(req.prompt, slot)

    def _emit_token(self, req, tok, inst):
        """Append one generated token, stream it, observe TTFT on the
        first. Returns True when the request just finished."""
        req.generated.append(tok)
        req.stream.put(tok)
        if req.t_first is None:
            req.t_first = time.perf_counter()
            if inst is not None:
                inst.ttft.observe(req.t_first - req.t_submit)
        return (len(req.generated) >= req.max_new
                or (req.eos_id is not None and tok == req.eos_id))

    def _boundary_done(self, inst, executable, prompt=0, answer=0,
                       positions=None):
        """What the engine counts once a boundary, at the end of its
        emit phase: the boundary, the positions it fed, and the gauges
        with the pool's fill summed beside its gauge (a mean without
        polling). ``positions`` are those the token step's launch fed
        its active slots: the pages their contexts reach are what its
        attention visited (a block boundary passes none and is left
        out of that sum)."""
        inst.boundary(executable, prompt, answer)
        inst.slots.set(len(self._active))
        if self._kv is not None:
            fill = self._kv.used_pages / max(1, self._kv.n_pages)
            inst.kv_occupancy.set(fill)
            inst.kv_fill_sum.inc(fill)
            if positions is not None:
                inst.live_pages_sum.inc(
                    int((positions // self._kv.page + 1).sum()))

    def _prefill_boundary(self, inst) -> bool:
        """Boundary phase 1 (ISSUE 12 tentpole a): retire up to
        ``chunk`` prompt tokens per prefilling slot through the block
        executable — always leaving the final prompt token for the
        emitting phase, so first-token emission stays on the
        per-token/verify path. Returns False when the dispatch failed
        (every request was failed, skip phase 2)."""
        todo = {s: r for s, r in list(self._active.items())
                if r.ptr < len(r.prompt) - 1}
        if not todo:
            return True
        S = self.model.max_slots
        C = self._block.chunk
        self._account.clear()   # no interval between token steps across it
        with _phase(inst, "build", self._account):
            blocks = np.zeros((S, C), np.int32)
            pos0 = np.zeros((S,), np.int32)
            counts = np.zeros((S,), np.int32)
            for slot, req in todo.items():
                n = min(C, len(req.prompt) - 1 - req.ptr)
                blocks[slot, :n] = req.prompt[req.ptr:req.ptr + n]
                pos0[slot] = req.ptr
                counts[slot] = n
            # a REAL copy, not ascontiguousarray (which aliases an
            # already-contiguous table): admission mutates the table
            # between boundaries, and jax may zero-copy numpy inputs
            table = self._table.copy()
        t_b0 = time.perf_counter()
        try:
            with _phase(inst, "dispatch", self._account):
                outs, self._state = self._block.launch(
                    self._state, blocks, pos0, counts, table,
                    site=f"decode:{self.name}:prefill")
            with _phase(inst, "readback", self._account):
                np.asarray(outs)    # prefill wants none of it: the wait
                if self._spec is not None:
                    self._spec.prefill(blocks, pos0, counts)
        except Exception as e:
            # OOM forensics (ISSUE 14): a device allocation failure at
            # this boundary fails the requests with the typed error
            self._fail_boundary(_boundary_error(
                e, f"decode:{self.name}:prefill", "chunk prefill failed"))
            return False
        t_b1 = time.perf_counter()
        with _phase(inst, "emit", self._account):
            self._last_boundary = time.monotonic()
            for slot, req in todo.items():
                if self._active.get(slot) is not req:
                    continue
                req.ptr += int(counts[slot])
                if req.trace is not None and \
                        req.spans_emitted < self.boundary_span_cap:
                    req.spans_emitted += 1
                    tracing.emit("decode.prefill_chunk", req.trace,
                                 t_b0, t_b1, slot=slot,
                                 tokens=int(counts[slot]), pos=req.ptr)
            if inst is not None:
                self._boundary_done(inst, "prefill",
                                    prompt=int(counts.sum()))
        return True

    def _has_position(self, req):
        """Whether ``req`` has a position left to feed, by count: the
        prompt's and all but the last of its answer's. (A request that
        asked for no token is given one, as ever.)"""
        return req.ptr < len(req.prompt) + max(req.max_new, 1) - 1

    def _step_boundary(self, inst):
        """One per-token boundary through the step executable: every
        active slot with a position left advances one token (prefilling
        slots feed their next prompt token), and one token step stays
        in flight. The launch of this boundary is built and dispatched
        BEFORE the launch in flight is read: a slot that answers is fed
        the marker -1 and takes that launch's token on the device
        (``_pick_token``), positions advance here, and the boundary in
        flight is then read, emitted and retired (``_deliver``) while
        the device runs this one. A slot that is not fed, free or
        still holding its pages while its last launch is out, is given
        a zero row of the page table and writes the scratch page. This
        boundary stays in flight in its turn unless the engine is
        serial (a block executable or a draft: their boundaries need
        its tokens on the host) or nobody active has a position left to
        feed; then it is delivered at once, as a boundary with nothing
        to overlap."""
        prev, self._flight = self._flight, None
        S = self.model.max_slots
        with _phase(inst, "build", self._account):
            feed = np.zeros((S,), np.int32)
            pos = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            fed = []
            # snapshot: close() may clear _active concurrently
            for slot, req in list(self._active.items()):
                if not self._has_position(req):
                    continue        # its last launch is out, not read
                k = req.ptr - len(req.prompt)
                if k < 0:
                    feed[slot] = req.prompt[req.ptr]
                elif k < len(req.generated):
                    feed[slot] = req.generated[k]
                else:
                    feed[slot] = -1     # in flight: prev's nxt[slot]
                pos[slot] = req.ptr
                active[slot] = True
                fed.append((slot, req, req.ptr))
            # a REAL copy, not ascontiguousarray (which aliases an
            # already-contiguous table): admission mutates the table
            # between boundaries, and jax may zero-copy numpy inputs
            table = self._table.copy()
            # a slot that is not fed writes K/V of (token 0, position 0)
            # all the same: to the scratch page, as a free slot's zero
            # row does, not to the first page of a request whose last
            # launch is out (a prefix page that others may share)
            table[~active] = 0
        t_b0 = time.perf_counter()
        if inst is not None:
            inst.dispatched(self._account, t_b0)
        try:
            with _phase(inst, "dispatch", self._account):
                tokens = feed
                if (feed < 0).any():
                    tokens = self._pick(feed, prev[0])
                nxt, self._state, beside = self._model_step(
                    self._state, tokens, pos, table)
                if self._spec is not None:
                    # fallback boundaries keep the draft pool in sync so
                    # a later speculation probe proposes from real
                    # context
                    self._spec.track(feed, pos, active)
        except Exception as e:
            self._fail_boundary(_boundary_error(
                e, f"decode:{self.name}:step", "decode step failed"))
            return
        for _, req, _ in fed:
            req.ptr += 1
        launch = (nxt, fed, t_b0, beside)
        if prev is not None and not self._deliver(inst, prev, True):
            return
        if self._pick is not None and any(
                self._has_position(r)
                for r in list(self._active.values())):
            self._flight = launch
        else:
            self._deliver(inst, launch, False)

    def _deliver(self, inst, launch, overlapped):
        """Read a dispatched token step's tokens and give them out: the
        `readback` and `emit` phases of its boundary, from the record
        the launch left of what it fed. ``overlapped`` says that its
        successor was dispatched before this read. A row whose request
        has ended since the dispatch (its ``eos`` came with the
        boundary before, or it was failed or closed) is discarded:
        nothing of it is emitted or counted. What the step returned
        beside its tokens (``_model_step``: the router's counts, the
        residual path's health) comes to the host in the same read as the
        tokens and goes to the ``dl4j_moe_*`` series and the ``dl4j_hc_*``
        gauges at the end of `emit`: nothing is read a second time, and
        nothing on the dispatch side waits for them. Returns False when
        the read raised (every request was failed)."""
        nxt, fed, t_b0, beside = launch
        try:
            with _phase(inst, "readback", self._account):
                if beside is None:
                    nxt = np.asarray(nxt)
                else:
                    import jax

                    nxt, beside = jax.device_get((nxt, beside))
        except Exception as e:
            self._fail_boundary(_boundary_error(
                e, f"decode:{self.name}:step", "decode step failed"))
            return False
        t_b1 = time.perf_counter()
        with _phase(inst, "emit", self._account):
            self._last_boundary = time.monotonic()
            n_decoded = n_prompt = n_answer = 0
            positions = []
            for slot, req, p in fed:
                if self._active.get(slot) is not req:
                    continue
                prefilling = p + 1 < len(req.prompt)
                if req.trace is not None:
                    # one child span per token boundary this sequence
                    # took part in (ISSUE 10): prefill and decode
                    # interleave through the same executable, and the
                    # span name says which phase this boundary was.
                    # Capped per request: a near-max_new generation
                    # would otherwise evict every concurrent trace
                    # (including its own early spans) from the bounded
                    # ring — boundaries past the cap aggregate into
                    # one decode.tokens span at finish.
                    if req.spans_emitted < self.boundary_span_cap:
                        req.spans_emitted += 1
                        tracing.emit(
                            "decode.prefill" if prefilling
                            else "decode.token",
                            req.trace, t_b0, t_b1, slot=slot, pos=p)
                    elif req.t_suppressed is None:
                        req.t_suppressed = t_b0
                positions.append(p)
                if p < len(req.prompt):
                    n_prompt += 1
                else:
                    n_answer += 1
                self._publish(req, slot, p + 1)
                if prefilling:
                    continue
                done = self._emit_token(req, int(nxt[slot]), inst)
                n_decoded += 1
                if self._spec is not None and inst is not None:
                    inst.accepted("fallback", 1)
                if done:
                    self._finish(req)
            if inst is not None:
                inst.tokens.inc(n_decoded)
                self._boundary_done(inst, "step", n_prompt, n_answer,
                                    np.asarray(positions, np.int32))
                if overlapped:
                    inst.overlapped.inc()
                if beside is not None:
                    counts, *health = beside
                    if len(counts):
                        inst.moe_step(self.model.moe_layers, counts,
                                      getattr(self.model, "moe_dense", False))
                    if health:
                        inst.residual_health(*health[0])
                startup_done()      # one flag read after the process's first
        return True

    def _speculative_boundary(self, inst):
        """Boundary phase 2, speculative (ISSUE 12 tentpole c): the
        draft proposes k tokens per decoding slot, the target verifies
        the whole block in ONE call through the chunk executable, and
        the accepted prefix (plus the verifier's own next token — the
        free one) is emitted. Greedy-identical to plain decode, up to
        k+1 tokens per boundary."""
        S = self.model.max_slots
        ready = {s: r for s, r in list(self._active.items())
                 if r.ptr >= len(r.prompt) - 1}
        if not ready:       # everyone still prefilling: plain boundary
            self._step_boundary(inst)
            return
        V = self._spec.k + 1
        self._account.clear()   # no interval between token steps across it
        with _phase(inst, "build", self._account):
            feed = np.zeros((S,), np.int32)
            pos = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            for slot, req in ready.items():
                feed[slot] = (req.prompt[req.ptr]
                              if req.ptr < len(req.prompt)
                              else req.generated[-1])
                pos[slot] = req.ptr
                active[slot] = True
            # a REAL copy, not ascontiguousarray (which aliases an
            # already-contiguous table): admission mutates the table
            # between boundaries, and jax may zero-copy numpy inputs
            table = self._table.copy()
        t_b0 = time.perf_counter()
        try:
            with _phase(inst, "dispatch", self._account):
                drafts = self._spec.propose(feed, pos, active)
                blocks = np.zeros((S, V), np.int32)
                counts = np.zeros((S,), np.int32)
                for slot, req in ready.items():
                    c = min(V, req.max_new - len(req.generated))
                    blocks[slot, 0] = feed[slot]
                    if c > 1:
                        blocks[slot, 1:c] = drafts[slot, :c - 1]
                    counts[slot] = c
                outs, self._state = self._block.launch(
                    self._state, blocks, pos, counts, table,
                    site=f"decode:{self.name}:verify")
            with _phase(inst, "readback", self._account):
                outs = np.asarray(outs)
        except Exception as e:
            self._fail_boundary(_boundary_error(
                e, f"decode:{self.name}:verify",
                "speculative decode failed"))
            return
        t_b1 = time.perf_counter()
        with _phase(inst, "emit", self._account):
            self._last_boundary = time.monotonic()
            n_decoded = n_prompt = n_answer = 0
            for slot, req in ready.items():
                if self._active.get(slot) is not req:
                    continue
                c = int(counts[slot])
                if c < 1:
                    continue
                # o_0 is the target's answer to the real last token
                # (always valid); each later o_j is valid iff the draft
                # proposal fed at j matched o_{j-1} — the greedy
                # acceptance rule
                m = 1
                while m < c and \
                        int(blocks[slot, m]) == int(outs[slot, m - 1]):
                    m += 1
                self._spec.observe(m, c)
                if inst is not None:
                    inst.accepted("accepted", m)
                    if c > m:
                        inst.accepted("rejected", c - m)
                if req.trace is not None and \
                        req.spans_emitted < self.boundary_span_cap:
                    req.spans_emitted += 1
                    tracing.emit("decode.speculate", req.trace, t_b0,
                                 t_b1, slot=slot, drafted=c - 1,
                                 accepted=m, pos=req.ptr)
                # of the m positions accepted, the first was the prompt's
                # last token where the slot came straight from prefill
                first_is_prompt = req.ptr < len(req.prompt)
                n_prompt += first_is_prompt
                n_answer += m - first_is_prompt
                # rejected positions were written past the accepted
                # point in both pools — above the causal mask until the
                # true tokens overwrite those same positions (no
                # rollback)
                req.ptr += m
                self._publish(req, slot, req.ptr)
                done = False
                for j in range(m):
                    done = self._emit_token(req, int(outs[slot, j]),
                                            inst)
                    n_decoded += 1
                    if done:
                        break
                if done:
                    self._finish(req)
            self._spec.boundary_done()
            if inst is not None:
                inst.tokens.inc(n_decoded)
                self._boundary_done(inst, "verify", n_prompt, n_answer)

    def _loop(self):
        while not self._closed:
            inst = self._instruments_fn()
            # an idle poll leaves no admit observation behind: the phase
            # is timed only where there is a request to admit or advance
            busy = self._active or self._waiting or \
                not self._pending.empty()
            with _phase(inst if busy else None, "admit", self._account):
                self._admit(inst)
                for req in list(self._active.values()):
                    # until the launch of its first token is dispatched
                    if req.ptr < len(req.prompt):
                        req.ttft_boundaries += 1
            if not self._active:
                self._last_boundary = None   # idle: nothing to wedge
                self._account.clear()        # nor an interval to observe
                self._wake.wait(0.05)
                self._wake.clear()
                continue
            self._last_boundary = time.monotonic()
            if self._block is not None and \
                    not self._prefill_boundary(inst):
                continue
            if self._spec is not None and any(
                    r.ptr >= len(r.prompt) - 1
                    for r in list(self._active.values())) \
                    and self._spec.speculate_now():
                self._speculative_boundary(inst)
            else:
                self._step_boundary(inst)
        self._flight = None     # closed: what is in flight is dropped
        self._account.clear()
