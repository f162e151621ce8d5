"""Token-step decode of a latent-attention causal LM over a paged pool of
latents.

The block is `models/causal_lm.py`'s, read from the same `CausalLMConfig`
the trainer reads: every layer `latent` attention, a `dense` or a `sparse`
MLP (`parallel/moe.py:moe_share_apply`, the share of the experts held
here). What a position leaves in the cache is not K and V by head but one
row all heads share: the normed latent `c` and the rotated key `k_r`,
`kv_rank + rope_dim` numbers a position and layer. The token step ABSORBS
the up-projection `wkv_b` into the query and the output,

    q_lat_h = q_nope_h W^K_h        score_h,j = q_lat_h . c_j + q_rope_h . k_r,j
    o_lat_h = sum_j p_h,j c_j       o_h = o_lat_h W^V_h   (a page at a time)

so a cached position is never expanded into heads: a page's scores are one
`[H, kv_rank + rope_dim] x [kv_rank + rope_dim, page]` product and its
values the first `kv_rank` of the same numbers a position. On a TPU, where
the pool's pages fill whole tiles, a Pallas kernel reads each live page once
from the pool where it lies and keeps a slot's running softmax on the chip
(`kernels/latent_attention.py`; `wv_b` once a slot after it). Everywhere
else (the CPU, a small page) the loop over the batch's live pages and the
split-K combination are `serving/decode.py`'s (`live_pages`,
`live_page_attention`), shared with `TransformerDecodeModel`; this model
brings the page's partial. Which of the two a step was traced with is
counted (`kernels.decode_attention_route`). Greedy argmax over the held
rows of the vocabulary.

Per-sequence determinism: attention, norms and the dense products are
row-wise, and so is the expert share while its products run dense
(`moe_dense`: every row through every held expert, as at up to
`parallel/moe.py:DENSE_ROWS` slots). With more slots the share sorts its
pairs into a buffer, where a row's place depends on what the other rows
chose (on the CPU the grouped product gives a row the same bits wherever it
lies). Tests assert that a sequence decodes bit-identically alone and among
strangers."""

from __future__ import annotations

import math

import numpy as np

from deeplearning4j_tpu.serving.decode import (
    DecodeError, _maybe_store, live_page_attention, live_pages)
from deeplearning4j_tpu.telemetry import compile_ledger

# leaves of the block that stay float32 whatever the weights' dtype: norm
# gains, what the router chooses by, and what the residual path's maps are
# made from (`causal_lm.stream_maps`: `phi`, `alpha` and their `bias`)
_FLOAT32 = ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "final_norm",
            "router", "bias", "phi", "alpha")


def cast_leaves(params, dtype, float32):
    """`params` with every leaf in `dtype` but those named in `float32`. A
    leaf that already has its dtype is taken as it is (no second copy of a
    model that fills the chip)."""
    import jax
    import jax.numpy as jnp

    def cast(path, a):
        name = str(getattr(path[-1], "key", path[-1]))
        want = jnp.float32 if name in float32 else dtype
        a = jnp.asarray(a)
        return a if a.dtype == want else a.astype(want)

    return jax.tree_util.tree_map_with_path(cast, params)


def decode_layout(params, cfg, dtype):
    """`causal_lm.init_params`'s tree as the token step reads it: matrices
    in `dtype`, norm gains, router and bias in float32, and each layer's
    `wkv_b [kv_rank, H * (nope + v)]` split once into the two matrices the
    absorbed step multiplies by, `wk_b [H, nope, kv_rank]` and `wv_b [H,
    kv_rank, v]`. A leaf that already has its dtype is taken as it is (no
    second copy of a model that fills the chip). A tree of shapes
    (`jax.ShapeDtypeStruct`) gives the layout's shapes: a model can be built
    on shapes and its step compiled ahead of time."""
    import jax
    import jax.numpy as jnp

    if any(isinstance(a, jax.ShapeDtypeStruct)
           for a in jax.tree_util.tree_leaves(params)):
        return jax.eval_shape(lambda p: decode_layout(p, cfg, dtype), params)

    out = cast_leaves(params, dtype, _FLOAT32)
    layers = []
    for lp, spec in zip(out["layers"], cfg.layers):
        lp = dict(lp)
        kvb = lp.pop("wkv_b").reshape(cfg.kv_rank, spec.heads,
                                      cfg.nope_dim + cfg.v_dim)
        lp["wk_b"] = jnp.transpose(kvb[:, :, :cfg.nope_dim], (1, 2, 0))
        lp["wv_b"] = jnp.transpose(kvb[:, :, cfg.nope_dim:], (1, 0, 2))
        layers.append(lp)
    return dict(out, layers=layers)


class LatentDecodeModel:
    """Causal single-token decode over a paged pool of latents, behind the
    engine's model protocol. `params` is a tree shaped as
    `causal_lm.init_params(cfg, ...)` makes it; `dtype` is the weights',
    activations' and pool's (bfloat16 as served; products accumulate in
    float32; norms, rotary tables, softmax and router scores are float32).
    The step returns, beside the tokens, what each sparse layer's router
    did with the rows the launch fed (`DecodeEngine._model_step`),
    `moe_layers` names those layers, and `moe_dense` says whether their
    expert products run dense at this many slots (`moe_share_dense`: the
    engine counts such steps in `dl4j_moe_dense_steps_total`). Where the
    description gives a position more than one residual stream
    (`cfg.streams`), the step carries `[slots, streams, hidden]` through
    its layers and returns a fourth value, the residual path's two health
    numbers over the rows it fed (`_apply`)."""

    uses_pages = True
    # the pool is donated to every executable over it and written in
    # place, as `TransformerDecodeModel`'s pools are
    state_donation = (1,)

    def __init__(self, params, cfg, max_slots=8, page=16,
                 max_pages_per_slot=8, n_pages=None, dtype="bfloat16"):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.kernels import latent_attention
        from deeplearning4j_tpu.models.causal_lm import rope_tables
        from deeplearning4j_tpu.parallel.moe import moe_share_dense

        if any(s.attention != "latent" for s in cfg.layers):
            raise DecodeError("LatentDecodeModel serves latent-attention "
                              "layers only")
        self.cfg = cfg
        self.dtype = jnp.dtype(dtype)
        self.params = decode_layout(params, cfg, self.dtype)
        self.n_layers = len(cfg.layers)
        self.n_heads = cfg.layers[0].heads
        self.row = cfg.kv_rank + cfg.rope_dim
        self.vocab = cfg.vocab_held
        self.max_slots = int(max_slots)
        self.page = int(page)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.max_len = self.page * self.max_pages_per_slot
        self.n_pages = (int(n_pages) if n_pages is not None
                        else max_slots * max_pages_per_slot)
        # whether the pool's pages are shapes the paged-attention kernel
        # takes; the backend decides the rest, when a step is traced
        self.kernel_fits = latent_attention.available(
            self.n_heads, self.row, self.page, cfg.kv_rank, self.dtype)
        self.moe_layers = tuple(cfg.sparse_layers)
        # the step asks its expert share for `max_slots * top_k` rows
        self.moe_dense = bool(self.moe_layers) and moe_share_dense(
            self.max_slots, cfg.top_k, self.max_slots * cfg.top_k)
        # rotary rows of every position a slot can reach, float32
        self._cos, self._sin = rope_tables(cfg.rope["latent"], cfg.rope_dim,
                                           self.max_len)
        self._jit_step = _maybe_store(
            jax.jit(self._fn, donate_argnums=self.state_donation),
            "decode:step", self, "step", donation=self.state_donation)
        self._jit_masked = _maybe_store(
            jax.jit(self.masked_fn, donate_argnums=self.state_donation),
            "decode:step", self, "masked", donation=self.state_donation)

    def _store_program(self):
        """Store program digest: the block description and the engine
        geometry determine the step (param shapes ride in the
        per-signature key, and the values never shape the program)."""
        return (f"decode:LatentDecodeModel:{self.cfg!r}"
                f":dtype={self.dtype.name}:slots={self.max_slots}"
                f":page={self.page}:pages={self.n_pages}"
                f":pps={self.max_pages_per_slot}")

    def _pool_shape(self):
        """[L, n_pages + 1, kv_rank + rope_dim, page]; page 0 is scratch.
        One pool, not K and V: a position's latent and rotated key lie
        one under the other in the position's COLUMN of its page. With
        the positions minor a page fills the device's (16, 128) tiles as
        written and is the right-hand side of the score product as it
        lies; with the 576 numbers minor (four and a half lanes of 128)
        the device stores the pool position-minor all the same and
        every launch converts the whole pool there and back (PERF.md,
        PR 32: the compiler's own choice, read from an AOT compile).
        Both attention paths read it so: the kernel copies a page
        ``[row, page]`` to VMEM as one contiguous piece of the pool,
        which stays in HBM in this layout (a custom call's operand is
        taken as it lies: no copy of the pool, PERF.md, PR 37); the
        loop gathers chunks of such pages into a copy."""
        return (self.n_layers, self.n_pages + 1, self.row, self.page)

    def init_state(self):
        import jax.numpy as jnp

        return {"latent": jnp.zeros(self._pool_shape(), self.dtype)}

    def pool_device_bytes(self) -> dict:
        """{device label: bytes} of the latent pool: all of it on the one
        device a decode replica runs on."""
        from deeplearning4j_tpu.telemetry import memledger

        return {memledger.device_label():
                math.prod(self._pool_shape()) * self.dtype.itemsize}

    def _walk(self, pos, table):
        """Once a step, for all its layers: the route the step's
        attention takes and what writes its rows (the kernel, or XLA's
        scatter of whole pages), both counted, and the order in which it
        reads the pool: the kernel's walk over the live pages, or the
        loop's list of them."""
        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.kernels import latent_attention

        route = kernels.decode_attention_route(type(self).__name__,
                                               self.kernel_fits)
        kernels.decode_pool_write(type(self).__name__,
                                  "kernel" if route == "kernel"
                                  else "scatter")
        if route == "kernel":
            return route, latent_attention.page_walk(pos, table, self.page)
        return route, live_pages(pos, table, self.page)

    def _attend(self, q, row, wv_b, pool, li, walk, pidx, off):
        """Each fed slot's new ``row [S, kv_rank + rope_dim]`` into column
        ``off[s]`` of its page ``pidx[s]`` of layer ``li`` (the scratch
        page for a slot that is not fed), then q [S, H, kv_rank +
        rope_dim] (the absorbed query beside the rotated one) against
        each slot's own positions of that layer -> (each head's output
        [S, H, v_dim] float32, the pool so written); ``walk`` is
        `_walk`'s.

        The kernel (a TPU, pages of whole tiles) is handed the whole
        pool in HBM and reads each live page of each slot once, where
        it lies, by a copy of that page alone into VMEM; a slot's
        running maximum, sum and weighted latent stay on the chip, and
        each head's normalised latent goes through its ``wv_b`` once a
        slot. It writes the rows too: the page a fed slot writes is the
        last it copies, the column is set there before it is scored,
        and that page alone goes back to the pool, which is the
        kernel's output aliased to its input (`latent_attention`'s
        docstring: which page, the ring entry's wait, ``pidx == 0``).
        No gather, no scatter, no partials, no combination, no loop in
        the step.

        The loop (everywhere else) first writes the rows in XLA: each
        fed slot's page comes out of the donated pool, takes the
        column by a select and goes back whole (a scatter of single
        columns makes the device turn the whole pool round for it, and
        back, in every layer: PERF.md, PR 32). It then reduces a chunk
        of live pages at a time: gather the pages out of the whole
        pool into a copy, score every position against every head in
        one product, weigh
        the positions' first ``kv_rank`` numbers, and take each head's
        weighted latent through its ``wv_b`` there and then: the
        combination is linear in a page's output, and a page's partial
        is then ``v_dim`` wide a head where the weighted latent is
        ``kv_rank`` (a quarter of the float32 the loop writes and the
        combination reads: PERF.md, PR 32)."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.kernels import latent_attention

        cfg, dt = self.cfg, self.dtype
        route, live = walk
        if route == "kernel":
            o, pool = latent_attention.latent_page_attention(
                q, pool, live, row, pidx, layer=li, kv_rank=cfg.kv_rank,
                scale=cfg.latent_scale)             # [S, H, kv_rank]
            return jnp.einsum("hsr,hrd->hsd", o.swapaxes(0, 1), wv_b,
                              preferred_element_type=jnp.float32
                              ).swapaxes(0, 1), pool
        cols = jnp.arange(self.page)
        pages = pool[li, pidx]                      # [S, row, page]
        pages = jnp.where((cols[None, :] == off[:, None])[:, None, :],
                          row[:, :, None], pages)
        pool = pool.at[li, pidx].set(pages)

        def partial(slot, pg, last):
            cb = pool[li, pg]                       # [C, row, page]
            s = jnp.einsum("chd,cdp->chp", q[slot], cb,
                           preferred_element_type=jnp.float32)
            seen = cols[None, :] <= last[:, None]   # causal + length
            s = jnp.where(seen[:, None, :], s * cfg.latent_scale, -jnp.inf)
            m = jnp.max(s, axis=-1)                 # [C, H]
            p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
            o = jnp.einsum("chp,cdp->hcd", p.astype(dt),
                           cb[:, :cfg.kv_rank],
                           preferred_element_type=jnp.float32)
            o = jnp.einsum("hcr,hrd->hcd", o.astype(dt), wv_b,
                           preferred_element_type=jnp.float32)
            return m, jnp.sum(p, axis=-1), o.swapaxes(0, 1)

        return live_page_attention(live, partial, lambda a: a[..., None],
                                   self.n_heads,
                                   (self.n_heads, cfg.v_dim)), pool

    def _fn(self, params, state, tokens, pos, table):
        import jax.numpy as jnp

        pidx = table[jnp.arange(self.max_slots), pos // self.page]
        logits, state, counts, health = self._apply(
            params, state, tokens, pos, table, pidx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if health is not None:
            return nxt, state, counts, health
        return (nxt, state, counts) if self.moe_layers else (nxt, state)

    def masked_fn(self, params, state, tokens, pos, table, active):
        """The step math with inactive slots routed to scratch: their
        pool writes land on page 0, their rows are left out of the expert
        layer and their outputs are -1, while an active row computes what
        ``_fn`` computes (serving/prefill.py builds on that)."""
        import jax.numpy as jnp

        pos = jnp.where(active, pos, 0)
        pidx = jnp.where(
            active, table[jnp.arange(self.max_slots), pos // self.page], 0)
        logits, state, _, _ = self._apply(params, state, tokens, pos, table,
                                          pidx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(active, nxt, -1), state

    def _apply(self, params, state, tokens, pos, table, pidx):
        """-> (float32 logits [S, vocab], the state, the routers' counts
        float32 [sparse layers, 5], the residual path's health float32
        [2] or None for one stream). ``pidx [S]`` is the page each slot
        writes: the scratch page for a slot that is not fed (the engine
        gives it a zero row of the table), which is also how the step
        knows the rows that carry a token. The health numbers, over the
        fed rows: the largest `|row or column sum - 1|` of any sublayer's
        mixing map (`stream_maps`' `defect`), and the largest RMS of a
        row's streams at the exit over their RMS at the entry."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.causal_lm import (
            _mm, latent_project, mlp_apply, rms_norm, stream_maps,
            stream_read, stream_write, streams_enter, streams_exit)
        from deeplearning4j_tpu.parallel.moe import moe_share_apply

        cfg, dt, S, H = self.cfg, self.dtype, self.max_slots, self.n_heads
        fed = pidx != 0
        n_fed = jnp.sum(fed).astype(jnp.float32)
        cos, sin = self._cos[pos], self._sin[pos]
        off = pos % self.page
        walk = self._walk(pos, table)               # once a step
        pool = state["latent"]
        h = entry = streams_enter(params["embed"][tokens].astype(dt), cfg)
        counts, defects = [], []
        for li, (lp, spec) in enumerate(zip(params["layers"], cfg.layers)):
            maps = stream_maps(lp.get("attn_streams"), h, cfg, fused=True)
            u = rms_norm(stream_read(h, maps), lp["attn_norm"],
                         cfg.rms_eps).astype(dt)
            with jax.named_scope("mla.project"):
                q_n, q_r, c, k_r = latent_project(lp, u, cfg, H, cos, sin)
                # heads lead the product's result (the CPU backend has
                # no bfloat16 product with the batch in the middle)
                q_lat = jnp.einsum("shd,hdc->hsc", q_n, lp["wk_b"],
                                   preferred_element_type=jnp.float32)
                q = jnp.concatenate(
                    [q_lat.astype(dt).swapaxes(0, 1), q_r], axis=-1)
                row = jnp.concatenate([c, k_r], axis=-1)
            # S columns a layer into the donated pool, in place, before
            # the layer's attention reads them (`_attend`). On the
            # kernel's route the kernel sets each fed slot's column in
            # the slot's last live page, which it copies to VMEM anyway,
            # before scoring it, and sends that page back where it lies:
            # the pool is aliased from the kernel's input to its output,
            # a page's write-back is waited for before its ring entry is
            # filled again, and ``pidx == 0`` (not fed) writes nothing.
            # On the loop's route XLA takes each fed slot's page out,
            # selects the column in and puts the page back whole
            with jax.named_scope("mla.attend"):
                o, pool = self._attend(q, row, lp["wv_b"], pool, li, walk,
                                       pidx, off)
                att = _mm(o.astype(dt).reshape(S, H * cfg.v_dim), lp["wo"])
            h = stream_write(h, att, maps)
            mlp_maps = stream_maps(lp.get("mlp_streams"), h, cfg,
                                   fused=True)
            u = rms_norm(stream_read(h, mlp_maps), lp["mlp_norm"],
                         cfg.rms_eps).astype(dt)
            if maps is not None:
                defects += [maps["defect"], mlp_maps["defect"]]
            if spec.mlp == "dense":
                with jax.named_scope("mlp.dense"):
                    out = mlp_apply(lp["mlp"], u)
            else:
                # `rows` is every choice of every row, so nothing is ever
                # dropped; at up to `moe.DENSE_ROWS` slots that makes the
                # products one batched product over the held experts, no
                # buffer at all (`self.moe_dense`)
                routed, choices, dropped = moe_share_apply(
                    lp["moe"], u, top_k=cfg.top_k,
                    experts_held=cfg.experts_held,
                    routed_scale=cfg.routed_scale, n_group=cfg.n_group,
                    topk_group=cfg.topk_group, rows=S * cfg.top_k, live=fed)
                with jax.named_scope("moe.shared"):
                    out = routed + mlp_apply(lp["shared"], u)
                held = choices.astype(jnp.float32)
                counts.append(jnp.stack([
                    n_fed * cfg.top_k, jnp.sum(held),
                    dropped.astype(jnp.float32),
                    jnp.max(held) / jnp.maximum(jnp.mean(held), 1e-9),
                    jnp.sum(held > 0).astype(jnp.float32)]))
            h = stream_write(h, out, mlp_maps)
        with jax.named_scope("lm.head"):
            x = rms_norm(streams_exit(h, cfg), params["final_norm"],
                         cfg.rms_eps).astype(dt)
            logits = _mm(x, params["head"])
        counts = (jnp.stack(counts) if counts
                  else jnp.zeros((0, 5), jnp.float32))
        health = None
        if defects:
            rms = lambda a: jnp.sqrt(jnp.mean(  # noqa: E731
                jnp.square(a.astype(jnp.float32)), axis=(-2, -1)))
            health = jnp.stack([
                jnp.max(jnp.where(fed, jnp.max(jnp.stack(defects), 0), 0.0)),
                jnp.max(jnp.where(fed, rms(h) / rms(entry), 0.0))])
        return logits, {"latent": pool}, counts, health

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
        # stale rows are unreachable once the page table drops their
        # pages (the length mask covers in-page staleness): no wipe
        return state
