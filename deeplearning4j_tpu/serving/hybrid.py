"""Token-step decode of a hybrid state-space causal LM: state by slot beside
paged K and V.

The block is `models/causal_lm.py`'s, read from the same `CausalLMConfig`
(`from_hybrid_published`): every layer ONE mixer, `h <- h + mixer(norm(h))`,
a Mamba-2 mixer (`mamba`), grouped-head attention without rotary embedding
(`full`) or latent experts with a shared expert (`sparse`,
`parallel/moe.py:moe_share_apply`). Two kinds of state live in one `state`,
both donated and written in place:

- **pages**, which `PagedKVCache` hands out by the page table: the attention
  layers' K and V, `[attention layers, pages + 1, kv_heads, page, head_dim]`
  each. The loop over the batch's live pages and the split-K combination are
  `serving/decode.py`'s (`live_pages`, `live_page_attention`); this model
  brings the grouped-head page partial.
- **a block a slot**, which no table names: each Mamba layer's float32 state
  `[slots, heads, head_dim, ssm_state]` (an array a layer) and the
  `conv_kernel - 1` rows its causal convolution still sees, `[mamba layers,
  slots, conv_kernel - 1, conv_width]`.

A slot's recurrent state starts from nought for every request INSIDE the step:
a fed slot at position 0 reads zeros in place of what the slot's last request
left (the step reads the old state anyway, so a reset costs no launch and no
pass over the state; `reset_slot` stays the no-op it is for pages). A slot
that is not fed (its row of the page table is zero), or is masked out of a
prefill block, keeps its state bit for bit. A position is therefore not a row
of a page alone: the engine refuses `prefix_cache` and `speculative` for a
model with `slot_state` (a prefix hit would start a request past position 0
on another request's state; a rejected draft could not be rolled back).

Per-sequence determinism: every product, the scan's update and the attention
are row-wise, and so is the expert share while its products run dense
(`moe_dense`, as at up to `parallel/moe.py:DENSE_ROWS` slots)."""

from __future__ import annotations

import math

import numpy as np

from deeplearning4j_tpu.serving.decode import (
    DecodeError, _maybe_store, live_page_attention, live_pages)
from deeplearning4j_tpu.serving.latent import cast_leaves
from deeplearning4j_tpu.telemetry import compile_ledger

# leaves that stay float32 whatever the weights' dtype: norm gains, what the
# router chooses by, and what shapes the scan's update
_FLOAT32 = ("attn_norm", "mlp_norm", "final_norm", "gate_norm", "router",
            "bias", "conv_w", "conv_b", "dt_bias", "A_log", "D")


def decode_layout(params, dtype):
    """`causal_lm.init_params`'s tree as the token step reads it: matrices in
    `dtype`, the `_FLOAT32` leaves in float32 (`latent.cast_leaves`); a tree
    of shapes gives the layout's shapes."""
    import jax

    if any(isinstance(a, jax.ShapeDtypeStruct)
           for a in jax.tree_util.tree_leaves(params)):
        return jax.eval_shape(lambda p: decode_layout(p, dtype), params)
    return cast_leaves(params, dtype, _FLOAT32)


class HybridDecodeModel:
    """Causal single-token decode of a hybrid state-space LM, behind the
    engine's model protocol. `params` is a tree shaped as
    `causal_lm.init_params(cfg, ...)` makes it; `dtype` is the weights',
    activations', pages' and convolution tails' (bfloat16 as served;
    products accumulate in float32; norms, softmax, router scores, the
    convolution and the scan's state and update are float32). The step
    returns, beside the tokens, what each expert layer's router did with the
    rows the launch fed (`DecodeEngine._model_step`); `moe_layers` names
    those layers and `moe_dense` says whether their products run dense."""

    uses_pages = True
    # beside its pages the model holds state by slot: a request's state
    # starts at its position 0 and nowhere else (the engine asks)
    slot_state = True
    state_donation = (1,)

    def __init__(self, params, cfg, max_slots=8, page=16,
                 max_pages_per_slot=8, n_pages=None, dtype="bfloat16"):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.parallel.moe import moe_share_dense

        kinds = [(s.attention, s.mlp) for s in cfg.layers]
        if any(k not in (("mamba", "none"), ("full", "none"),
                         ("none", "sparse")) for k in kinds) or cfg.rope:
            raise DecodeError(
                "HybridDecodeModel serves layers of one mixer each: mamba, "
                "full attention without rotary embedding, or sparse experts")
        if cfg.streams > 1:
            raise DecodeError(
                f"HybridDecodeModel carries one residual stream a position; "
                f"the description asks for streams = {cfg.streams}")
        self.cfg = cfg
        self.dtype = jnp.dtype(dtype)
        self.params = decode_layout(params, self.dtype)
        self.mamba_layers = tuple(
            i for i, s in enumerate(cfg.layers) if s.attention == "mamba")
        self.attn_layers = tuple(
            i for i, s in enumerate(cfg.layers) if s.attention == "full")
        self.moe_layers = tuple(cfg.sparse_layers)
        self.vocab = cfg.vocab_held
        self.max_slots = int(max_slots)
        self.page = int(page)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.max_len = self.page * self.max_pages_per_slot
        self.n_pages = (int(n_pages) if n_pages is not None
                        else max_slots * max_pages_per_slot)
        # the step asks its expert share for `max_slots * top_k` rows
        self.moe_dense = bool(self.moe_layers) and moe_share_dense(
            self.max_slots, cfg.top_k, self.max_slots * cfg.top_k)
        self._jit_step = _maybe_store(
            jax.jit(self._fn, donate_argnums=self.state_donation),
            "decode:step", self, "step", donation=self.state_donation)
        self._jit_masked = _maybe_store(
            jax.jit(self.masked_fn, donate_argnums=self.state_donation),
            "decode:step", self, "masked", donation=self.state_donation)

    def _store_program(self):
        """Store program digest: the block description and the engine
        geometry determine the step."""
        return (f"decode:HybridDecodeModel:{self.cfg!r}"
                f":dtype={self.dtype.name}:slots={self.max_slots}"
                f":page={self.page}:pages={self.n_pages}"
                f":pps={self.max_pages_per_slot}")

    def init_state(self):
        """`kv` pages: K and V side by side on the leading axis, `[2,
        attention layers, pages + 1, kv_heads, page, head_dim]`, page 0
        scratch; a head's page `[page, head_dim]` fills the device's tiles as
        written and is an operand of the score and the value products as it
        lies. `ssm`: an array a Mamba layer, `[slots, heads, head_dim,
        ssm_state]` float32: a layer of one stacked array would be updated by
        a dynamic-update-slice, which the compiler keeps apart from the
        read-out `S C`, so that the state is read twice a launch (PERF.md, PR
        34: read from an AOT compile). `conv` `[mamba layers, slots,
        conv_kernel - 1, conv_width]`."""
        import jax.numpy as jnp

        cfg, M = self.cfg, len(self.mamba_layers)
        heads = cfg.layers[self.mamba_layers[0]].heads if M else 0
        return {
            "kv": jnp.zeros((2, len(self.attn_layers), self.n_pages + 1,
                             cfg.kv_heads, self.page, cfg.head_dim),
                            self.dtype),
            "ssm": tuple(jnp.zeros((self.max_slots, heads,
                                    cfg.mamba_head_dim, cfg.ssm_state),
                                   jnp.float32) for _ in range(M)),
            "conv": jnp.zeros((M, self.max_slots, max(cfg.conv_kernel - 1, 0),
                               cfg.conv_width if M else 0), self.dtype)}

    def _bytes(self, *leaves):
        import jax

        from deeplearning4j_tpu.telemetry import memledger

        shapes = jax.eval_shape(self.init_state)
        return memledger.tree_bytes([shapes[k] for k in leaves])

    def slot_state_bytes(self) -> int:
        """Bytes of the state held by slot: the scans' states and the
        convolution tails, beside the pool's pages."""
        return self._bytes("ssm", "conv")

    def pool_device_bytes(self) -> dict:
        """{device label: bytes} of everything `init_state` pins, pages and
        slot state: all of it on the one device a decode replica runs on."""
        from deeplearning4j_tpu.telemetry import memledger

        return {memledger.device_label(): self._bytes("kv", "ssm", "conv")}

    def _attend(self, q, kv, ai, live):
        """q [S, H, D] against each slot's own positions of attention layer
        ``ai`` -> [S, H, D] float32. The grouped-head partial of a chunk of
        live pages: each of the ``kv_heads`` K and V heads serves its group of
        ``H / kv_heads`` query heads, a page's scores one ``[group, D] x [D,
        page]`` product a head."""
        import jax.numpy as jnp

        cfg, dt = self.cfg, self.dtype
        H, KV, D = q.shape[1], cfg.kv_heads, cfg.head_dim
        scale = 1.0 / math.sqrt(D)
        cols = jnp.arange(self.page)
        qg = q.reshape(q.shape[0], KV, H // KV, D)

        def partial(slot, pg, last):
            kb, vb = kv[0, ai, pg], kv[1, ai, pg]      # [C, KV, page, D]
            s = jnp.einsum("ckgd,ckpd->ckgp", qg[slot], kb,
                           preferred_element_type=jnp.float32) * scale
            seen = cols[None, :] <= last[:, None]      # causal + length
            s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
            m = jnp.max(s, axis=-1)                    # [C, KV, G]
            p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
            o = jnp.einsum("ckgp,ckpd->ckgd", p.astype(dt), vb,
                           preferred_element_type=jnp.float32)
            C = slot.shape[0]
            return (m.reshape(C, H), jnp.sum(p, axis=-1).reshape(C, H),
                    o.reshape(C, H, D))

        return live_page_attention(live, partial, lambda a: a[..., None],
                                   H, (H, D))

    def _fn(self, params, state, tokens, pos, table):
        import jax.numpy as jnp

        pidx = table[jnp.arange(self.max_slots), pos // self.page]
        logits, state, counts = self._apply(params, state, tokens, pos,
                                            table, pidx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, state, counts) if self.moe_layers else (nxt, state)

    def masked_fn(self, params, state, tokens, pos, table, active):
        """The step math with inactive slots left alone: their page writes
        land on page 0, their slot state stays bit for bit, their rows are
        left out of the expert layers and their outputs are -1, while an
        active row computes what ``_fn`` computes (serving/prefill.py builds
        on that)."""
        import jax.numpy as jnp

        pos = jnp.where(active, pos, 0)
        pidx = jnp.where(
            active, table[jnp.arange(self.max_slots), pos // self.page], 0)
        logits, state, _ = self._apply(params, state, tokens, pos, table,
                                       pidx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(active, nxt, -1), state

    @staticmethod
    def _request_starts(fed, pos):
        """[S] bool: the fed slots at a request's first position, whose slot
        state is read as nought."""
        return fed & (pos == 0)

    def _apply(self, params, state, tokens, pos, table, pidx):
        """-> (float32 logits [S, vocab], the state, the routers' counts
        float32 [expert layers, 5]). ``pidx [S]`` is the page each slot
        writes: the scratch page for a slot that is not fed (the engine gives
        it a zero row of the table), which is also how the step knows the
        rows that carry a token, and so whose slot state moves."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.causal_lm import (
            _mm, mamba_step, mlp_apply, rms_norm)
        from deeplearning4j_tpu.parallel.moe import moe_share_apply

        cfg, dt, S = self.cfg, self.dtype, self.max_slots
        KV, D = cfg.kv_heads, cfg.head_dim
        fed = pidx != 0
        start = self._request_starts(fed, pos)
        n_fed = jnp.sum(fed).astype(jnp.float32)
        at_row = (jnp.arange(self.page)[None, None, :, None]
                  == (pos % self.page)[:, None, None, None])
        live = (live_pages(pos, table, self.page)       # once a step
                if self.attn_layers else None)
        kv, ssm, conv = state["kv"], list(state["ssm"]), state["conv"]
        h = params["embed"][tokens].astype(dt)
        counts = []
        mi = ai = 0
        for lp, spec in zip(params["layers"], cfg.layers):
            norm = lp["mlp_norm" if spec.mlp == "sparse" else "attn_norm"]
            u = rms_norm(h, norm, cfg.rms_eps).astype(dt)
            if spec.attention == "mamba":
                # what the slot's last request left is read as zeros at a
                # request's first position; a slot that is not fed gets back
                # what it had, selected and never multiplied
                old_t, old_s = conv[mi], ssm[mi]
                out, new_t, new_s = mamba_step(
                    lp, u, jnp.where(start[:, None, None], 0, old_t),
                    jnp.where(start[:, None, None, None], 0.0, old_s),
                    cfg, spec.heads)
                with jax.named_scope("ssm.update"):
                    ssm[mi] = jnp.where(fed[:, None, None, None], new_s,
                                        old_s)
                conv = conv.at[mi].set(jnp.where(
                    fed[:, None, None], new_t, old_t))
                mi += 1
            elif spec.attention == "full":
                with jax.named_scope("gqa.attend"):
                    def heads(w, n):
                        return _mm(u, w).astype(dt).reshape(S, n, D)

                    q = heads(lp["wq"], spec.heads)
                    # S rows a layer into the donated pages, in place,
                    # before the layer's attention reads them: each slot's
                    # page comes out, takes the row by a select and goes
                    # back whole (a scatter of single rows makes the device
                    # turn the whole pool round and back in every launch:
                    # PERF.md, PR 34, as PR 32 found for its latents)
                    for j, w in enumerate(("wk", "wv")):
                        pages = kv[j, ai, pidx]         # [S, KV, page, D]
                        kv = kv.at[j, ai, pidx].set(jnp.where(
                            at_row, heads(lp[w], KV)[:, :, None, :], pages))
                    o = self._attend(q, kv, ai, live)
                    out = _mm(o.astype(dt).reshape(S, spec.heads * D),
                              lp["wo"])
                ai += 1
            else:
                # `rows` is every choice of every row: nothing is ever
                # dropped, and at up to `moe.DENSE_ROWS` slots the products
                # run dense (`self.moe_dense`)
                routed, choices, dropped = moe_share_apply(
                    lp["moe"], u, top_k=cfg.top_k,
                    experts_held=cfg.experts_held,
                    routed_scale=cfg.routed_scale, n_group=cfg.n_group,
                    topk_group=cfg.topk_group, rows=S * cfg.top_k, live=fed,
                    activation=cfg.expert_act)
                with jax.named_scope("moe.shared"):
                    out = routed + mlp_apply(lp["shared"], u, cfg.expert_act)
                held = choices.astype(jnp.float32)
                counts.append(jnp.stack([
                    n_fed * cfg.top_k, jnp.sum(held),
                    dropped.astype(jnp.float32),
                    jnp.max(held) / jnp.maximum(jnp.mean(held), 1e-9),
                    jnp.sum(held > 0).astype(jnp.float32)]))
            h = (h + out).astype(dt)
        with jax.named_scope("lm.head"):
            x = rms_norm(h, params["final_norm"], cfg.rms_eps).astype(dt)
            logits = _mm(x, params["head"])
        counts = (jnp.stack(counts) if counts
                  else jnp.zeros((0, 5), jnp.float32))
        return logits, {"kv": kv, "ssm": tuple(ssm), "conv": conv}, counts

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
        # pages: stale rows are unreachable once the page table drops them;
        # slot state: the step itself starts a request from nought
        return state
