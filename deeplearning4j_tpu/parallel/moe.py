"""Mixture-of-Experts with expert parallelism over the `expert` mesh axis.

Reference capability: ABSENT in the reference (SURVEY.md §2.6 marks
expert parallel "NO") — additive capability, built the TPU-native way
(GShard/Switch formulation): top-k gating produces dense one-hot
dispatch/combine tensors, expert FFNs are batched einsums with the expert
axis sharded over `expert`, and XLA inserts the all-to-alls that move
tokens to their experts. No custom scheduler, no per-expert kernels —
the MXU sees E parallel [C, H] x [H, F] matmuls.

Beside it, `moe_share_apply`: the dropless layer of a program that holds a
share of the experts (sigmoid router over all of them, sort by expert,
grouped products over the held ones, weighted scatter-add), which
`models/causal_lm.py` trains.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, EXPERT_AXIS, spec_for)


def moe_init(key, hidden: int, ffn: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / math.sqrt(hidden)
    s2 = 1.0 / math.sqrt(ffn)
    return {
        "gate_w": jax.random.normal(k1, (hidden, n_experts), dtype) * s1,
        "w1": jax.random.normal(k2, (n_experts, hidden, ffn), dtype) * s1,
        "b1": jnp.zeros((n_experts, ffn), dtype),
        "w2": jax.random.normal(k3, (n_experts, ffn, hidden), dtype) * s2,
        "b2": jnp.zeros((n_experts, hidden), dtype),
    }


def moe_param_specs() -> dict:
    """PartitionSpecs: experts sharded over the expert axis."""
    return {
        "gate_w": P(),
        "w1": P(EXPERT_AXIS), "b1": P(EXPERT_AXIS),
        "w2": P(EXPERT_AXIS), "b2": P(EXPERT_AXIS),
    }


def moe_apply(params, x, k: int = 2, capacity_factor: float = 1.5):
    """x: [N, H] tokens -> ([N, H], aux_loss).

    Top-k gating with per-expert capacity C = ceil(k*N/E * cf). Overflow
    tokens are dropped (standard GShard behavior); aux_loss is the load-
    balancing loss (Switch Transformer eq. 4)."""
    n, h = x.shape
    e = params["gate_w"].shape[1]
    c = int(math.ceil(k * n / e * capacity_factor))

    # gating math in f32 regardless of activation dtype: routing decisions
    # and the aux loss are tiny tensors but precision-sensitive
    logits = x.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)           # [N, E] f32

    # load-balancing aux loss: E * sum_e (frac tokens to e * mean prob e)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, e, dtype=probs.dtype), axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))

    # top-k expert choice per token
    topk_p, topk_i = jax.lax.top_k(probs, k)          # [N, k]
    topk_p = topk_p / jnp.maximum(
        jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity:
    # cumulative count of earlier tokens routed to the same expert
    oh = jax.nn.one_hot(topk_i, e, dtype=jnp.int32)   # [N, k, E]
    flat = oh.reshape(n * k, e)
    pos_flat = jnp.cumsum(flat, axis=0) - flat        # [N*k, E]
    pos = jnp.sum(pos_flat.reshape(n, k, e) * oh, axis=-1)  # [N, k]
    keep = pos < c                                    # capacity mask

    # dense dispatch/combine tensors [N, E, C] in the activation dtype
    # (these feed the big MXU einsums)
    pos_oh = jax.nn.one_hot(pos, c, dtype=jnp.float32) * keep[..., None]
    disp = jnp.einsum("nke,nkc->nec", oh.astype(jnp.float32),
                      pos_oh).astype(x.dtype)
    comb = jnp.einsum("nk,nke,nkc->nec", topk_p, oh.astype(jnp.float32),
                      pos_oh).astype(x.dtype)

    # to experts, through the FFN, back — XLA turns the sharded-E einsums
    # into all-to-alls over the expert axis
    expert_in = jnp.einsum("nec,nh->ech", disp, x)
    hmid = jax.nn.gelu(
        jnp.einsum("ech,ehf->ecf", expert_in, params["w1"])
        + params["b1"][:, None, :])
    expert_out = (jnp.einsum("ecf,efh->ech", hmid, params["w2"])
                  + params["b2"][:, None, :])
    y = jnp.einsum("nec,ech->nh", comb, expert_out)
    return y, aux


def moe_share_init(key, hidden: int, ffn: int, n_experts: int, held: int,
                   std: float = 0.02) -> dict:
    """A router over all `n_experts` and the gated MLPs of the `held`
    experts that live here, float32."""
    k = jax.random.split(key, 4)
    norm = lambda kk, shape: jax.random.normal(  # noqa: E731
        kk, shape, jnp.float32) * std
    return {"router": norm(k[0], (hidden, n_experts)),
            "gate": norm(k[1], (held, hidden, ffn)),
            "up": norm(k[2], (held, hidden, ffn)),
            "down": norm(k[3], (held, ffn, hidden))}


# The buffer of a share's (token, choice) pairs holds this many times the even
# share. At the worst case instead (every choice of every token: 8x for 32 of
# 256 experts) the step of PERF.md's cell took 879 ms against 743 (PR 28).
SHARE_BUFFER = 2.0


def moe_share_rows(n_tokens: int, top_k: int, n_experts: int,
                   held: int) -> int:
    """Rows of the buffer `moe_share_apply` works on: `SHARE_BUFFER` times
    the even share of the choices (a multiple of 8), and never more than
    every choice of every token, which is what the whole layer gets."""
    worst = n_tokens * top_k
    even = worst * held / n_experts
    return min(worst, int(math.ceil(SHARE_BUFFER * even / 8.0)) * 8)


def _keep_groups(select, n_group: int, topk_group: int):
    """`select` [N, E] with the experts outside each token's best
    `topk_group` of `n_group` equal groups at -inf. A group's mark is the
    sum of its two largest entries (node-limited routing: a token's
    experts lie on at most `topk_group` nodes)."""
    n, e = select.shape
    grouped = select.reshape(n, n_group, e // n_group)
    mark = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)       # [N, G]
    _, best = jax.lax.top_k(mark, topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                       # [N, G]
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, e)


def moe_share_apply(params, x, *, top_k: int, experts_held,
                    routed_scale: float = 1.0, n_group: int = 1,
                    topk_group: int = 1, rows: int | None = None,
                    live=None):
    """The part of a sigmoid-routed expert layer that the experts held here
    give. x: [N, H] -> (y [N, H] float32, choices int32 [held], dropped
    int32 scalar).

    `experts_held = (first, count)` names the experts whose weights
    `params` holds (`gate`, `up` [count, H, F], `down` [count, F, H]). The
    router scores every token over ALL experts (`router` [H, E], float32
    sigmoid), takes the `top_k` largest and weighs each chosen expert by
    `routed_scale * s_e / sum of the chosen s`. Where `params` holds a
    `bias` [E] (a buffer, not trained by the loss) the choice is made on
    `s + bias` and the weights stay the unbiased `s`; with `n_group > 1`
    it is made inside each token's best `topk_group` groups
    (`_keep_groups`, their marks from `s + bias` too). One group and no
    bias is the plain top-k over all experts. The (token, choice) pairs
    whose expert lives here are sorted by expert into one buffer of static
    size, pass through three grouped products (`jax.lax.ragged_dot`, which
    on a TPU is a kernel that skips the tiles no group fills) and are
    added, weighted, into their tokens' rows. The buffer holds
    `moe_share_rows` pairs, twice the even share; `dropped` counts the held
    pairs that did not fit and were left out, and a caller that wants the
    layer dropless holds that count to nought (the whole layer's buffer is
    the worst case and drops nothing; `rows` sets another size, as a token
    step does, whose worst case is small). `live` [N] bool names the rows
    of x that carry a token: the pairs of the others (a decode batch's idle
    slots) are neither worked on nor counted. What the absent experts would
    add is left out: on one chip the layer runs without its exchange, and
    the shares of all chips add up to the whole layer. `choices[e]` counts
    the pairs routed to held expert `e`."""
    n, _ = x.shape
    first, count = experts_held
    n_experts = params["router"].shape[1]
    if rows is None:
        rows = moe_share_rows(n, top_k, n_experts, count)
    dtype = x.dtype
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), params["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        select = scores
        if "bias" in params:
            select = scores + params["bias"].astype(jnp.float32)
        if n_group > 1:
            select = _keep_groups(select, n_group, topk_group)
        if select is scores:
            top_s, top_i = jax.lax.top_k(scores, top_k)        # [N, k]
        else:
            _, top_i = jax.lax.top_k(select, top_k)
            top_s = jnp.take_along_axis(scores, top_i, axis=-1)
        weight = (routed_scale * top_s
                  / jnp.sum(top_s, -1, keepdims=True)).reshape(-1)
        local = top_i - first
        here = (local >= 0) & (local < count)
        if live is not None:
            here &= live[:, None]
        # pairs of absent experts sort behind every held one
        group = jnp.where(here, local, count).reshape(-1)       # [N*k]
        pair = jnp.argsort(group, stable=True)[:rows]
        choices = jnp.sum(
            group[:, None] == jnp.arange(count, dtype=group.dtype)[None],
            axis=0, dtype=jnp.int32)
        # where each held group ends in the buffer: cut at its end
        ends = jnp.minimum(jnp.cumsum(choices), rows)
        dropped = jnp.sum(choices) - ends[-1]
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        token = pair // top_k
        filled = jnp.arange(rows) < ends[-1]
        w_rows = jnp.where(filled, weight[pair], 0.0)
    with jax.named_scope("moe.experts"):
        # On a TPU the grouped product leaves the rows past the last group
        # as it found them, forward and backward: whatever crosses such a
        # product is selected (never multiplied) back to nought on the
        # other side, so that neither the rows nor their cotangents reach a
        # token.
        live = lambda a: jnp.where(filled[:, None], a, 0)  # noqa: E731
        xs = live(x[token])                                     # [rows, H]
        dot = lambda a, b: live(jax.lax.ragged_dot(  # noqa: E731
            a, b.astype(dtype), sizes, preferred_element_type=jnp.float32))
        mid = (jax.nn.silu(dot(xs, params["gate"]))
               * dot(xs, params["up"])).astype(dtype)
        out = dot(mid, params["down"]) * w_rows[:, None]        # f32
        y = jnp.zeros(x.shape, jnp.float32).at[token].add(out)
    return y, choices, dropped


class MoELayerTrainer:
    """Minimal expert-parallel trainer: one MoE FFN block regressing
    targets, params expert-sharded, batch data-sharded."""

    def __init__(self, mesh: Mesh, hidden=16, ffn=32, n_experts=4, k=2,
                 lr=1e-2, aux_weight=1e-2, seed=0):
        self.mesh = mesh
        self.k = k
        self.lr = lr
        self.aux_weight = aux_weight
        params = moe_init(jax.random.key(seed), hidden, ffn, n_experts)
        to_sh = lambda s: NamedSharding(  # noqa: E731
            mesh, P(*[a if a in mesh.axis_names else None for a in s]))
        self.p_sh = {kk: to_sh(s) for kk, s in moe_param_specs().items()}
        self.params = jax.device_put(params, self.p_sh)
        self.x_sh = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))
        self._step_fn = None

    def loss(self, params, x, y):
        out, aux = moe_apply(params, x, k=self.k)
        return jnp.mean((out - y) ** 2) + self.aux_weight * aux

    def _build(self):
        repl = NamedSharding(self.mesh, P())

        def step(params, x, y):
            loss, grads = jax.value_and_grad(self.loss)(params, x, y)
            params = jax.tree_util.tree_map(
                lambda p, g: p - self.lr * g, params, grads)
            return loss, params

        return jax.jit(step, in_shardings=(self.p_sh, self.x_sh, self.x_sh),
                       out_shardings=(repl, self.p_sh), donate_argnums=(0,))

    def train_step(self, x, y):
        if self._step_fn is None:
            self._step_fn = self._build()
        loss, self.params = self._step_fn(self.params, np.asarray(x),
                                          np.asarray(y))
        return loss
