"""Mixture-of-Experts with expert parallelism over the `expert` mesh axis.

Reference capability: ABSENT in the reference (SURVEY.md §2.6 marks
expert parallel "NO") — additive capability, built the TPU-native way
(GShard/Switch formulation): top-k gating produces dense one-hot
dispatch/combine tensors, expert FFNs are batched einsums with the expert
axis sharded over `expert`, and XLA inserts the all-to-alls that move
tokens to their experts. No custom scheduler, no per-expert kernels —
the MXU sees E parallel [C, H] x [H, F] matmuls.

Beside it, `moe_share_apply`: the dropless layer of a program that holds a
share of the experts (sigmoid router over all of them, then the held
experts' products: sorted by expert into a buffer and grouped, or, for the
few rows of a token step, one batched product over the held experts; the
expert a gated silu MLP on the model's width or an ungated relu² MLP in a
latent space), which `models/causal_lm.py` trains and `serving/latent.py`
and `serving/hybrid.py` serve.
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
                   std: float = 0.02, gated: bool = True,
                   latent: int = 0) -> dict:
    """A router over all `n_experts` and the MLPs of the `held` experts
    that live here, float32: gated (`gate`, `up`, `down`) or, with
    `gated=False`, `up` and `down` alone. With `latent` the experts work on
    rows of that width, between `latent_in` [hidden, latent] and
    `latent_out` [latent, hidden]."""
    k = jax.random.split(key, 4)
    norm = lambda kk, shape: jax.random.normal(  # noqa: E731
        kk, shape, jnp.float32) * std
    inner = latent or hidden
    out = {"router": norm(k[0], (hidden, n_experts)),
           "up": norm(k[2], (held, inner, ffn)),
           "down": norm(k[3], (held, ffn, inner))}
    if gated:
        out["gate"] = norm(k[1], (held, inner, ffn))
    if latent:
        out["latent_in"] = norm(jax.random.fold_in(key, 4), (hidden, latent))
        out["latent_out"] = norm(jax.random.fold_in(key, 5),
                                 (latent, hidden))
    return out


# what an expert's first product goes through; a gated expert multiplies the
# activated `gate` product by the `up` product, an ungated one activates `up`
ACTIVATIONS = {"silu": jax.nn.silu,
               "relu2": lambda a: jnp.square(jax.nn.relu(a))}


def expert_mid(params, dot, act):
    """What goes into an expert's `down` product, float32: `dot(w)` is the
    rows' product with one of the expert matrices."""
    if "gate" in params:
        return act(dot(params["gate"])) * dot(params["up"])
    return act(dot(params["up"]))


# The buffer of a share's (token, choice) pairs holds this many times the even
# share. At the worst case instead (every choice of every token: 8x for 32 of
# 256 experts) the step of PERF.md's cell took 879 ms against 743 (PR 28).
SHARE_BUFFER = 2.0

# Up to this many rows, a call whose buffer is the worst case runs its
# products dense (`moe_share_dense`): every row through every held expert.
# A v5e multiplies 240 FLOPs in the time it streams one byte (197e12 over
# 819e9): under some 240 rows a bfloat16 expert's weights take longer to
# arrive than every row takes to cross them, so the rows nobody chose are
# free, and the sort, the gathers and the buffer's empty tiles are not.
DENSE_ROWS = 256


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


def moe_share_dense(n_tokens: int, top_k: int, rows: int) -> bool:
    """Whether `moe_share_apply` runs its three products as one batched
    product over the held experts: the buffer asked for is the worst case
    (`rows == n_tokens * top_k`: nothing can be dropped, so a dropless
    product and the buffer give the same result) and the batch is at most
    `DENSE_ROWS` rows. A function of the call's shapes alone; a publisher
    of the `dl4j_moe_*` series asks it what its step ran."""
    return rows == n_tokens * top_k and n_tokens <= DENSE_ROWS


def _share_route(params, x, top_k, experts_held, routed_scale, n_group,
                 topk_group, live):
    """The router of `moe_share_apply`, float32: -> (weight float32 [N * k],
    what each (token, choice) pair's expert gets of the token's result;
    group int32 [N * k], the pair's held expert 0..count-1, or `count`
    where the expert is not held here or the row is not `live`)."""
    first, count = experts_held
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
    return weight, jnp.where(here, local, count).reshape(-1)


def _held_choices(group, count: int):
    """int32 [count]: the pairs of `group` that fell on each held expert."""
    return jnp.sum(
        group[:, None] == jnp.arange(count, dtype=group.dtype)[None],
        axis=0, dtype=jnp.int32)


def _grouped_products(params, x, weight, group, count: int, top_k: int,
                      rows: int, act=jax.nn.silu):
    """The held pairs sorted by expert into one buffer of `rows` rows, three
    grouped products (`jax.lax.ragged_dot`, which on a TPU is a kernel that
    skips the tiles no group fills, and whose time follows the buffer's
    length all the same) and a weighted scatter-add into the tokens' rows.
    -> (y float32 [N, H], choices, dropped: the held pairs that did not
    fit the buffer and were left out)."""
    dtype = x.dtype
    with jax.named_scope("moe.route"):
        pair = jnp.argsort(group, stable=True)[:rows]
        choices = _held_choices(group, count)
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
        mid = expert_mid(params, lambda w: dot(xs, w), act).astype(dtype)
        out = dot(mid, params["down"]) * w_rows[:, None]        # f32
        y = jnp.zeros(x.shape, jnp.float32).at[token].add(out)
    return y, choices, dropped


def _dense_products(params, x, weight, group, count: int, top_k: int,
                    act=jax.nn.silu):
    """Every row through every held expert in one batched product a matrix
    (no sort, no gather, no scatter-add: a held expert's weights stream no
    faster than these few rows multiply them), each row's result weighed by
    the router's weight where the row chose the expert. A row that did not
    choose an expert, or is not live, is selected to nought, never
    multiplied: whatever an idle row holds stays in that row's products and
    reaches no other. Nothing can be dropped.
    -> (y float32 [N, H], choices, dropped = 0)."""
    dtype = x.dtype
    with jax.named_scope("moe.route"):
        choices = _held_choices(group, count)
        # [count, N]: a row's k choices are k different experts, so at
        # most one term of each sum is not nought
        hit = (group.reshape(-1, top_k)[None]
               == jnp.arange(count, dtype=group.dtype)[:, None, None])
        w = jnp.sum(jnp.where(hit, weight.reshape(-1, top_k)[None], 0.0),
                    axis=-1)
        chose = jnp.any(hit, axis=-1)
    with jax.named_scope("moe.experts"):
        dot = lambda spec, a, b: jnp.einsum(  # noqa: E731
            spec, a, b.astype(dtype), preferred_element_type=jnp.float32)
        if "gate" in params:
            first = lambda w: dot("sd,edf->esf", x, w)  # noqa: E731
        else:
            # the same product with the rows laid out an expert each: the
            # CPU backend folds the transposition of a lone `sd,edf->esf`
            # into the product after it and then has no bfloat16 product
            # for it (on the chip the two forms are one program: PERF.md,
            # PR 33)
            xe = jnp.broadcast_to(x[None], (count, *x.shape))
            first = lambda w: dot("esd,edf->esf", xe, w)  # noqa: E731
        mid = expert_mid(params, first, act).astype(dtype)
        out = dot("esf,efd->esd", mid, params["down"])          # f32
        y = jnp.sum(jnp.where(chose[:, :, None], out * w[:, :, None], 0.0),
                    axis=0)
    return y, choices, jnp.zeros((), jnp.int32)


def moe_share_apply(params, x, *, top_k: int, experts_held,
                    routed_scale: float = 1.0, n_group: int = 1,
                    topk_group: int = 1, rows: int | None = None,
                    live=None, activation: str = "silu"):
    """The part of a sigmoid-routed expert layer that the experts held here
    give. x: [N, H] -> (y [N, H] float32, choices int32 [held], dropped
    int32 scalar).

    `experts_held = (first, count)` names the experts whose weights
    `params` holds. The expert's form is read from them: `gate`, `up`
    [count, H, F] and `down` [count, F, H] are a gated MLP, `down(act(gate
    x) * up x)`; without `gate` it is ungated, `down(act(up x))`;
    `activation` names `act` (`ACTIVATIONS`: `silu`, `relu2`). Where
    `params` holds `latent_in` [H, R] and `latent_out` [R, H] the experts
    work in a latent space of width R: the rows go down through `latent_in`
    once before the products (the router still scores the full width), and
    the weighted sum over the held experts comes up through `latent_out`
    once after. That is linear, so the shares of all chips still add up to
    the whole layer.

    The router scores every token over ALL experts (`router` [H, E], float32
    sigmoid), takes the `top_k` largest and weighs each chosen expert by
    `routed_scale * s_e / sum of the chosen s`. Where `params` holds a
    `bias` [E] (a buffer, not trained by the loss) the choice is made on
    `s + bias` and the weights stay the unbiased `s`; with `n_group > 1`
    it is made inside each token's best `topk_group` groups
    (`_keep_groups`, their marks from `s + bias` too). One group and no
    bias is the plain top-k over all experts. `live` [N] bool names the
    rows of x that carry a token: the pairs of the others (a decode batch's
    idle slots) are neither counted nor added to any row. `choices[e]`
    counts the (token, choice) pairs routed to held expert `e`.

    One routing, then the expert's products in one of two forms, chosen
    from the call's shapes (`moe_share_dense`):

    - *grouped* (`_grouped_products`): the held pairs are sorted by expert
      into one buffer of `rows` rows, pass through three
      `jax.lax.ragged_dot`s and are added, weighted, into their tokens'
      rows. `rows` is `moe_share_rows` where the caller names none: twice
      the even share, as a training step's tens of thousands of tokens
      want it; `dropped` counts the held pairs that did not fit and were
      left out, and a caller that wants the layer dropless holds that
      count to nought.
    - *dense* (`_dense_products`), where `rows` is the worst case (every
      choice of every row, `N * top_k`, as a token step asks for so that
      nothing is ever dropped) and `N <= DENSE_ROWS`: every row goes
      through every held expert in one batched product a matrix, and a
      dense `[count, N]` matrix of the router's weights picks what each
      row keeps. `dropped` is a constant 0. The grouped product's time
      follows its buffer's length, not its fill, and the worst case is
      `E / count` times the even fill.

    Both give a chosen (row, expert) pair the same three products of the
    same operands with float32 accumulation and float32 weighing; the
    order of the float32 sum over a row's experts differs. What the absent
    experts would add is left out: on one chip the layer runs without its
    exchange, and the shares of all chips add up to the whole layer."""
    n, _ = x.shape
    _, count = experts_held
    if rows is None:
        rows = moe_share_rows(n, top_k, params["router"].shape[1], count)
    with jax.named_scope("moe.route"):
        weight, group = _share_route(params, x, top_k, experts_held,
                                     routed_scale, n_group, topk_group, live)
    act = ACTIVATIONS[activation]
    latent = "latent_in" in params
    if latent:
        with jax.named_scope("moe.latent"):
            x = jnp.matmul(x, params["latent_in"].astype(x.dtype),
                           preferred_element_type=jnp.float32).astype(x.dtype)
    if moe_share_dense(n, top_k, rows):
        y, choices, dropped = _dense_products(params, x, weight, group,
                                              count, top_k, act)
    else:
        y, choices, dropped = _grouped_products(params, x, weight, group,
                                                count, top_k, rows, act)
    if latent:
        with jax.named_scope("moe.latent"):
            y = jnp.matmul(y.astype(x.dtype),
                           params["latent_out"].astype(x.dtype),
                           preferred_element_type=jnp.float32)
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
