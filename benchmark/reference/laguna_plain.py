"""Plain reference for the Laguna block (`model_type: laguna`): forward, loss,
gradients and Adam steps in straightforward `jax.numpy`, float32 with every
matmul at `Precision.HIGHEST`, no kernel, no cache, and nothing imported from
the program.

The equations, for a pre-norm block with RMSNorm (no biases anywhere):

    h = x + Attn_l(RMSNorm(x));  y = h + MLP_l(RMSNorm(h))
    logits = RMSNorm(y_L) W_head

- Attention, u the normed input: q = u W_q with H_l heads of `head_dim`, k and
  v with `kv_heads` heads; query head h reads KV head h // (H_l / kv_heads).
  Rotate-half RoPE over the first `partial_rotary_factor * head_dim`
  dimensions of each head, by the layer kind's `rope_parameters` (`yarn`:
  inverse frequencies blended between interpolated and extrapolated by the
  linear ramp over the rotary dimensions, cos and sin times
  `attention_factor`; else plain). Scores q k^T / sqrt(head_dim), causal; a
  sliding layer also masks i - j >= sliding_window. Output gate per head,
  g = sigmoid(u W_g), o_h <- g_h o_h; then W_o.
- Dense MLP: (silu(u W_gate) * (u W_up)) W_down.
- Sparse MLP: s = sigmoid(u W_r) over all `num_experts`; the `top_k` largest
  I; w_e = routed_scale * s_e / sum_{j in I} s_j; the sum over the chosen
  experts **that are held here** of w_e E_e(u), each E_e the gated MLP, plus
  one shared expert, unweighted. `experts_held = [first, count]` is the same
  share the program holds: what the absent experts would add is left out.
- Loss: next token, float32 log-softmax over the held rows of the vocabulary,
  mean over the positions that have a next token (label >= 0).

What the configuration leaves open is settled as its file's `assumed` says
(sigmoid gate from the normed input, sigmoid router normalised over the
chosen, silu, no QK-norm, no gate on the shared expert).

**Layout of the parameters** (a matrix is [in, out]). `draw_params` makes
them from a seed with numpy's generator, and the driver renames them into the
program's layout and loads them there: the program is fed the reference's
weights, not the reverse.

    {"embed_tokens": [V, d], "lm_head": [d, V], "norm": [d],
     "layers": [{"input_layernorm": [d], "post_attention_layernorm": [d],
                 "q_proj": [d, H_l*hd], "k_proj": [d, KV*hd],
                 "v_proj": [d, KV*hd], "g_proj": [d, H_l], "o_proj": [H_l*hd, d],
                 and either "mlp": {"gate_proj", "up_proj", "down_proj"}
                 or "router": [d, E],
                    "experts": {"gate_proj": [held, d, f], "up_proj": [held, d, f],
                                "down_proj": [held, f, d]},
                    "shared_expert": {"gate_proj", "up_proj", "down_proj"}}]}

`sizes` is a plain dict: hidden, head_dim, kv_heads, sliding_window,
num_experts, top_k, routed_scale, experts_held, rms_eps, rope {"full": ...,
"sliding": ...}, layers [{"attention", "heads", "mlp"}] and, for
`draw_params`, vocab, dense_ffn, expert_ffn, shared_ffn and weights
{"matrix_std", "embedding_std"}.

`mode` lowers the precision: "f32" is the reference; "bf16" keeps activations
in bfloat16 and multiplies in one bfloat16 pass with float32 accumulation
(norms, rotary tables, router and loss stay float32): what the configuration
states, a witness; "fp8" also rounds both operands of every matmul to
float8_e4m3 with one scale a tensor: the control that has to fail. `fault`
plants one: "no_window" (a sliding layer attends as a full one),
"drop_expert" (the last held expert of every sparse layer is left out) or
"keep_rows" (only the first half of every batch's rows is trained on).

One row at a time, each layer recomputed going backward, the score matrix in
blocks of queries (`block_q` divides the sequence), Adam's moments on the
host: the parameters, two gradients and one row's float32 activations fit a
16 GB chip."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def leaf_names(tree):
    """{"layers.3.q_proj": leaf, ...}: one naming for both sides."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    name = lambda path: ".".join(  # noqa: E731
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
    return {name(path): leaf for path, leaf in flat}


def draw_params(sizes, seed):
    """The weights of one run, float32 numpy arrays in the layout above:
    every matrix normal(0, matrix_std), the embedding's rows normal(0,
    embedding_std), the norms' gains 1; the same seed gives the same
    weights."""
    rng = np.random.default_rng([int(seed), 11])
    w = sizes["weights"]
    d, hd, v = sizes["hidden"], sizes["head_dim"], sizes["vocab"]
    held = sizes["experts_held"][1]

    def mat(*shape, std=w["matrix_std"]):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def mlp(f, *lead):
        return {"gate_proj": mat(*lead, d, f), "up_proj": mat(*lead, d, f),
                "down_proj": mat(*lead, f, d)}

    layers = []
    for spec in sizes["layers"]:
        lp = {"input_layernorm": np.ones(d, np.float32),
              "post_attention_layernorm": np.ones(d, np.float32),
              "q_proj": mat(d, spec["heads"] * hd),
              "k_proj": mat(d, sizes["kv_heads"] * hd),
              "v_proj": mat(d, sizes["kv_heads"] * hd),
              "g_proj": mat(d, spec["heads"]),
              "o_proj": mat(spec["heads"] * hd, d)}
        if spec["mlp"] == "dense":
            lp["mlp"] = mlp(sizes["dense_ffn"])
        else:
            lp["router"] = mat(d, sizes["num_experts"])
            lp["experts"] = mlp(sizes["expert_ffn"], held)
            lp["shared_expert"] = mlp(sizes["shared_ffn"])
        layers.append(lp)
    return {"embed_tokens": mat(v, d, std=w["embedding_std"]),
            "lm_head": mat(d, v), "norm": np.ones(d, np.float32),
            "layers": layers}


# -- precision -----------------------------------------------------------------

def _q8(x):
    """Round to float8_e4m3 with one scale for the tensor; the gradient passes
    straight through, as an fp8 training recipe has it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, mode):
    if mode == "f32":
        return jnp.einsum(eq, a, b, precision=HI)
    if mode == "fp8":
        a, b = _q8(a.astype(jnp.float32)), _q8(b.astype(jnp.float32))
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _act(mode):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


# -- the pieces ----------------------------------------------------------------

def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope_tables(rope, head_dim, seq):
    """(cos, sin) float32 [seq, rot/2], rot the rotary dimensions."""
    rot = int(rope.get("partial_rotary_factor", 1) * head_dim)
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor = rope["factor"]
        orig = rope["original_max_position_embeddings"]

        def dim_of(turns):   # the dimension that turns so often in `orig`
            return rot * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def rotate(x, cos, sin):
    """x [T, H, D] float32; rotate-half over the first 2*cos.shape[1]."""
    half = cos.shape[1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def attention(q, k, v, window, mode, block_q):
    """q [T, H, D], k, v [T, KV, D] -> [T, H, D] float32. A block of queries
    at a time, the mask applied to the scores: against every key on a full
    layer, against the `window - 1 + block_q` keys a block can reach on a
    sliding one (the keys before position 0 are padding, masked as well)."""
    t, h, d = q.shape
    kv = k.shape[1]
    n = t // block_q
    qb = q.reshape(n, block_q, kv, h // kv, d)
    if window is not None:
        pad = ((window, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        reach = window - 1 + block_q

    @jax.checkpoint
    def block(args):
        q_, q0 = args
        i = q0 + jnp.arange(block_q)[:, None]
        if window is None:
            k_, v_, j = k, v, jnp.arange(t)[None, :]
            seen = j <= i
        else:
            # key position j lies at row j + window of the padded arrays
            k_, v_ = (jax.lax.dynamic_slice_in_dim(a, q0 + 1, reach)
                      for a in (k, v))
            j = q0 - window + 1 + jnp.arange(reach)[None, :]
            seen = (j >= 0) & (j <= i) & (i - j < window)
        s = _mm("qkgd,skd->kgqs", q_, k_, mode).astype(jnp.float32) \
            / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("kgqs,skd->qkgd", p.astype(_act(mode)), v_, mode)

    out = jax.lax.map(block, (qb, jnp.arange(n) * block_q))
    return out.astype(jnp.float32).reshape(t, h, d)


def gated_mlp(p, u, mode):
    a = _act(mode)
    mid = jax.nn.silu(_mm("td,df->tf", u, p["gate_proj"], mode)) \
        * _mm("td,df->tf", u, p["up_proj"], mode)
    return _mm("tf,fd->td", mid.astype(a), p["down_proj"], mode)


def route(lp, u, sizes):
    """(indices [T, k], weights [T, k]) of the chosen experts: float32
    whatever the mode."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", u.astype(jnp.float32),
                                  lp["router"], precision=HI))
    top_s, top_i = jax.lax.top_k(s, sizes["top_k"])
    return top_i, sizes["routed_scale"] * top_s / jnp.sum(
        top_s, -1, keepdims=True)


def sparse_mlp(lp, u, sizes, mode, fault=None):
    """-> (routed part of the held experts + shared expert [T, d], choices
    int32 [held])."""
    first, count = sizes["experts_held"]
    top_i, weight = route(lp, u, sizes)
    ids = first + jnp.arange(count)
    # [T, held]: the weight of each held expert on each token, 0 if not chosen
    w = jnp.sum(jnp.where(top_i[:, :, None] == ids[None, None, :],
                          weight[:, :, None], 0.0), axis=1)
    choices = jnp.sum(top_i[:, :, None] == ids[None, None, :], axis=(0, 1),
                      dtype=jnp.int32)
    if fault == "drop_expert":
        w = w.at[:, count - 1].set(0.0)

    @jax.checkpoint
    def one(acc, xs):
        ep, w_e = xs
        return acc + w_e[:, None] * gated_mlp(ep, u, mode), None

    routed, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                             (lp["experts"], w.T))
    return routed + gated_mlp(lp["shared_expert"], u, mode), choices


def layer(lp, x, spec, sizes, tables, mode, fault=None, block_q=256):
    """x [T, d] -> (y [T, d], choices int32 [held]); activations in the
    mode's dtype."""
    a, t, hd = _act(mode), x.shape[0], sizes["head_dim"]
    u = rms_norm(x, lp["input_layernorm"], sizes["rms_eps"]).astype(a)
    heads = lambda w: _mm("td,dn->tn", u, w, mode).astype(  # noqa: E731
        jnp.float32).reshape(t, -1, hd)
    cos, sin = tables[spec["attention"]]
    q = rotate(heads(lp["q_proj"]), cos, sin).astype(a)
    k = rotate(heads(lp["k_proj"]), cos, sin).astype(a)
    v = heads(lp["v_proj"]).astype(a)
    sliding = spec["attention"] == "sliding" and fault != "no_window"
    o = attention(q, k, v, sizes["sliding_window"] if sliding else None,
                  mode, block_q)
    gate = jax.nn.sigmoid(_mm("td,dh->th", u, lp["g_proj"], mode))
    o = (o * gate[:, :, None]).astype(a).reshape(t, -1)
    h = (x + _mm("tn,nd->td", o, lp["o_proj"], mode)).astype(a)
    u = rms_norm(h, lp["post_attention_layernorm"], sizes["rms_eps"]).astype(a)
    if spec["mlp"] == "dense":
        out = gated_mlp(lp["mlp"], u, mode)
        choices = jnp.zeros((sizes["experts_held"][1],), jnp.int32)
    else:
        out, choices = sparse_mlp(lp, u, sizes, mode, fault)
    return (h + out).astype(a), choices


def row_logits(params, sizes, tokens, mode="f32", fault=None, block_q=256):
    """tokens [T] -> (float32 logits [T, V], choices [sparse layers, held])."""
    tables = {kind: rope_tables(sizes["rope"][kind], sizes["head_dim"],
                                tokens.shape[0])
              for kind in {s["attention"] for s in sizes["layers"]}}
    x = params["embed_tokens"][tokens].astype(_act(mode))
    choices = []
    for lp, spec in zip(params["layers"], sizes["layers"]):
        x, c = jax.checkpoint(functools.partial(
            layer, spec=spec, sizes=sizes, tables=tables, mode=mode,
            fault=fault, block_q=block_q))(lp, x)
        if spec["mlp"] == "sparse":
            choices.append(c)
    x = rms_norm(x, params["norm"], sizes["rms_eps"]).astype(_act(mode))
    return (_mm("td,dv->tv", x, params["lm_head"], mode).astype(jnp.float32),
            jnp.stack(choices))


def row_nll(params, sizes, tokens, labels, mode="f32", fault=None,
            block_q=256):
    """Summed negative log-likelihood of one row's next tokens."""
    lg, choices = row_logits(params, sizes, tokens, mode, fault, block_q)
    lp = jax.nn.log_softmax(lg, axis=-1)
    valid = labels >= 0
    got = jnp.take_along_axis(lp, jnp.where(valid, labels, 0)[:, None],
                              axis=-1)[:, 0]
    return -jnp.sum(jnp.where(valid, got, 0.0)), choices


# -- training ------------------------------------------------------------------

class LmReference:
    """Adam steps of next-token training, a row at a time, the gradient
    summed over the rows; Adam(0.9, 0.999, 1e-8), no decay, the rate
    climbing linearly to `lr` over `warmup_steps` where there are any, its
    moments on the host."""

    def __init__(self, sizes, params, lr, mode="f32", fault=None,
                 block_q=256, warmup_steps=0):
        self.lr, self.warmup_steps, self.fault = lr, warmup_steps, fault
        self.params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        self.p0 = jax.tree_util.tree_map(np.asarray, params)
        self.m = jax.tree_util.tree_map(np.zeros_like, self.p0)
        self.v = jax.tree_util.tree_map(np.zeros_like, self.p0)
        self.t, self.g1 = 0, None

        def row(p, tokens, labels, denom):
            nll, choices = row_nll(p, sizes, tokens, labels, mode, fault,
                                   block_q)
            return nll / denom, choices

        def adam(p, g, m, v, t, lr):
            m = B1 * m + (1 - B1) * g
            v = B2 * v + (1 - B2) * g * g
            tt = t + 1
            p = p - lr * (m / (1 - B1 ** tt)) \
                / (jnp.sqrt(v / (1 - B2 ** tt)) + ADAM_EPS)
            return p, m, v

        self._row = jax.jit(jax.value_and_grad(row, has_aux=True))
        self._adam = jax.jit(adam, donate_argnums=(0,))
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.add, a, b), donate_argnums=(0,))

    def step(self, tokens, labels):
        """One step on the batch [B, T]; returns (loss, {leaf: gradient
        norm}, choices int [sparse layers, held] summed over the rows).
        The first step's gradient stays in `self.g1`, on the host."""
        if self.fault == "keep_rows":
            half = max(len(tokens) // 2, 1)
            tokens, labels = tokens[:half], labels[:half]
        denom = jnp.float32(max(int((np.asarray(labels) >= 0).sum()), 1))
        loss, grads, choices = 0.0, None, 0
        for tok, lab in zip(np.asarray(tokens), np.asarray(labels)):
            (l_, c_), g_ = self._row(self.params, jnp.asarray(tok, jnp.int32),
                                     jnp.asarray(lab, jnp.int32), denom)
            loss += float(l_)
            choices = choices + np.asarray(c_)
            grads = g_ if grads is None else self._add(grads, g_)
            del g_
        norms = tree_norms(grads)
        if self.t == 0:
            self.g1 = jax.device_get(grads)
        t = jnp.int32(self.t)
        lr = self.lr
        if self.warmup_steps:
            lr *= min((self.t + 1) / self.warmup_steps, 1.0)
        lr = jnp.float32(lr)
        flat_p, tree = jax.tree_util.tree_flatten(self.params)
        flat = zip(flat_p, jax.tree_util.tree_leaves(grads),
                   jax.tree_util.tree_leaves(self.m),
                   jax.tree_util.tree_leaves(self.v))
        del grads
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in flat:
            p, m, v = self._adam(p, g, m, v, t, lr)
            new_p.append(p)
            new_m.append(np.asarray(m))
            new_v.append(np.asarray(v))
        self.params, self.m, self.v = (jax.tree_util.tree_unflatten(tree, x)
                                       for x in (new_p, new_m, new_v))
        self.t += 1
        return loss, norms, choices

    def change_norms(self):
        start = leaf_names(self.p0)
        return {k: float(np.linalg.norm((np.asarray(v) - start[k]).ravel()))
                for k, v in leaf_names(self.params).items()}


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def tree_norms(tree):
    return {k: float(v) for k, v in leaf_names(_norms(tree)).items()}
