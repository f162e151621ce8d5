"""Plain reference for the Nemotron-H hybrid block (`model_type: nemotron_h`,
pattern letters `M`, `*`, `E`): the forward pass in straightforward
`jax.numpy`, float32 with every matmul at `Precision.HIGHEST`, no kernel, no
cache, no paging, no batching of requests, the Mamba layer a scan over
positions, and nothing imported from the program.

The equations. Every layer is ONE mixer on a pre-norm residual stream,

    h <- h + mixer(RMSNorm(h));      logits = RMSNorm(h_L) W_head   (untied)

RMSNorm with eps `norm_eps`, no biases but the convolution's.

- **`M`, Mamba-2** (`heads` x `head_dim` = inner; `groups`; `state`; `conv`
  taps). [z | xBC | dt] = u W_in, widths inner | inner + 2 groups state |
  heads. xBC_t <- silu(b + sum_{j<conv} w_j * xBC_{t-conv+1+j}), depthwise,
  causal, zeros before the sequence. xBC = [x (heads, head_dim) | B (groups,
  state) | C (groups, state)]; head h reads group h // (heads / groups).
  dt = softplus(dt + dt_bias), A = -exp(A_log), a head each. State S [heads,
  head_dim, state], zero before the sequence:
  S_t = exp(dt A) S_{t-1} + dt x_t (outer) B_t;  y_t = S_t C_t + D x_t.
  y <- y * silu(z), normalised (RMS) inside each of the `groups` groups of
  inner / groups, times one gain of inner; out = y W_out.
- **`*`, attention**: q = u W_q (`attn_heads` of `attn_head_dim`), k, v = u W_k,
  u W_v (`kv_heads`; query head h reads K/V head h // (attn_heads /
  kv_heads)); causal softmax of q.k x attn_head_dim^-0.5; **no rotary
  embedding** (the configuration's `assumed` says why); out = o W_o.
- **`E`, latent experts**: s = sigmoid(u W_r) over all `num_experts`, float32;
  the `top_k` largest of s + b are chosen (b the selection bias, a buffer;
  one group: no group limit); w_e = routed_scale x s_e / sum of the chosen s.
  v = u W_down (hidden -> latent);
  r = sum over the chosen experts **that are held here** of
  w_e relu(v W1_e)^2 W2_e (latent -> expert_ffn -> latent, no gate);
  out = r W_up + relu(u W1_s)^2 W2_s (the shared expert on the full width,
  unweighted). `experts_held = [first, count]` is the share the program
  holds; what the absent experts would add is left out (linear in r, so the
  shares of all chips add up through W_up).
- The multi-token-prediction module is not part of this configuration.

**Layout of the parameters** (a matrix is [in, out]; every leaf is rounded
once to bfloat16 and kept so, and every use widens it to float32).
`draw_params` makes them from a seed, and the driver renames them into the
program's layout and loads them there.

    {"embed_tokens": [V, d], "lm_head": [d, V], "norm_f": [d],
     "layers": [{"norm": [d], and one of
        M: "in_proj": [d, 2 inner + 2 groups state + heads],
           "conv1d_weight": [conv, inner + 2 groups state], "conv1d_bias",
           "dt_bias": [heads], "A_log": [heads], "D": [heads],
           "mixer_norm": [inner], "out_proj": [inner, d]
        *: "q_proj": [d, attn_heads attn_head_dim], "k_proj", "v_proj":
           [d, kv_heads attn_head_dim], "o_proj"
        E: "gate": [d, E], "e_score_correction_bias": [E],
           "fc1_latent_proj": [d, latent], "fc2_latent_proj": [latent, d],
           "experts": {"up_proj": [held, latent, f], "down_proj": [held, f,
           latent]}, "shared_experts": {"up_proj": [d, fs], "down_proj"}}]}

`sizes` is a plain dict: hidden, heads, head_dim, groups, state, conv,
attn_heads, kv_heads, attn_head_dim, latent, expert_ffn, shared_ffn,
num_experts, top_k, routed_scale, experts_held, eps, time_step [min, max,
floor], layers ["mamba" | "attention" | "sparse", ...], vocab and weights
{matrix_std, embedding_std, router_bias_std}.

`mode` lowers the precision: "f32" is the reference; "bf16" keeps activations
in bfloat16 and multiplies in one bfloat16 pass with float32 accumulation
(norms, softmax, the router, the convolution, the scan and its state stay
float32): what the configuration states, a witness; "fp8" also rounds both
operands of every such product to float8_e4m3 with one scale a tensor: the
control that has to fail; "bf16_state" is "bf16" with the Mamba state rounded
to bfloat16 after every position: the second control."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


# -- weights -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, how, scale):
    if how == "normal":
        a = jax.random.normal(key, shape, jnp.float32) * scale
    else:                       # uniform in [-scale, scale]
        a = jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    return a.astype(jnp.bfloat16)


def draw_params(seed, sizes):
    """The run's weights from its seed, in the layout above: every matrix
    normal(0, matrix_std), the embedding's rows normal(0, embedding_std), the
    selection bias normal(0, router_bias_std); the convolution's taps and
    bias uniform in +-conv^-0.5 (the published modelling code's own
    initialiser for a depthwise convolution: at 0.02 B and C would be some
    0.03 and the state's part of y a thousandth of D x, so that no number
    would tell a wrong state from a right one); A_log = ln U(1, 16); dt_bias
    the inverse softplus of exp(U(ln min, ln max)) floored; D and the gains
    1. Each tensor from its own fold of the seed's key, bfloat16."""
    s, w = sizes, sizes["weights"]
    d = s["hidden"]
    inner = s["heads"] * s["head_dim"]
    width = inner + 2 * s["groups"] * s["state"]
    count = iter(range(10 ** 6))
    root = jax.random.key(int(seed) % (2 ** 31 - 1))
    fold = lambda: jax.random.fold_in(root, next(count))  # noqa: E731
    draw = lambda shape, std=w["matrix_std"]: _draw(  # noqa: E731
        fold(), tuple(shape), "normal", float(std))
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    lo, hi, floor = s["time_step"]

    def mamba():
        taps = s["conv"] ** -0.5
        dt = jnp.maximum(jnp.exp(
            jax.random.uniform(fold(), (s["heads"],), jnp.float32,
                               math.log(lo), math.log(hi))), floor)
        return {
            "in_proj": draw((d, inner + width + s["heads"])),
            "conv1d_weight": _draw(fold(), (s["conv"], width), "uniform",
                                   taps),
            "conv1d_bias": _draw(fold(), (width,), "uniform", taps),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16),
            "A_log": jnp.log(jax.random.uniform(
                fold(), (s["heads"],), jnp.float32, 1.0, 16.0)).astype(
                    jnp.bfloat16),
            "D": ones(s["heads"]), "mixer_norm": ones(inner),
            "out_proj": draw((inner, d))}

    def attention():
        q, kv = s["attn_heads"] * s["attn_head_dim"], \
            s["kv_heads"] * s["attn_head_dim"]
        return {"q_proj": draw((d, q)), "k_proj": draw((d, kv)),
                "v_proj": draw((d, kv)), "o_proj": draw((q, d))}

    def sparse():
        held, f, r = s["experts_held"][1], s["expert_ffn"], s["latent"]
        return {
            "gate": draw((d, s["num_experts"])),
            "e_score_correction_bias": draw((s["num_experts"],),
                                            w["router_bias_std"]),
            "fc1_latent_proj": draw((d, r)), "fc2_latent_proj": draw((r, d)),
            "experts": {"up_proj": draw((held, r, f)),
                        "down_proj": draw((held, f, r))},
            "shared_experts": {"up_proj": draw((d, s["shared_ffn"])),
                               "down_proj": draw((s["shared_ffn"], d))}}

    make = {"mamba": mamba, "attention": attention, "sparse": sparse}
    params = {"embed_tokens": draw((s["vocab"], d), w["embedding_std"]),
              "lm_head": draw((d, s["vocab"])), "norm_f": ones(d),
              "layers": []}
    for kind in s["layers"]:
        params["layers"].append(dict(make[kind](), norm=ones(d)))
    return params


def count_params(params):
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))


# -- the block -----------------------------------------------------------------

def _q8(x):
    """Round to float8_e4m3 with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _mm(eq, a, b, mode):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "f32":
        return jnp.einsum(eq, a, b, precision=HI)
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    out = jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)


def _act(mode):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, g, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(g)


def relu2(a):
    return jnp.square(jax.nn.relu(a))


def mamba(lp, u, sizes, mode):
    """u [T, d] normed -> [T, d]: one sequence from a zero state."""
    s, act = sizes, _act(mode)
    T, H, P, G, N = u.shape[0], s["heads"], s["head_dim"], s["groups"], \
        s["state"]
    inner, K = H * P, s["conv"]
    zxd = _f32(_mm("td,dk->tk", u, lp["in_proj"], mode))
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:-H], zxd[:, -H:]
    # causal depthwise convolution over positions, zeros before the first
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    w = _f32(lp["conv1d_weight"])
    conv = sum(w[j][None, :] * padded[j:j + T] for j in range(K)) \
        + _f32(lp["conv1d_bias"])
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(T, H, P)
    b = jnp.repeat(xbc[:, inner:inner + G * N].reshape(T, G, N), H // G, 1)
    c = jnp.repeat(xbc[:, inner + G * N:].reshape(T, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))              # [T, H]
    a = -jnp.exp(_f32(lp["A_log"]))                             # [H]

    def one(state, args):
        x_t, b_t, c_t, dt_t = args
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if mode == "bf16_state":
            state = _f32(state.astype(jnp.bfloat16))
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(one, jnp.zeros((H, P, N), jnp.float32),
                        (x, b, c, dt))
    y = (y + _f32(lp["D"])[None, :, None] * x).reshape(T, inner)
    y = (y * jax.nn.silu(z)).reshape(T, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + s["eps"])
    y = (y.reshape(T, inner) * _f32(lp["mixer_norm"])).astype(act)
    return _mm("tk,kd->td", y, lp["out_proj"], mode)


def attention(lp, u, sizes, mode):
    """u [T, d] normed -> [T, d]; scores a block of queries at a time."""
    s, act = sizes, _act(mode)
    T, H, KV, D = u.shape[0], s["attn_heads"], s["kv_heads"], \
        s["attn_head_dim"]
    q = _mm("td,dk->tk", u, lp["q_proj"], mode).reshape(T, KV, H // KV, D)
    k = _mm("td,dk->tk", u, lp["k_proj"], mode).reshape(T, KV, D)
    v = _mm("td,dk->tk", u, lp["v_proj"], mode).reshape(T, KV, D)
    blocks = -(-T // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - T
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, KV, H // KV, D)

    def block(args):
        qb, i0 = args
        sc = _f32(_mm("qkgd,skd->kgqs", qb, k, mode)) * D ** -0.5
        i = i0 + jnp.arange(QUERY_BLOCK)
        seen = jnp.arange(T)[None, :] <= i[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return _mm("kgqs,skd->qkgd", p.astype(act), v, mode)

    o = jax.lax.map(block, (q, jnp.arange(blocks) * QUERY_BLOCK))
    o = o.reshape(blocks * QUERY_BLOCK, H * D)[:T]
    return _mm("tk,kd->td", o, lp["o_proj"], mode)


def route(lp, u, sizes):
    """[T, E] float32: w_e where expert e is chosen for the token, else 0.
    Float32 at the highest precision in every mode."""
    s = sizes
    T, E = u.shape[0], s["num_experts"]
    sigma = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", _f32(u), _f32(lp["gate"]), precision=HI))
    biased = sigma + _f32(lp["e_score_correction_bias"])
    chosen = jnp.argsort(-biased, axis=-1)[:, :s["top_k"]]
    picked = jnp.zeros((T, E), bool).at[
        jnp.arange(T)[:, None], chosen].set(True)
    total = jnp.sum(jnp.where(picked, sigma, 0.0), axis=-1, keepdims=True)
    return jnp.where(picked, s["routed_scale"] * sigma / total, 0.0)


def plain_mlp(p, u, mode):
    mid = relu2(_f32(_mm("td,df->tf", u, p["up_proj"], mode)))
    return _mm("tf,fd->td", mid.astype(_act(mode)), p["down_proj"], mode)


def sparse(lp, u, sizes, mode, shared=True):
    """The held experts' part of the layer, through the latent space, and the
    shared expert."""
    act = _act(mode)
    first, count = sizes["experts_held"]
    w = route(lp, u, sizes)[:, first:first + count]              # [T, held]
    v = _mm("td,dr->tr", u, lp["fc1_latent_proj"], mode).astype(act)

    def one(r, args):
        p, w_e = args
        return r + w_e[:, None] * _f32(plain_mlp(p, v, mode)), None

    r, _ = jax.lax.scan(one, jnp.zeros(v.shape, jnp.float32),
                        (lp["experts"], w.T))
    y = _f32(_mm("tr,rd->td", r.astype(act), lp["fc2_latent_proj"], mode))
    if shared:
        y = y + _f32(plain_mlp(lp["shared_experts"], u, mode))
    return y


MIXERS = {"mamba": mamba, "attention": attention, "sparse": sparse}


def layer(lp, x, sizes, kind, mode):
    """x [T, d] -> [T, d], one layer."""
    act = _act(mode)
    u = rms_norm(x, lp["norm"], sizes["eps"]).astype(act)
    return (x + MIXERS[kind](lp, u, sizes, mode).astype(act)).astype(act)


@functools.lru_cache(maxsize=None)
def _compiled(sizes_json, mode):
    """A layer of each kind and the head as launches of their own, compiled
    once for each (sizes, mode); a jit's cache then keys on the shapes."""
    sizes = json.loads(sizes_json)

    def head(norm, w, x):
        x = rms_norm(x, norm, sizes["eps"]).astype(_act(mode))
        return _f32(_mm("td,dv->tv", x, w, mode))

    layers = {kind: jax.jit(functools.partial(
        layer, sizes=sizes, kind=kind, mode=mode)) for kind in MIXERS}
    return layers, jax.jit(head)


def forward_logits(params, sizes, tokens, mode="f32"):
    """tokens [T] -> float32 logits [T, V] of the next token at every
    position: one sequence, every layer a launch of its own."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embed_tokens"][tokens].astype(_act(mode))
    layers, head = _compiled(json.dumps(
        {k: v for k, v in sizes.items() if k != "weights"}, sort_keys=True),
        mode)
    for lp, kind in zip(params["layers"], sizes["layers"]):
        x = layers[kind](lp, x)
    return head(params["norm_f"], params["lm_head"], x)


def decoder_logits(params, sizes, seqs, lengths, mode="f32"):
    """seqs [N, T] (row j holds `lengths[j]` tokens, anything behind them)
    -> float32 logits [N, T, V] on the host, nought behind a row's length. A
    row at a time, each cut to whole blocks of queries so that the rows share
    a few compiled shapes (a causal model: what lies behind a position does
    not reach it)."""
    seqs = np.asarray(seqs)
    out = np.zeros((*seqs.shape, sizes["vocab"]), np.float32)
    for j, (row, n) in enumerate(zip(seqs, lengths)):
        t = min(-(-int(n) // QUERY_BLOCK) * QUERY_BLOCK, seqs.shape[1])
        out[j, :t] = np.asarray(forward_logits(params, sizes, row[:t], mode))
    return out
