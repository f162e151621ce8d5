"""Plain reference for the DeepSeek-V3 block (`model_type: deepseek_v3`): the
forward pass in straightforward `jax.numpy`, float32 with every matmul at
`Precision.HIGHEST`, no kernel, no cache, nothing absorbed, one request at a
time, and nothing imported from the program.

The equations, for a pre-norm block with RMSNorm (eps `rms_eps`, no biases):

    h = x + Attn(RMSNorm(x));  y = h + MLP_l(RMSNorm(h))
    logits = RMSNorm(y_L) W_head                       (untied)

- Latent attention, every layer, u the normed input. Queries: c_q =
  RMSNorm(u W_qa) (`q_rank`), q = c_q W_qb, H heads of [q_nope `nope` | q_rope
  `rope`]. Keys and values: [c_kv | k_r] = u W_kva (`kv_rank` + `rope`),
  c = RMSNorm(c_kv); k_r is rotated and is ONE key all heads share; per head
  [k_nope_h `nope` | v_h `v`] = c W_kvb,h: **every position is expanded into
  heads here** (the program never does). Score of head h at (i, j <= i):
  (q_nope_h,i . k_nope_h,j + rot(q_rope_h,i) . rot(k_r,j)) x s, softmax over j,
  o_h = sum_j p v_h,j, out = concat_h(o_h) W_o.
- Rotary: pairs (x[2t], x[2t+1]) of the `rope` dimensions turn by pos x f_t,
  as the published inference code lays them. YaRN: f_t blends the frequency
  over `factor` into theta^(-2t/rope) by a linear ramp over t between the
  dimensions that turn `beta_fast` and `beta_slow` times inside
  `original_max_position_embeddings`. `mscale` = `mscale_all_dim`, so cos and
  sin carry no factor and s = (nope + rope)^-0.5 x (0.1 mscale ln factor + 1)^2.
- Dense MLP (the leading layers): (silu(u W_gate) * (u W_up)) W_down.
- Sparse MLP: sigma = sigmoid(u W_r) over all `num_experts`, float32. The
  choice is made on sigma + b (b the `noaux_tc` bias, a buffer): the experts
  lie in `n_group` groups of equal size, a group's mark is the sum of its two
  largest sigma + b, the best `topk_group` groups stay, and among their experts
  the `top_k` largest sigma + b are chosen. The weights are the UNBIASED scores:
  w_e = routed_scale x sigma_e / sum_chosen sigma. y = sum over the chosen
  experts **that are held here** of w_e E_e(u), plus one shared expert S(u),
  unweighted; E and S gated silu MLPs. `experts_held = [first, count]` is the
  share the program holds; what the absent experts would add is left out.
- The multi-token-prediction module is not part of this configuration.

**Layout of the parameters** (a matrix is [in, out]; the values are
bfloat16-rounded and kept as bfloat16, and every use widens them to float32:
4.57B parameters in float32 would not fit the chip). `draw_params` makes them
from a seed, and the driver renames them into the program's layout and loads
them there: the program is fed the reference's weights, not the reverse.

    {"embed_tokens": [V, d], "lm_head": [d, V], "norm": [d],
     "layers": [{"input_layernorm": [d], "post_attention_layernorm": [d],
                 "q_a_proj": [d, q_rank], "q_a_layernorm": [q_rank],
                 "q_b_proj": [q_rank, H*(nope+rope)],
                 "kv_a_proj_with_mqa": [d, kv_rank+rope],
                 "kv_a_layernorm": [kv_rank],
                 "kv_b_proj": [kv_rank, H*(nope+v)], "o_proj": [H*v, d],
                 and either "mlp": {"gate_proj", "up_proj", "down_proj"}
                 or "gate": [d, E], "e_score_correction_bias": [E],
                    "experts": {"gate_proj": [held, d, f], "up_proj": [held, d, f],
                                "down_proj": [held, f, d]},
                    "shared_experts": {"gate_proj", "up_proj", "down_proj"}}]}

`sizes` is a plain dict: hidden, heads, q_rank, kv_rank, nope, rope, v,
dense_ffn, expert_ffn, shared_ffn, num_experts, n_group, topk_group, top_k,
routed_scale, experts_held, rms_eps, rope_theta, yarn {factor,
original_max_position_embeddings, beta_fast, beta_slow, mscale,
mscale_all_dim}, layers ["dense" | "sparse", ...], vocab and weights
{matrix_std, embedding_std, router_bias_std}.

`mode` lowers the precision: "f32" is the reference; "bf16" keeps activations
in bfloat16 and multiplies in one bfloat16 pass with float32 accumulation
(norms, rotary, softmax and the router stay float32): what the configuration
states, a witness; "fp8" also rounds both operands of every such product to
float8_e4m3 with one scale a tensor: the control that has to fail."""

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

@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    """normal(0, std), rounded once to bfloat16."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def draw_params(seed, sizes):
    """The run's weights from its seed, in the layout above: every matrix
    normal(0, matrix_std), the embedding's rows normal(0, embedding_std), the
    router's bias normal(0, router_bias_std), the norms' gains 1; each tensor
    from its own fold of the seed's key, bfloat16."""
    s, w = sizes, sizes["weights"]
    d, H = s["hidden"], s["heads"]
    count = iter(range(10 ** 6))
    root = jax.random.key(int(seed) % (2 ** 31 - 1))
    draw = lambda shape, std=w["matrix_std"]: _normal(  # noqa: E731
        jax.random.fold_in(root, next(count)), tuple(shape), float(std))
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    mlp = lambda f, lead=(): {  # noqa: E731
        "gate_proj": draw((*lead, d, f)), "up_proj": draw((*lead, d, f)),
        "down_proj": draw((*lead, f, d))}
    params = {"embed_tokens": draw((s["vocab"], d), w["embedding_std"]),
              "lm_head": draw((d, s["vocab"])), "norm": ones(d), "layers": []}
    for kind in s["layers"]:
        lp = {"input_layernorm": ones(d),
              "post_attention_layernorm": ones(d),
              "q_a_proj": draw((d, s["q_rank"])),
              "q_a_layernorm": ones(s["q_rank"]),
              "q_b_proj": draw((s["q_rank"], H * (s["nope"] + s["rope"]))),
              "kv_a_proj_with_mqa": draw((d, s["kv_rank"] + s["rope"])),
              "kv_a_layernorm": ones(s["kv_rank"]),
              "kv_b_proj": draw((s["kv_rank"], H * (s["nope"] + s["v"]))),
              "o_proj": draw((H * s["v"], d))}
        if kind == "dense":
            lp["mlp"] = mlp(s["dense_ffn"])
        else:
            lp["gate"] = draw((d, s["num_experts"]))
            lp["e_score_correction_bias"] = draw((s["num_experts"],),
                                                 w["router_bias_std"])
            lp["experts"] = mlp(s["expert_ffn"], (s["experts_held"][1],))
            lp["shared_experts"] = mlp(s["shared_ffn"])
        params["layers"].append(lp)
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


def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def yarn_frequencies(sizes):
    """float64 [rope / 2]: the turn of pair t a position."""
    y, rot, base = sizes["yarn"], sizes["rope"], float(sizes["rope_theta"])
    plain = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    dim_of = lambda turns: rot * math.log(  # noqa: E731
        y["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(base))
    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return plain / y["factor"] * ramp + plain * (1 - ramp)


def softmax_scale(sizes):
    y = sizes["yarn"]
    m = 0.1 * y["mscale"] * math.log(y["factor"]) + 1.0
    return (sizes["nope"] + sizes["rope"]) ** -0.5 * m * m


def rotate(x, angle):
    """x [T, ..., rope] by angle [T, rope / 2], neighbouring pairs."""
    x = x.astype(jnp.float32)
    shape = x.shape
    x = x.reshape(*shape[:-1], -1, 2)
    ang = angle.reshape(shape[0], *([1] * (len(shape) - 2)), -1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([x[..., 0] * c - x[..., 1] * s,
                      x[..., 0] * s + x[..., 1] * c], -1).reshape(shape)


def attention(lp, u, sizes, angle, mode):
    """u [T, d] normed -> [T, d]; scores a block of queries at a time."""
    s, act = sizes, _act(mode)
    T, H = u.shape[0], s["heads"]
    c_q = rms_norm(_mm("td,dr->tr", u, lp["q_a_proj"], mode),
                   lp["q_a_layernorm"], s["rms_eps"]).astype(act)
    q = _mm("tr,rk->tk", c_q, lp["q_b_proj"], mode).reshape(
        T, H, s["nope"] + s["rope"])
    kv = _mm("td,dr->tr", u, lp["kv_a_proj_with_mqa"], mode)
    c = rms_norm(kv[:, :s["kv_rank"]], lp["kv_a_layernorm"],
                 s["rms_eps"]).astype(act)
    k_r = rotate(kv[:, s["kv_rank"]:], angle).astype(act)           # [T, rope]
    q_n = q[..., :s["nope"]]
    q_r = rotate(q[..., s["nope"]:], angle).astype(act)
    heads = _mm("tr,rk->tk", c, lp["kv_b_proj"], mode).reshape(
        T, H, s["nope"] + s["v"])
    k_n, v = heads[..., :s["nope"]], heads[..., s["nope"]:]
    scale = softmax_scale(s)
    blocks = -(-T // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - T
    padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731

    def block(args):
        qn, qr, i0 = args
        sc = (_mm("qhd,khd->hqk", qn, k_n, mode).astype(jnp.float32)
              + _mm("qhd,kd->hqk", qr, k_r, mode).astype(jnp.float32)) * scale
        i = i0 + jnp.arange(QUERY_BLOCK)
        seen = jnp.arange(T)[None, :] <= i[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", p.astype(act), v, mode)

    split = lambda a: padded(a).reshape(blocks, QUERY_BLOCK, *a.shape[1:])  # noqa: E731
    o = jax.lax.map(block, (split(q_n), split(q_r),
                            jnp.arange(blocks) * QUERY_BLOCK))
    o = o.reshape(blocks * QUERY_BLOCK, H * s["v"])[:T]
    return _mm("tk,kd->td", o, lp["o_proj"], mode)


def gated_mlp(p, u, mode):
    g = _mm("td,df->tf", u, p["gate_proj"], mode)
    up = _mm("td,df->tf", u, p["up_proj"], mode)
    return _mm("tf,fd->td", (jax.nn.silu(g) * up).astype(_act(mode)),
               p["down_proj"], mode)


def route(lp, u, sizes):
    """[T, E] float32: w_e where expert e is chosen for the token, else 0.
    Float32 at the highest precision in every mode."""
    s = sizes
    T, E, G = u.shape[0], s["num_experts"], s["n_group"]
    sigma = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", u.astype(jnp.float32), lp["gate"].astype(jnp.float32),
        precision=HI))
    biased = sigma + lp["e_score_correction_bias"].astype(jnp.float32)
    by_group = biased.reshape(T, G, E // G)
    mark = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)   # [T, G]
    stays = jnp.argsort(-mark, axis=-1)[:, :s["topk_group"]]
    group_ok = jnp.zeros((T, G), bool).at[
        jnp.arange(T)[:, None], stays].set(True)
    allowed = jnp.repeat(group_ok, E // G, axis=1)
    chosen = jnp.argsort(-jnp.where(allowed, biased, -jnp.inf),
                         axis=-1)[:, :s["top_k"]]
    picked = jnp.zeros((T, E), bool).at[
        jnp.arange(T)[:, None], chosen].set(True)
    total = jnp.sum(jnp.where(picked, sigma, 0.0), axis=-1, keepdims=True)
    return jnp.where(picked, s["routed_scale"] * sigma / total, 0.0)


def sparse_mlp(lp, u, sizes, mode, shared=True):
    """The held experts' part of the layer, and the shared expert."""
    first, count = sizes["experts_held"]
    w = route(lp, u, sizes)[:, first:first + count]                 # [T, held]

    def one(y, args):
        p, w_e = args
        return y + w_e[:, None] * gated_mlp(p, u, mode).astype(
            jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                        (lp["experts"], w.T))
    if shared:
        y = y + gated_mlp(lp["shared_experts"], u, mode).astype(jnp.float32)
    return y


def layer(lp, x, sizes, angle, mode):
    """x [T, d] -> [T, d], one layer."""
    act, eps = _act(mode), sizes["rms_eps"]
    u = rms_norm(x, lp["input_layernorm"], eps).astype(act)
    h = (x + attention(lp, u, sizes, angle, mode).astype(act)).astype(act)
    u = rms_norm(h, lp["post_attention_layernorm"], eps).astype(act)
    out = (gated_mlp(lp["mlp"], u, mode) if "mlp" in lp
           else sparse_mlp(lp, u, sizes, mode))
    return (h + out.astype(act)).astype(act)


@functools.lru_cache(maxsize=None)
def _compiled(sizes_json, mode):
    """One layer and the head as launches of their own, compiled once for
    each (sizes, mode); a jit's cache then keys on the shapes."""
    sizes = json.loads(sizes_json)

    def head(norm, w, x):
        x = rms_norm(x, norm, sizes["rms_eps"]).astype(_act(mode))
        return _mm("td,dv->tv", x, w, mode).astype(jnp.float32)

    return (jax.jit(lambda lp, x, angle: layer(lp, x, sizes, angle, mode)),
            jax.jit(head))


def forward_logits(params, sizes, tokens, mode="f32"):
    """tokens [T] -> float32 logits [T, V] of the next token at every
    position: one sequence, every layer a launch of its own."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    angle = jnp.asarray(np.arange(T, dtype=np.float64)[:, None]
                        * yarn_frequencies(sizes)[None, :], jnp.float32)
    x = params["embed_tokens"][tokens].astype(_act(mode))
    one_layer, head = _compiled(json.dumps(
        {k: v for k, v in sizes.items() if k != "weights"}, sort_keys=True),
        mode)
    for lp in params["layers"]:
        x = one_layer(lp, x, angle)
    return head(params["norm"], params["lm_head"], x)


def decoder_logits(params, sizes, seqs, lengths, mode="f32"):
    """seqs [N, T] (row j holds `lengths[j]` tokens, anything behind them)
    -> float32 logits [N, T, V] on the host, nought behind a row's length. A
    row at a time, each cut to whole blocks of queries so that the rows share
    a few compiled shapes."""
    seqs = np.asarray(seqs)
    out = np.zeros((*seqs.shape, sizes["vocab"]), np.float32)
    for j, (row, n) in enumerate(zip(seqs, lengths)):
        t = min(-(-int(n) // QUERY_BLOCK) * QUERY_BLOCK, seqs.shape[1])
        out[j, :t] = np.asarray(forward_logits(params, sizes, row[:t], mode))
    return out
