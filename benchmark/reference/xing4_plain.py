"""Plain reference for the Xing4.0 block (`model_type: xing4_0`): DeepSeek-V3's
sublayers (latent attention, dense MLP, sigmoid-routed experts with a shared
one: `deepseek_v3_plain.py`'s functions, imported as they are) on a residual
path of `n = hc_mult` streams mixed by Sinkhorn-normalised maps: manifold-
constrained hyper-connections (Xie et al., "mHC: Manifold-Constrained
Hyper-Connections", arXiv 2512.24880, on Zhu et al., "Hyper-Connections",
arXiv 2409.19606). Straightforward `jax.numpy`, float32 with every matmul at
`Precision.HIGHEST`, no kernel, no cache, one request at a time, nothing
imported from the program. The residual path, the layer loop and the draws are
written here.

Per position the residual path holds X in R^{n x C} (C the hidden size).
Entry: X_i = embed(token) for every i. Each layer applies the step below
twice, around its attention (F = latent attention behind `input_layernorm`)
and around its MLP (F = the dense MLP in the leading layers, router + held
experts + shared expert in the others, behind `post_attention_layernorm`).
Exit: h = sum_i X_i, then `norm`, then `lm_head`.

    xbar    = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)            R^{nC}, no gain
    [a|b|c] = xbar Phi                           Phi in R^{nC x (n + n + n^2)}
    H_pre   = sigmoid(alpha_pre a + b_pre)                            R^n
    H_post  = 2 sigmoid(alpha_post b + b_post)                        R^n
    M       = exp(clip(alpha_res mat(c) + B_res, clamp_min, clamp_max))  R^{nxn}
    repeat `sinkhorn_iters` times:  M <- M / (column sums + hc_eps)
                                    M <- M / (row sums + hc_eps)
    H_res   = M
    y       = F(norm_g(sum_i H_pre[i] X_i))
    X'_i    = sum_j H_res[i, j] X_j + H_post[i] y

`mat(c)` fills the matrix row by row. What the published config leaves open
(the configuration file's `assumed` says why each): the streams start as n
copies of the embedding and end as their sum; the norm over the flattened
streams has no gain; `hc_eps` stands in both denominators and columns go
first; the clamp is applied before `exp`; the maps are float32 at the highest
matmul precision in every mode, as the router's scores are.

**Layout of the parameters**: `deepseek_v3_plain.py`'s, and in every layer two
groups more, `attn_hc` and `mlp_hc`, each {"phi": [nC, 2n + n^2], "alpha": [3]
(pre, post, res), "bias": [2n + n^2] (b_pre, b_post, B_res row by row)},
float32 (the matrices of F are bfloat16-rounded, as there).

`sizes` is `deepseek_v3_plain.py`'s dict with `streams`, `sinkhorn_iters`,
`hc_eps`, `res_clamp` [min, max], and under `weights` the maps' draws:
`hc_phi_std`, `hc_alpha`, `hc_bias_std`, `hc_res_diagonal`.

`mode` lowers the precision as there: "bf16" keeps the streams and every
activation of F in bfloat16, each mix accumulated in float32 and rounded once
(what the configuration states: a witness); "fp8" also rounds both operands
of F's products to float8_e4m3: the control that has to fail."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import deepseek_v3_plain as v3
from benchmark.reference.deepseek_v3_plain import (  # noqa: F401
    HI, QUERY_BLOCK, count_params)


# -- weights -------------------------------------------------------------------

def draw_params(seed, sizes):
    """The run's weights from its seed. F's tensors as `deepseek_v3_plain`
    draws them (matrices normal(0, matrix_std), embedding rows normal(0,
    embedding_std), selection bias normal(0, router_bias_std), gains 1;
    bfloat16). A sublayer's maps, float32: `phi` normal(0, hc_phi_std); the
    three `alpha` all `hc_alpha`; `b_pre`, `b_post` and `B_res` normal(0,
    hc_bias_std), `B_res` with `hc_res_diagonal` added on its diagonal. Each
    tensor from its own fold of the seed's key."""
    s, w = sizes, sizes["weights"]
    d, H, n = s["hidden"], s["heads"], s["streams"]
    count = iter(range(10 ** 6))
    root = jax.random.key(int(seed) % (2 ** 31 - 1))
    key = lambda: jax.random.fold_in(root, next(count))  # noqa: E731
    draw = lambda shape, std=w["matrix_std"]: v3._normal(  # noqa: E731
        key(), tuple(shape), float(std))
    ones = lambda k: jnp.ones((k,), jnp.bfloat16)  # noqa: E731
    mlp = lambda f, lead=(): {  # noqa: E731
        "gate_proj": draw((*lead, d, f)), "up_proj": draw((*lead, d, f)),
        "down_proj": draw((*lead, f, d))}

    def maps():
        f32 = lambda shape, std: jax.random.normal(  # noqa: E731
            key(), shape, jnp.float32) * std
        bias = f32((2 * n + n * n,), w["hc_bias_std"])
        return {"phi": f32((n * d, 2 * n + n * n), w["hc_phi_std"]),
                "alpha": jnp.full((3,), w["hc_alpha"], jnp.float32),
                "bias": bias.at[2 * n:].add(
                    w["hc_res_diagonal"] * jnp.eye(n).reshape(-1))}

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
              "o_proj": draw((H * s["v"], d)),
              "attn_hc": maps(), "mlp_hc": maps()}
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


# -- the residual path ---------------------------------------------------------

def hyper_maps(hp, X, sizes):
    """X [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]),
    float32 at the highest precision in every mode."""
    s, n = sizes, sizes["streams"]
    x = X.astype(jnp.float32).reshape(X.shape[0], -1)
    xbar = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + s["rms_eps"])
    abc = jnp.einsum("tk,kf->tf", xbar, hp["phi"], precision=HI)
    alpha, bias = hp["alpha"], hp["bias"]
    pre = jax.nn.sigmoid(alpha[0] * abc[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * abc[:, n:2 * n] + bias[n:2 * n])
    logits = (alpha[2] * abc[:, 2 * n:] + bias[2 * n:]).reshape(-1, n, n)
    lo, hi = s["res_clamp"]
    M = jnp.exp(jnp.clip(logits, lo, hi))
    for _ in range(s["sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + s["hc_eps"])
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + s["hc_eps"])
    return pre, post, M


def sublayer(hp, X, sizes, F, mode):
    """One step of the residual path around F (normed input [T, C] -> [T,
    C]): X [T, n, C] -> X' [T, n, C]."""
    act = v3._act(mode)
    pre, post, res = hyper_maps(hp, X, sizes)
    Xf = X.astype(jnp.float32)
    y = F(jnp.einsum("ti,tic->tc", pre, Xf, precision=HI).astype(act))
    out = jnp.einsum("tij,tjc->tic", res, Xf, precision=HI) \
        + post[:, :, None] * y.astype(act).astype(jnp.float32)[:, None, :]
    return out.astype(act)


def layer(lp, X, sizes, angle, mode):
    """X [T, n, C] -> [T, n, C], one layer."""
    act, eps = v3._act(mode), sizes["rms_eps"]
    normed = lambda g, u: v3.rms_norm(u, g, eps).astype(act)  # noqa: E731
    X = sublayer(lp["attn_hc"], X, sizes, lambda u: v3.attention(
        lp, normed(lp["input_layernorm"], u), sizes, angle, mode), mode)
    mlp = (lambda u: v3.gated_mlp(lp["mlp"], u, mode)) if "mlp" in lp \
        else (lambda u: v3.sparse_mlp(lp, u, sizes, mode))
    return sublayer(lp["mlp_hc"], X, sizes, lambda u: mlp(
        normed(lp["post_attention_layernorm"], u)), mode)


@functools.lru_cache(maxsize=None)
def _compiled(sizes_json, mode):
    """One layer and the head as launches of their own, compiled once for
    each (sizes, mode); a jit's cache then keys on the shapes and on the
    layer's kind."""
    sizes = json.loads(sizes_json)

    def head(norm, w, X):
        h = jnp.sum(X.astype(jnp.float32), axis=1)
        x = v3.rms_norm(h, norm, sizes["rms_eps"]).astype(v3._act(mode))
        return v3._mm("td,dv->tv", x, w, mode).astype(jnp.float32)

    return (jax.jit(lambda lp, X, angle: layer(lp, X, sizes, angle, mode)),
            jax.jit(head))


def forward_logits(params, sizes, tokens, mode="f32"):
    """tokens [T] -> float32 logits [T, V] of the next token at every
    position: one sequence, every layer a launch of its own."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T, n = tokens.shape[0], sizes["streams"]
    angle = jnp.asarray(np.arange(T, dtype=np.float64)[:, None]
                        * v3.yarn_frequencies(sizes)[None, :], jnp.float32)
    x = params["embed_tokens"][tokens].astype(v3._act(mode))
    X = jnp.broadcast_to(x[:, None, :], (T, n, x.shape[-1]))
    one_layer, head = _compiled(json.dumps(
        {k: v for k, v in sizes.items() if k != "weights"}, sort_keys=True),
        mode)
    for lp in params["layers"]:
        X = one_layer(lp, X, angle)
    return head(params["norm"], params["lm_head"], X)


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
