"""A causal language model whose block is described by data, TPU-first.

One pre-norm block, `h = x + Attn_l(RMSNorm(x))`, `y = h + MLP_l(RMSNorm(h))`,
or, where the description gives a position `streams` > 1 residual vectors,
each sublayer reading a learned combination of them and writing back through
a doubly stochastic mix (`stream_maps`, `stream_read`, `stream_write`: the
residual path is part of the description, and the plain sum is its one-stream
case),
where each layer says for itself which attention it has (`full` or
`sliding`: causal, a sliding layer also masks `i - j >= sliding_window`;
`latent`: causal, queries and keys and values through low-rank latents, one
rotary key shared by all heads; `mamba`: a Mamba-2 selective state-space
mixer, a convolution tail and a float32 state a sequence in place of keys
and values), how many query heads (grouped over `kv_heads` K and V heads),
which rotary parameters (partial rotary, YaRN, plain, or none) and which MLP
(`dense`, a gated MLP; `sparse`, a sigmoid-routed expert layer plus one
shared expert, chosen over all experts or inside the best groups on biased
scores; the experts gated silu MLPs on the model's width or ungated relu²
MLPs in a latent space). Either half may be `none`: a hybrid model's layer
is one mixer alone, `x + mixer(RMSNorm(x))`. The configuration is built from
a published `config.json`'s own keys (`layer_types`,
`num_attention_heads_per_layer`, `mlp_layer_types`, `rope_parameters`, ...;
`from_latent_published` reads the keys of a latent-attention config,
`from_hybrid_published` a `hybrid_override_pattern`), cut to the chip's
share of a deployment:
`experts_held = (first, count)` of each sparse layer's experts and
`vocab_held` rows of embedding and head (`parallel/moe.py:moe_share_apply`).

bfloat16 activations and matmul operands with float32 accumulation; norms,
rotary tables, router scores and the loss in float32; float32 parameters;
each layer under `jax.checkpoint`, which keeps the layer's input and its
attention's output. `CausalLMTrainer` trains it through the
step engine that `BertTrainer` uses; `serving/latent.py` decodes the same
block description, token by token, over a paged pool of latents, and
`serving/hybrid.py` a hybrid one over K/V pages and a state a slot."""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, spec_for
from deeplearning4j_tpu.parallel.moe import (
    ACTIVATIONS, expert_mid, moe_share_apply, moe_share_dense,
    moe_share_init, moe_share_rows)
from deeplearning4j_tpu.parallel.step_engine import StepEngine, loss_and_adam

# the splash kernel's least tile: a sequence it runs on is a multiple of this
ATTENTION_BLOCK = 512
# what `forward`'s checkpoint keeps of a layer: the attention's result
ATTENTION_SAVED = "attention.out"
# positions of each row whose logits the loss holds at a time
LOSS_CHUNK = 4096
# weights from a seed: normal(0, INIT_STD) for every matrix and the embedding
INIT_STD = 0.02


@dataclass(frozen=True)
class LayerSpec:
    attention: str          # "full" | "sliding" | "latent" | "mamba" | "none"
    heads: int              # query (or state-space) heads of this layer
    mlp: str                # "dense" | "sparse" | "none"


@dataclass(frozen=True)
class CausalLMConfig:
    layers: tuple            # of LayerSpec
    rope: dict               # attention kind -> its rope_parameters entry
    vocab_held: int
    hidden: int
    head_dim: int
    kv_heads: int
    sliding_window: int
    dense_ffn: int
    expert_ffn: int
    shared_ffn: int
    num_experts: int         # the router's width: every expert of the layer
    top_k: int
    routed_scale: float
    experts_held: tuple      # (first, count) of the experts that live here
    rms_eps: float = 1e-6
    compute_dtype: str = "bfloat16"
    # the router's choice: inside the best `topk_group` of `n_group` groups,
    # on scores plus a bias that the loss does not train
    n_group: int = 1
    topk_group: int = 1
    router_bias: bool = False
    # latent attention: the ranks of the query's and the cache's latents,
    # each head's part without and with rotary, its value's width, and the
    # factor on the scores
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    latent_scale: float = 1.0
    # a `full` or `sliding` layer's sigmoid output gate, one value a head
    attn_gate: bool = True
    # the experts' form: gated or not, their activation, and the width of
    # the latent space they work in (0: the model's own width)
    expert_gated: bool = True
    expert_act: str = "silu"
    expert_latent: int = 0
    # a `mamba` layer: each head's width, the groups that share B and C, the
    # state's width a head and group, the causal convolution's taps, and
    # (min, max, floor) of the time step the initial `dt_bias` is drawn for
    mamba_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    conv_kernel: int = 0
    time_step: tuple = (0.001, 0.1, 1e-4)
    # the residual path: `streams` residual vectors a position (1: the plain
    # `x + f(norm(x))`); with more, the iterations and the `eps` of the
    # Sinkhorn normalisation that makes a sublayer's mixing map doubly
    # stochastic, and the clamp (min, max) on that map's logits before `exp`
    streams: int = 1
    sinkhorn_iters: int = 0
    sinkhorn_eps: float = 0.0
    res_clamp: tuple = (0.0, 0.0)

    @classmethod
    def from_hybrid_published(cls, published: dict, layer_ids=None,
                              experts_held=None, vocab_held=None, **kw):
        """From the keys of a published hybrid state-space config.json
        (`hybrid_override_pattern`, `mamba_num_heads`, `mamba_head_dim`,
        `n_groups`, `ssm_state_size`, `conv_kernel`, `moe_latent_size`,
        `mlp_hidden_act`, ...). A letter of the pattern a layer, each ONE
        mixer: `M` Mamba-2, `*` attention (grouped heads, no rotary
        embedding: the state-space layers carry position), `E` latent
        experts with one shared expert on the full width. `layer_ids` are
        the published layers that run here, all by default."""
        pattern = published["hybrid_override_pattern"]
        ids = range(len(pattern)) if layer_ids is None else layer_ids
        kinds = {
            "M": LayerSpec("mamba", published["mamba_num_heads"], "none"),
            "*": LayerSpec("full", published["num_attention_heads"], "none"),
            "E": LayerSpec("none", 0, "sparse")}
        if any(pattern[i] not in kinds for i in ids):
            raise ValueError(
                f"layers {sorted(set(pattern) - set(kinds))} of the pattern "
                f"are not written here (M, * and E are)")
        return cls(
            layers=tuple(kinds[pattern[i]] for i in ids), rope={},
            vocab_held=vocab_held or published["vocab_size"],
            hidden=published["hidden_size"], head_dim=published["head_dim"],
            kv_heads=published["num_key_value_heads"], sliding_window=0,
            dense_ffn=published["intermediate_size"],
            expert_ffn=published["moe_intermediate_size"],
            shared_ffn=(published["n_shared_experts"]
                        * published["moe_shared_expert_intermediate_size"]),
            num_experts=published["n_routed_experts"],
            top_k=published["num_experts_per_tok"],
            routed_scale=published["routed_scaling_factor"],
            experts_held=tuple(experts_held
                               or (0, published["n_routed_experts"])),
            rms_eps=published["norm_eps"], n_group=published["n_group"],
            topk_group=published["topk_group"], router_bias=True,
            attn_gate=False, expert_gated=False,
            expert_act=published["mlp_hidden_act"],
            expert_latent=published["moe_latent_size"],
            mamba_head_dim=published["mamba_head_dim"],
            ssm_groups=published["n_groups"],
            ssm_state=published["ssm_state_size"],
            conv_kernel=published["conv_kernel"],
            time_step=(published["time_step_min"],
                       published["time_step_max"],
                       published["time_step_floor"]), **kw)

    @classmethod
    def from_latent_published(cls, published: dict, layer_ids=None,
                              experts_held=None, vocab_held=None, **kw):
        """From the keys of a published latent-attention config.json
        (`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
        `qk_rope_head_dim`, `v_head_dim`, `first_k_dense_replace`,
        `n_routed_experts`, `n_group`, `topk_group`, `rope_scaling`, ...).
        `layer_ids` are the published layers that run here, all by default;
        the first `first_k_dense_replace` have a dense MLP. YaRN's two
        magnitude factors are equal there, so the tables carry none and
        the scores are scaled by (1 + 0.1 mscale ln factor)^2 beside
        1 / sqrt(the query head's width). `hc_mult` > 1 gives a position that
        many residual streams (`hc_sinkhorn_iters`, `hc_eps`,
        `mhc_h_res_clamp_min`, `mhc_h_res_clamp_max` beside it). A key whose
        value cannot be honoured is refused by name, not ignored."""
        refuse = lambda key, why: ValueError(  # noqa: E731
            f"{key} = {published.get(key)!r}: {why}")
        if published.get("moe_layer_freq", 1) != 1:
            raise refuse("moe_layer_freq", "every layer after the leading "
                         "dense ones is sparse here")
        if published["n_shared_experts"] < 1:
            raise refuse("n_shared_experts", "a sparse layer has a shared "
                         "expert here")
        if published["topk_method"] not in ("noaux_tc", "greedy"):
            raise refuse("topk_method", "noaux_tc (biased scores, a group's "
                         "mark its two best) and greedy (plain top-k) are "
                         "written here")
        streams = published.get("hc_mult", 1)
        hc_keys = ("hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                   "mhc_h_res_clamp_max")
        if streams > 1 and any(k not in published for k in hc_keys):
            raise refuse("hc_mult", "more than one residual stream needs "
                         + ", ".join(k for k in hc_keys
                                     if k not in published))
        hyper = {} if streams == 1 else dict(
            streams=streams, sinkhorn_iters=published["hc_sinkhorn_iters"],
            sinkhorn_eps=published["hc_eps"],
            res_clamp=(published["mhc_h_res_clamp_min"],
                       published["mhc_h_res_clamp_max"]))
        ids = (range(published["num_hidden_layers"]) if layer_ids is None
               else layer_ids)
        heads = published["num_attention_heads"]
        layers = tuple(
            LayerSpec("latent", heads,
                      "dense" if i < published["first_k_dense_replace"]
                      else "sparse") for i in ids)
        scaling = published["rope_scaling"]
        if scaling["mscale"] != scaling["mscale_all_dim"]:
            raise ValueError("rotary tables with a magnitude factor of "
                             "their own are not written here")
        rope = {"rope_theta": published["rope_theta"], "rope_type": "yarn",
                "attention_factor": 1.0,
                **{k: scaling[k] for k in (
                    "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow")}}
        nope, rot = (published["qk_nope_head_dim"],
                     published["qk_rope_head_dim"])
        mscale = 1.0 + 0.1 * scaling["mscale"] * math.log(scaling["factor"])
        return cls(
            layers=layers, rope={"latent": rope},
            vocab_held=vocab_held or published["vocab_size"],
            hidden=published["hidden_size"], head_dim=nope + rot,
            kv_heads=published["num_key_value_heads"], sliding_window=0,
            dense_ffn=published["intermediate_size"],
            expert_ffn=published["moe_intermediate_size"],
            shared_ffn=(published["n_shared_experts"]
                        * published["moe_intermediate_size"]),
            num_experts=published["n_routed_experts"],
            top_k=published["num_experts_per_tok"],
            routed_scale=published["routed_scaling_factor"],
            experts_held=tuple(experts_held
                               or (0, published["n_routed_experts"])),
            rms_eps=published["rms_norm_eps"],
            n_group=published["n_group"],
            topk_group=published["topk_group"],
            router_bias=published["topk_method"] == "noaux_tc",
            q_rank=published["q_lora_rank"],
            kv_rank=published["kv_lora_rank"], nope_dim=nope, rope_dim=rot,
            v_dim=published["v_head_dim"],
            latent_scale=mscale * mscale / math.sqrt(nope + rot),
            **{**hyper, **kw})

    @classmethod
    def from_published(cls, published: dict, num_layers=None,
                       experts_held=None, vocab_held=None, **kw):
        """From a published config.json's keys; the three cuts default to
        the whole model."""
        n = num_layers or published["num_hidden_layers"]
        kinds = {"full_attention": "full", "sliding_attention": "sliding"}
        layers = tuple(
            LayerSpec(kinds[a], h, m) for a, h, m in zip(
                published["layer_types"][:n],
                published["num_attention_heads_per_layer"][:n],
                published["mlp_layer_types"][:n]))
        ropes = published["rope_parameters"]
        return cls(
            layers=layers,
            rope={kinds[k]: ropes[k] for k in kinds},
            vocab_held=vocab_held or published["vocab_size"],
            hidden=published["hidden_size"],
            head_dim=published["head_dim"],
            kv_heads=published["num_key_value_heads"],
            sliding_window=published["sliding_window"],
            dense_ffn=published["intermediate_size"],
            expert_ffn=published["moe_intermediate_size"],
            shared_ffn=published["shared_expert_intermediate_size"],
            num_experts=published["num_experts"],
            top_k=published["num_experts_per_tok"],
            routed_scale=published.get("moe_routed_scaling_factor", 1.0),
            experts_held=tuple(experts_held
                               or (0, published["num_experts"])),
            rms_eps=published["rms_norm_eps"], **kw)

    @property
    def sparse_layers(self):
        return [i for i, s in enumerate(self.layers) if s.mlp == "sparse"]

    @property
    def mamba_inner(self):
        """Width of a Mamba layer's inner stream: heads x their width."""
        heads = next(s.heads for s in self.layers if s.attention == "mamba")
        return heads * self.mamba_head_dim

    @property
    def conv_width(self):
        """Channels of a Mamba layer's convolution: x beside B and C."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def rotary_width(self, kind):
        """The width `rope_tables` is asked for: a latent layer rotates the
        shared key's `rope_dim`, the others (part of) a head."""
        return self.rope_dim if kind == "latent" else self.head_dim


# -- parameters ---------------------------------------------------------------

def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _mlp_init(key, hidden, ffn, std, gated=True):
    k = jax.random.split(key, 3)
    out = {"up": _normal(k[1], (hidden, ffn), std),
           "down": _normal(k[2], (ffn, hidden), std)}
    if gated:
        out["gate"] = _normal(k[0], (hidden, ffn), std)
    return out


def _mamba_init(cfg, heads, key, std):
    """A Mamba-2 mixer's leaves: `A_log = ln U(1, 16)`, `dt_bias` the
    inverse softplus of a time step drawn log-uniformly in `cfg.time_step`'s
    range and floored, `D` and the gains 1, the convolution's taps and bias
    uniform in +-conv_kernel^-0.5 (a depthwise convolution's usual
    initialiser: at `std` B and C would be so small that the state's part
    of the output vanished beside `D x`)."""
    k = jax.random.split(key, 6)
    inner, width = heads * cfg.mamba_head_dim, cfg.conv_width
    lo, hi, floor = cfg.time_step
    taps = cfg.conv_kernel ** -0.5
    dt = jnp.maximum(jnp.exp(jax.random.uniform(k[3], (heads,)) * (
        math.log(hi) - math.log(lo)) + math.log(lo)), floor)
    return {"w_in": _normal(k[0], (cfg.hidden, inner + width + heads), std),
            "conv_w": jax.random.uniform(k[1], (cfg.conv_kernel, width),
                                         jnp.float32, -taps, taps),
            "conv_b": jax.random.uniform(k[5], (width,), jnp.float32,
                                         -taps, taps),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k[4], (heads,), minval=1.0, maxval=16.0)),
            "D": jnp.ones((heads,)), "gate_norm": jnp.ones((inner,)),
            "w_out": _normal(k[2], (inner, cfg.hidden), std)}


def _stream_init(cfg, key, std):
    """One sublayer's residual maps: `phi [streams * hidden, 2 streams +
    streams^2]` (the columns that make `H_pre`, `H_post` and, row by row,
    `H_res`), the three `alpha` on its products and the `bias` under them,
    all float32. The start is the plain residual as nearly as the maps can
    say it, the choice here since a published config gives none: `alpha`
    0.01, `H_pre = sigmoid(0)` on every stream, `H_post = 2 sigmoid(0) = 1`,
    and `H_res` the identity but for `exp(-8)` off the diagonal."""
    n = cfg.streams
    return {"phi": _normal(key, (n * cfg.hidden, 2 * n + n * n), std),
            "alpha": jnp.full((3,), 0.01, jnp.float32),
            "bias": jnp.concatenate([
                jnp.zeros((2 * n,)),
                (-8.0 * (1.0 - jnp.eye(n))).reshape(-1)])}


def init_params(cfg: CausalLMConfig, key) -> dict:
    d, hd, std = cfg.hidden, cfg.head_dim, INIT_STD
    keys = jax.random.split(key, 2 + len(cfg.layers))
    norm = lambda kk, shape: _normal(kk, shape, std)  # noqa: E731
    params = {"embed": norm(keys[0], (cfg.vocab_held, d)),
              "head": norm(keys[1], (d, cfg.vocab_held)),
              "final_norm": jnp.ones((d,)), "layers": []}
    for spec, lk in zip(cfg.layers, keys[2:]):
        k = jax.random.split(lk, 8)
        layer = {}
        if spec.attention != "none":
            layer["attn_norm"] = jnp.ones((d,))
        if spec.mlp != "none":
            layer["mlp_norm"] = jnp.ones((d,))
        if cfg.streams > 1:
            ka, km = jax.random.split(k[7])
            if spec.attention != "none":
                layer["attn_streams"] = _stream_init(cfg, ka, std)
            if spec.mlp != "none":
                layer["mlp_streams"] = _stream_init(cfg, km, std)
        if spec.attention == "mamba":
            layer.update(_mamba_init(cfg, spec.heads, k[0], std))
        elif spec.attention == "latent":
            layer.update(
                wq_a=norm(k[0], (d, cfg.q_rank)),
                q_norm=jnp.ones((cfg.q_rank,)),
                wq_b=norm(k[1], (cfg.q_rank, spec.heads * hd)),
                wkv_a=norm(k[2], (d, cfg.kv_rank + cfg.rope_dim)),
                kv_norm=jnp.ones((cfg.kv_rank,)),
                wkv_b=norm(k[3], (cfg.kv_rank, spec.heads
                                  * (cfg.nope_dim + cfg.v_dim))),
                wo=norm(k[4], (spec.heads * cfg.v_dim, d)))
        elif spec.attention != "none":
            layer.update(
                wq=norm(k[0], (d, spec.heads * hd)),
                wk=norm(k[1], (d, cfg.kv_heads * hd)),
                wv=norm(k[2], (d, cfg.kv_heads * hd)),
                wo=norm(k[4], (spec.heads * hd, d)))
            if cfg.attn_gate:
                layer["wg"] = norm(k[3], (d, spec.heads))
        if spec.mlp == "dense":
            layer["mlp"] = _mlp_init(k[5], d, cfg.dense_ffn, std)
        elif spec.mlp == "sparse":
            layer["moe"] = moe_share_init(
                k[5], d, cfg.expert_ffn, cfg.num_experts,
                cfg.experts_held[1], std, gated=cfg.expert_gated,
                latent=cfg.expert_latent)
            if cfg.router_bias:
                layer["moe"]["bias"] = jnp.zeros((cfg.num_experts,))
            layer["shared"] = _mlp_init(k[6], d, cfg.shared_ffn, std,
                                        gated=cfg.expert_gated)
        params["layers"].append(layer)
    return params


def param_specs(cfg: CausalLMConfig) -> dict:
    """Everything replicated: the share of the experts and of the
    vocabulary is this program's whole state, and `data` splits the rows."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return jax.tree_util.tree_map(lambda _: P(), shapes)


# -- rotary tables ------------------------------------------------------------

def rope_inv_freq(rope: dict, head_dim: int):
    """(inverse frequencies float64 [rot/2], factor on cos and sin) of one
    `rope_parameters` entry: the first `partial_rotary_factor * head_dim`
    dimensions rotate. `yarn` blends the interpolated frequencies (over
    `factor`) into the extrapolated ones by a linear ramp over the rotary
    dimensions, between the dimensions that turn `beta_fast` and
    `beta_slow` times within the original context."""
    rot = int(rope.get("partial_rotary_factor", 1) * head_dim)
    base = float(rope["rope_theta"])
    pos = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") != "yarn":
        return 1.0 / pos, 1.0
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]
    turn = lambda n: rot * math.log(  # noqa: E731
        orig / (n * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turn(rope["beta_fast"])), 0)
    high = min(math.ceil(turn(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv = (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1 - ramp)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def rope_tables(rope: dict, head_dim: int, seq: int):
    """(cos, sin) float32 [seq, rot/2] for positions 0..seq-1."""
    inv, scale = rope_inv_freq(rope, head_dim)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def apply_rope(x, cos, sin):
    """Rotate-half over the first 2 * cos.shape[-1] dimensions of each head,
    in float32; the rest pass. x: [B, T, H, D]."""
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1).astype(x.dtype)


def apply_rope_pairs(x, cos, sin):
    """Rotate the neighbouring pairs (x[2i], x[2i+1]) of the last axis, in
    float32: the layout of the published latent-attention code, where
    `apply_rope` pairs x[i] with x[i + half]. cos, sin: [..., last / 2],
    broadcast against x's leading axes."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


# -- attention ----------------------------------------------------------------

def _on_tpu():
    return jax.default_backend() == "tpu"


def causal_attention(q, k, v, window=None):
    """Causal attention with grouped K and V. q: [B, T, H, D]; k, v:
    [B, T, KV, D] with H a multiple of KV; query head h reads KV head
    h // (H / KV). `window` also masks `i - j >= window`. -> [B, T, H, D].

    On a TPU, for sequences the kernel's tiles divide, the splash kernel
    (blocked online softmax) visits only the blocks the mask leaves:
    a sliding layer does the work of its window, not of the sequence.
    Elsewhere a plain masked product.

    Both branches name their result `ATTENTION_SAVED` (the kernel its
    float32 log-sum-exp [B, H, T] too), for a `jax.checkpoint` whose policy
    keeps that name: `B x T x H x D` of the activations' dtype a call, and
    the backward pass does not run the forward kernel again. The plain
    branch names the output alone and makes its softmax again."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    q = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    # [B, KV, G, T, D] and [B, KV, T, D]
    qg = jnp.transpose(q.reshape(b, t, kv, g, d), (0, 2, 3, 1, 4))
    kt, vt = (jnp.transpose(a, (0, 2, 1, 3)) for a in (k, v))
    if _on_tpu() and t % ATTENTION_BLOCK == 0:
        out = _splash(qg, kt, vt, window)
    else:
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        s = jnp.einsum("bkgqd,bksd->bkgqs", qg, kt,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(vt.dtype), vt,
                         preferred_element_type=jnp.float32).astype(q.dtype)
        out = checkpoint_name(out, ATTENTION_SAVED)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, t, h, d)


def _splash(qg, kt, vt, window):
    """One multi-query kernel (G query heads on one K and V head) mapped
    over the KV heads and the batch; its output and log-sum-exp named
    `ATTENTION_SAVED`."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as mask)

    g, t = qg.shape[2], qg.shape[3]
    one = (mask.CausalMask((t, t)) if window is None else
           mask.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    # tiles from the mask: under a window a query tile of 512 visits two
    # key tiles of 512 and more columns at any other size; over the whole
    # triangle tiles twice as long read K and V half as often
    blk = ATTENTION_BLOCK
    if window is None and t % (2 * blk) == 0:
        blk *= 2
    sizes = kernel.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=ATTENTION_BLOCK,
        block_q_dkv=blk, block_kv_dkv=blk,
        block_kv_dkv_compute=ATTENTION_BLOCK, block_q_dq=blk,
        block_kv_dq=blk)

    def fn():
        # made where it is called: under a trace the kernel holds its mask's
        # tables as values of that trace
        return jax.vmap(jax.vmap(kernel.make_splash_mqa_single_device(
            mask.MultiHeadMask([one] * g), block_sizes=sizes,
            residual_checkpoint_name=ATTENTION_SAVED)))

    @jax.custom_vjp
    def attend(q, k, v):
        return fn()(q, k, v)

    def attend_fwd(q, k, v):
        # The kernel writes its log-sum-exp 128 lanes wide, [B, H, T, 128]
        # float32 (537 MB for 64 heads at 2 x 8,192), and keeps lane 0.
        # Left alone the compiler puts that slice off until the backward
        # pass and holds the wide array meanwhile; behind the barrier all
        # the backward pass is handed is made by the time the output is.
        out, pull = jax.vjp(fn(), q, k, v)
        return jax.lax.optimization_barrier((out, pull))

    attend.defvjp(attend_fwd, lambda pull, d_out: pull(d_out))
    return attend(qg, kt, vt)


# -- the residual path --------------------------------------------------------

def _total(parts):
    """The sum of a few arrays as additions of them: elementwise work."""
    return functools.reduce(jnp.add, parts)


def streams_enter(x, cfg: CausalLMConfig):
    """The embedding [..., d] as the residual path carries it: itself, or
    `streams` copies of it [..., streams, d]."""
    if cfg.streams == 1:
        return x
    return jnp.broadcast_to(x[..., None, :],
                            (*x.shape[:-1], cfg.streams, x.shape[-1]))


def streams_exit(x, cfg: CausalLMConfig):
    """What the final norm reads: the one stream, or the sum of them
    (float32 sum, rounded once)."""
    if cfg.streams == 1:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def stream_maps(hp, x, cfg: CausalLMConfig, fused=False):
    """The three maps of one sublayer from the streams it meets, x [...,
    streams, d], and `hp` (`_stream_init`'s leaves); None for one stream.

        xbar = vec(x) / sqrt(mean(vec(x)^2) + rms_eps)        (no gain)
        [a | b | c] = xbar phi
        pre = sigmoid(alpha_0 a + bias_a)                     n entries
        post = 2 sigmoid(alpha_1 b + bias_b)                  n entries
        m = exp(clip(alpha_2 mat(c) + bias_c, *res_clamp))    n x n entries
        `sinkhorn_iters` times:  m /= column sums + eps;  m /= row sums + eps
        res = m

    -> {"pre": [n], "post": [n], "res": [n][n], "defect"}: every ENTRY of a
    map is one float32 array over the positions, shaped as x's leading axes
    (`stream_matrix` stacks them where a matrix is wanted), and so is
    `defect`, the largest `|sum - 1|` over the rows and columns of `res`:
    how far the iterations left it from doubly stochastic. Float32 at the
    highest matmul precision whatever x's dtype: the maps decide what every
    stream holds next, as a router's scores decide a row's experts.

    The positions are the minor axis from the product on. What follows it
    is `kernels/stream_maps.py`: `packed_maps`, or, where `fused` says that
    no gradient is wanted (the decode step), the backend is a TPU and the
    positions fill whole lanes, the same as one kernel call."""
    if cfg.streams == 1:
        return None
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels import stream_maps as maps_kernel

    n = cfg.streams
    cols = 2 * n + n * n
    with jax.named_scope("hc.maps"):
        lead = x.shape[:-2]
        xf = x.astype(jnp.float32).reshape(-1, n * x.shape[-1])
        scale = jax.lax.rsqrt(jnp.mean(xf * xf, -1) + cfg.rms_eps)
        z = jnp.einsum("rk,kf->fr", xf, hp["phi"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        # each column's alpha: the three, spread over their maps' columns
        spread = np.repeat(np.eye(3, dtype=np.float32), [n, n, n * n], 1)
        by_map = jnp.sum(hp["alpha"].astype(jnp.float32)[:, None] * spread, 0)
        z = z * scale * by_map[:, None] \
            + hp["bias"].astype(jnp.float32)[:, None]
        how = dict(n=n, iters=cfg.sinkhorn_iters, eps=cfg.sinkhorn_eps,
                   clamp=cfg.res_clamp)
        if fused and kernels._on_tpu() \
                and maps_kernel.available(z.shape[1], n):
            packed = maps_kernel.sinkhorn_maps(z, **how)
        else:
            packed = maps_kernel.packed_maps(z, **how)
        entry = lambda k: packed[k].reshape(lead)  # noqa: E731
        return {"pre": [entry(i) for i in range(n)],
                "post": [entry(n + i) for i in range(n)],
                "res": [[entry(2 * n + i * n + j) for j in range(n)]
                        for i in range(n)],
                "defect": entry(cols)}


def stream_matrix(maps):
    """`stream_maps`' `res` as one array [..., n, n]: `[..., i, j]` is what
    stream i takes of stream j."""
    return jnp.stack([jnp.stack(row, -1) for row in maps["res"]], -2)


def stream_read(x, maps):
    """What a sublayer's norm reads: x itself, or `sum_i pre_i x_i` of the
    streams x [..., n, d] (float32 sum, rounded once to x's dtype)."""
    if maps is None:
        return x
    with jax.named_scope("hc.mix"):
        xf = x.astype(jnp.float32)
        return _total(w[..., None] * xf[..., i, :]
                      for i, w in enumerate(maps["pre"])).astype(x.dtype)


def stream_write(x, y, maps):
    """The sublayer's result y [..., d] (float32) taken into the residual
    path: `x + y`, or `x'_i = sum_j res_ij x_j + post_i y` for the streams
    x [..., n, d]; summed in float32, rounded once to x's dtype."""
    if maps is None:
        return (x + y).astype(x.dtype)
    with jax.named_scope("hc.mix"):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.stack([
            _total(w[..., None] * xf[..., j, :] for j, w in enumerate(row))
            + post[..., None] * yf
            for row, post in zip(maps["res"], maps["post"])],
            axis=-2).astype(x.dtype)


# -- the block ----------------------------------------------------------------

def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * g


def _mm(a, w):
    """Operands in the activations' dtype, float32 accumulation."""
    return jnp.matmul(a, w.astype(a.dtype),
                      preferred_element_type=jnp.float32)


def mlp_apply(p, u, activation="silu"):
    """An MLP in the form its parameters give (`parallel/moe.py:expert_mid`):
    gated where `p` holds `gate`, else `down(act(up u))`."""
    mid = expert_mid(p, lambda w: _mm(u, w), ACTIVATIONS[activation])
    return _mm(mid.astype(u.dtype), p["down"])


def latent_project(lp, u, cfg: CausalLMConfig, heads: int, cos, sin):
    """What a latent layer makes of its normed input u [..., d] at the
    positions whose rotary rows are cos, sin [..., rope_dim / 2]: each
    head's query without rotary [..., H, nope_dim] and with it [..., H,
    rope_dim] (rotated), and what the cache holds of a position, the
    normed latent c [..., kv_rank] and the rotated key k_r [..., rope_dim]
    that all heads share. The expanded attention (`latent_attention`) and
    the absorbed token step (`serving/latent.py`) both start here."""
    dtype = u.dtype
    c_q = rms_norm(_mm(u, lp["wq_a"]), lp["q_norm"], cfg.rms_eps)
    q = _mm(c_q.astype(dtype), lp["wq_b"]).astype(dtype).reshape(
        *u.shape[:-1], heads, cfg.nope_dim + cfg.rope_dim)
    kv = _mm(u, lp["wkv_a"])
    c = rms_norm(kv[..., :cfg.kv_rank], lp["kv_norm"],
                 cfg.rms_eps).astype(dtype)
    k_r = apply_rope_pairs(kv[..., cfg.kv_rank:], cos, sin).astype(dtype)
    q_r = apply_rope_pairs(q[..., cfg.nope_dim:], cos[..., None, :],
                           sin[..., None, :])
    return q[..., :cfg.nope_dim], q_r, c, k_r


def latent_attention(lp, u, cfg: CausalLMConfig, spec: LayerSpec, tables):
    """Causal latent attention over a whole sequence, every position
    expanded into heads: [k_nope_h | v_h] = c W_kvb,h, the score of head h
    is (q_nope_h . k_nope_h + q_rope_h . k_r) x `latent_scale`, softmax in
    float32. u [B, T, d] -> [B, T, d] float32. A plain masked product (no
    kernel takes heads whose keys are wider than their values)."""
    b, t, _ = u.shape
    cos, sin = tables["latent"]
    q_n, q_r, c, k_r = latent_project(lp, u, cfg, spec.heads, cos, sin)
    kv = _mm(c, lp["wkv_b"]).astype(u.dtype).reshape(
        b, t, spec.heads, cfg.nope_dim + cfg.v_dim)
    k_n, v = kv[..., :cfg.nope_dim], kv[..., cfg.nope_dim:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r,
                      preferred_element_type=jnp.float32)) * cfg.latent_scale
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(u.dtype), v,
                   preferred_element_type=jnp.float32).astype(u.dtype)
    return _mm(o.reshape(b, t, spec.heads * cfg.v_dim), lp["wo"])


def mamba_step(lp, u, tail, state, cfg: CausalLMConfig, heads: int):
    """One position of a Mamba-2 mixer for a batch of sequences, each with
    what it carries: u [S, d] the normed input; `tail` [S, conv_kernel - 1,
    conv_width], the rows of `xBC` that the causal convolution still sees,
    oldest first, zeros before a sequence; `state` [S, heads, head_dim,
    ssm_state] float32. -> (out [S, d] float32, the new tail, the new state).

        [z | xBC | dt] = u W_in;  xBC <- silu(conv_b + sum_j conv_w[j] * row_j)
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S <- exp(dt A) S + dt x (outer) B;   y = S C + D x
        out = rms_norm_g(y * silu(z)) W_out

    head h reads B and C of group `h // (heads / ssm_groups)`; the norm runs
    inside each of the `ssm_groups` groups of the inner stream, one gain over
    all of it. Products take operands in u's dtype and accumulate in float32;
    the convolution, the time step, the state and its update are float32.
    The sequence form (`mamba_mixer`) is a scan of this very function."""
    dtype, S = u.dtype, u.shape[0]
    P, G, N = cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, R = heads * P, heads // G
    with jax.named_scope("ssm.project"):
        zxd = _mm(u, lp["w_in"])
        z = zxd[:, :inner]
        xbc = zxd[:, inner:inner + cfg.conv_width].astype(dtype)
        dt = jax.nn.softplus(zxd[:, inner + cfg.conv_width:]
                             + lp["dt_bias"].astype(jnp.float32))    # [S, H]
    with jax.named_scope("ssm.conv"):
        rows = jnp.concatenate([tail, xbc[:, None, :]], axis=1)
        conv = jax.nn.silu(
            jnp.sum(rows.astype(jnp.float32)
                    * lp["conv_w"].astype(jnp.float32)[None], axis=1)
            + lp["conv_b"].astype(jnp.float32))
        x = conv[:, :inner].reshape(S, G, R, P)
        b = conv[:, inner:inner + G * N].reshape(S, G, 1, 1, N)
        c = conv[:, inner + G * N:].reshape(S, G, 1, 1, N)
    with jax.named_scope("ssm.update"):
        # heads by group: B and C broadcast over a group's heads
        dt = dt.reshape(S, G, R)
        decay = jnp.exp(-dt * jnp.exp(lp["A_log"].astype(jnp.float32))
                        .reshape(G, R))
        new = decay[..., None, None] * state.reshape(S, G, R, P, N) \
            + (dt[..., None] * x)[..., None] * b
        y = jnp.sum(new * c, axis=-1) \
            + lp["D"].astype(jnp.float32).reshape(G, R, 1) * x
    with jax.named_scope("ssm.gate"):
        y = y.reshape(S, G, R * P) * jax.nn.silu(z).reshape(S, G, R * P)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + cfg.rms_eps)
        y = (y.reshape(S, inner) * lp["gate_norm"]).astype(dtype)
        out = _mm(y, lp["w_out"])
    return out, rows[:, 1:], new.reshape(state.shape)


def mamba_mixer(lp, u, cfg: CausalLMConfig, heads: int):
    """A Mamba-2 mixer over whole sequences from an empty tail and a zero
    state, one position after another (`lax.scan` of `mamba_step`; no
    chunked scan is written here). u [B, T, d] -> [B, T, d] float32."""
    b = u.shape[0]
    carry = (jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_width), u.dtype),
             jnp.zeros((b, heads, cfg.mamba_head_dim, cfg.ssm_state),
                       jnp.float32))

    def one(carry, u_t):
        out, tail, state = mamba_step(lp, u_t, *carry, cfg, heads)
        return (tail, state), out

    _, out = jax.lax.scan(one, carry, jnp.swapaxes(u, 0, 1))
    return jnp.swapaxes(out, 0, 1)


def attention_block(lp, u, cfg: CausalLMConfig, spec: LayerSpec, tables):
    """u: the layer's normed input [B, T, d] -> [B, T, d] float32."""
    if spec.attention == "latent":
        return latent_attention(lp, u, cfg, spec, tables)
    if spec.attention == "mamba":
        return mamba_mixer(lp, u, cfg, spec.heads)
    b, t, _ = u.shape
    hd = cfg.head_dim
    heads = lambda w, n: _mm(u, w).astype(u.dtype).reshape(  # noqa: E731
        b, t, n, hd)
    q, k, v = (heads(lp["wq"], spec.heads), heads(lp["wk"], cfg.kv_heads),
               heads(lp["wv"], cfg.kv_heads))
    if spec.attention in tables:
        cos, sin = tables[spec.attention]
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    o = causal_attention(
        q, k, v, cfg.sliding_window if spec.attention == "sliding" else None)
    if "wg" in lp:
        # the output gate, one value a head, from the same normed input
        gate = jax.nn.sigmoid(_mm(u, lp["wg"]))
        o = o * gate[..., None].astype(o.dtype)
    return _mm(o.reshape(b, t, spec.heads * hd), lp["wo"])


def layer_forward(lp, x, cfg: CausalLMConfig, spec: LayerSpec, tables):
    """x [B, T, d] in the compute dtype ([B, T, streams, d] where the
    residual path has more than one) -> (y, choices int32 [held experts],
    dropped int32); the two counts are nought on a dense layer."""
    dtype = x.dtype
    b, t, d = x.shape[0], x.shape[1], x.shape[-1]
    h = x
    if spec.attention != "none":
        maps = stream_maps(lp.get("attn_streams"), x, cfg)
        with jax.named_scope("attention." + spec.attention):
            u = rms_norm(stream_read(x, maps), lp["attn_norm"],
                         cfg.rms_eps).astype(dtype)
            h = stream_write(x, attention_block(lp, u, cfg, spec, tables),
                             maps)
    count = cfg.experts_held[1]
    if spec.mlp != "sparse":
        choices = jnp.zeros((count,), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        if spec.mlp == "none":
            return h, choices, dropped
    maps = stream_maps(lp.get("mlp_streams"), h, cfg)
    u = rms_norm(stream_read(h, maps), lp["mlp_norm"],
                 cfg.rms_eps).astype(dtype)
    if spec.mlp == "dense":
        with jax.named_scope("mlp.dense"):
            out = mlp_apply(lp["mlp"], u)
    else:
        routed, choices, dropped = moe_share_apply(
            lp["moe"], u.reshape(b * t, d), top_k=cfg.top_k,
            experts_held=cfg.experts_held, routed_scale=cfg.routed_scale,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            activation=cfg.expert_act)
        with jax.named_scope("moe.shared"):
            out = routed.reshape(b, t, d) + mlp_apply(lp["shared"], u,
                                                      cfg.expert_act)
    return stream_write(h, out, maps), choices, dropped


def checkpointed_layer(cfg: CausalLMConfig, spec: LayerSpec, tables):
    """`layer_forward` of (lp, x) as `forward` runs it, recomputed going
    backward: the backward pass holds one layer's activations at a time,
    and of every layer its input and what `causal_attention` names."""
    return jax.checkpoint(
        lambda lp, x: layer_forward(lp, x, cfg, spec, tables),
        policy=jax.checkpoint_policies.save_only_these_names(ATTENTION_SAVED))


def forward(params, cfg: CausalLMConfig, tokens):
    """tokens [B, T] int32 -> (final hidden states [B, T, d], normed, in
    the compute dtype; choices int32 [sparse layers, held experts]; dropped
    int32 [sparse layers]).

    Every layer runs under `checkpointed_layer`. What a backward pass pays
    for not running attention twice: each `full` or `sliding` layer's
    attention output, `B x T x heads x head_dim` in the compute dtype (268
    MB at 2 x 8,192 x 64 x 128 in bfloat16), held from the layer's forward
    pass to its backward pass. A `latent`, `mamba` or `none` layer names
    nothing and keeps its input alone."""
    dtype = jnp.dtype(cfg.compute_dtype)
    t = tokens.shape[1]
    tables = {kind: rope_tables(cfg.rope[kind], cfg.rotary_width(kind), t)
              for kind in {s.attention for s in cfg.layers}
              if kind in cfg.rope}
    x = streams_enter(params["embed"][tokens].astype(dtype), cfg)
    choices, dropped = [], []
    for lp, spec in zip(params["layers"], cfg.layers):
        x, c, dr = checkpointed_layer(cfg, spec, tables)(lp, x)
        if spec.mlp == "sparse":
            choices.append(c)
            dropped.append(dr)
    x = rms_norm(streams_exit(x, cfg), params["final_norm"],
                 cfg.rms_eps).astype(dtype)
    held = cfg.experts_held[1]
    return (x, jnp.stack(choices) if choices
            else jnp.zeros((0, held), jnp.int32),
            jnp.stack(dropped) if dropped else jnp.zeros((0,), jnp.int32))


def logits(params, cfg: CausalLMConfig, tokens):
    """Float32 logits [B, T, vocab_held]."""
    x, _, _ = forward(params, cfg, tokens)
    with jax.named_scope("lm_head"):
        return _mm(x, params["head"])


def lm_loss(params, cfg: CausalLMConfig, tokens, labels):
    """Next-token cross-entropy: float32 log-softmax over the held rows of
    the vocabulary, mean over the positions that have a next token
    (`labels >= 0`; -100 elsewhere). -> (loss, (choices, dropped))."""
    x, choices, dropped = forward(params, cfg, tokens)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)

    @jax.checkpoint
    def chunk_nll(head, x_c, safe_c, valid_c):
        # one chunk's [B, chunk, V] logits at a time, made again going
        # backward
        lp = jax.nn.log_softmax(_mm(x_c, head), axis=-1)
        got = jnp.take_along_axis(lp, safe_c[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(valid_c, got, 0.0))

    b, t = tokens.shape
    n = t // math.gcd(t, LOSS_CHUNK)
    chunks = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(b, n, t // n, *a.shape[2:]), 1, 0)
    with jax.named_scope("lm_head"):
        nll = jax.lax.map(lambda a: chunk_nll(params["head"], *a),
                          (chunks(x), chunks(safe), chunks(valid)))
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)
    return loss, (choices, dropped)


# -- the trainer --------------------------------------------------------------

class CausalLMTrainer:
    """`train_step(tokens, labels)` over the shared step engine: fwd + bwd
    + Adam in one donated executable, rows over `data`. Beside the loss the
    step returns what each sparse layer's router did; the trainer
    publishes those counts one step behind, so that reading them never
    holds up a dispatch. `params` starts from weights of the caller's (a
    tree shaped as `init_params`'s) where the seed's are not wanted; with
    `warmup_steps` the rate climbs linearly to `lr` over that many steps.
    `name` is the `model` label of the `dl4j_moe_*` series."""

    def __init__(self, cfg: CausalLMConfig, mesh: Mesh, lr=1e-4, seed=0,
                 params=None, warmup_steps=0, name="causal_lm"):
        self.cfg, self.mesh, self.lr, self.name = cfg, mesh, lr, name
        self.warmup_steps = warmup_steps
        rows = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))
        repl = NamedSharding(mesh, P())

        def step(params, opt, tokens, labels, t):
            return self._step_math(params, opt, tokens, labels, t)

        self._engine = StepEngine(
            mesh, params if params is not None
            else lambda: init_params(cfg, jax.random.key(seed)),
            param_specs(cfg), step, (rows, rows), aux_sh=((repl, repl),))
        # the last step's (choices [sparse layers, held experts], dropped
        # [sparse layers]) on the device, and what waits to be published
        self.router_counts = None
        self._unpublished = None
        self._series = None      # the dl4j_moe_* bundle, once bound

    params = property(lambda self: self._engine.params)
    opt = property(lambda self: self._engine.opt)

    def _step_math(self, params, opt, tokens, labels, t):
        cfg, lr = self.cfg, self.lr
        if self.warmup_steps:
            lr = lr * jnp.minimum(
                (t + 1).astype(jnp.float32) / self.warmup_steps, 1.0)
        return loss_and_adam(
            lambda p: lm_loss(p, cfg, tokens, labels), params, opt, lr, t,
            has_aux=True)

    def train_step(self, tokens, labels):
        """tokens [B, T] int32; labels [B, T], the next token at each
        position and -100 where there is none. Returns the loss (on the
        device)."""
        loss, counts = self._engine.run(
            lambda: (np.asarray(tokens, np.int32),
                     np.asarray(labels, np.int32)),
            lambda batch, steps: batch)
        self.publish_router_counts()
        self.router_counts = counts
        self._unpublished = (counts, int(np.size(tokens)))
        return loss

    def publish_router_counts(self):
        """Add the counts of the last step that has not been published to
        the registry's `dl4j_moe_*` series (and wait for that step). After
        a `train_step` that is the step before it; a caller that wants the
        last step's too calls this once more."""
        from deeplearning4j_tpu import telemetry

        waiting, self._unpublished = self._unpublished, None
        if waiting is None or not telemetry.enabled():
            return
        choices, dropped = (np.asarray(a) for a in waiting[0])
        if self._series is None:
            self._series = telemetry.moe_instruments(self.name)
        cfg, n = self.cfg, waiting[1]
        every = n * cfg.top_k
        # the form `layer_forward`'s call took at this many tokens
        dense = moe_share_dense(n, cfg.top_k, moe_share_rows(
            n, cfg.top_k, cfg.num_experts, cfg.experts_held[1]))
        self._series.step(cfg.sparse_layers, [
            (every, c.sum(), d, c.max() / max(c.mean(), 1e-9), (c > 0).sum())
            for c, d in zip(choices, dropped)], dense)
