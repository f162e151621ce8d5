"""BERT-capability transformer encoder, TPU-first.

Reference capability: the BERT-base SameDiff TF-import path (SURVEY.md
§3.4, BASELINE.json configs[3]). The reference imports a frozen GraphDef
and interprets it op-by-op; here the model is a native graph-level module:
pure init/forward functions over an explicit param pytree, compiled to ONE
XLA step with GSPMD shardings:

  - data parallel: batch axis over 'data'
  - tensor parallel: Megatron column/row pairs over 'model' (QKV + FFN-in
    column-parallel, attn-out + FFN-out row-parallel)
  - sequence parallel: ring attention over 'seq' (SURVEY.md §5
    long-context: absent in the reference, additive here)

bfloat16 activations with float32 params/optimizer state (MXU-friendly);
the LM head ties the embedding matrix."""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental.pallas.ops.tpu.flash_attention import (
    flash_attention as _pallas_flash)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, SEQ_AXIS, spec_for)
from deeplearning4j_tpu.parallel.ring_attention import ring_attention
from deeplearning4j_tpu.parallel.step_engine import StepEngine, loss_and_adam


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn: int = 3072
    max_len: int = 512
    type_vocab: int = 2
    dropout: float = 0.1
    compute_dtype: str = "bfloat16"   # activations; params stay f32
    layer_norm_eps: float = 1e-12
    # MoE variant: n_experts > 0 replaces every layer's dense FFN with a
    # GShard/Switch top-k MoE block whose experts shard over the `expert`
    # mesh axis (dp x ep training through the same BertTrainer)
    n_experts: int = 0
    moe_k: int = 2
    moe_capacity: float = 1.5
    moe_aux_weight: float = 1e-2
    # "auto" routes by sequence length: dense softmax up to T=1024
    # (measured on v5e, XLA's fused dense attention beats the Pallas
    # flash kernel ~2x at BERT-base shapes — head_dim 64 pads to the
    # kernel's 128-wide MXU lane), and the Pallas flash kernel for
    # longer 128-divisible T on TPU, where the quadratic [B,H,T,T]
    # score tensor makes dense untenable. "dense"/"flash"/"dpa" force
    # a specific implementation.
    attention_impl: str = "auto"

    @property
    def head_dim(self):
        return self.hidden // self.num_heads


def init_params(cfg: BertConfig, key) -> dict:
    h, f, v = cfg.hidden, cfg.ffn, cfg.vocab_size
    std = 0.02
    keys = jax.random.split(key, 6 + cfg.num_layers)

    def norm(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * std

    params = {
        "tok_emb": norm(keys[0], (v, h)),
        "pos_emb": norm(keys[1], (cfg.max_len, h)),
        "type_emb": norm(keys[2], (cfg.type_vocab, h)),
        "emb_ln": {"g": jnp.ones((h,)), "b": jnp.zeros((h,))},
        "layers": [],
        "mlm_bias": jnp.zeros((v,)),
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[6 + i], 6)
        layer = {
            "qkv_w": norm(k[0], (h, 3 * h)),
            "qkv_b": jnp.zeros((3 * h,)),
            "out_w": norm(k[1], (h, h)),
            "out_b": jnp.zeros((h,)),
            "ln1": {"g": jnp.ones((h,)), "b": jnp.zeros((h,))},
            "ln2": {"g": jnp.ones((h,)), "b": jnp.zeros((h,))},
        }
        if cfg.n_experts > 0:
            from deeplearning4j_tpu.parallel.moe import moe_init

            layer["moe"] = moe_init(k[2], h, f, cfg.n_experts)
        else:
            layer.update({
                "ffn_in_w": norm(k[2], (h, f)),
                "ffn_in_b": jnp.zeros((f,)),
                "ffn_out_w": norm(k[3], (f, h)),
                "ffn_out_b": jnp.zeros((h,)),
            })
        params["layers"].append(layer)
    return params


def param_specs(cfg: BertConfig) -> dict:
    """Megatron-style PartitionSpecs matching init_params structure."""
    layer = {
        "qkv_w": P(None, MODEL_AXIS), "qkv_b": P(MODEL_AXIS),
        "out_w": P(MODEL_AXIS, None), "out_b": P(),
        "ln1": {"g": P(), "b": P()},
        "ln2": {"g": P(), "b": P()},
    }
    if cfg.n_experts > 0:
        from deeplearning4j_tpu.parallel.moe import moe_param_specs

        layer["moe"] = moe_param_specs()
    else:
        layer.update({
            "ffn_in_w": P(None, MODEL_AXIS), "ffn_in_b": P(MODEL_AXIS),
            "ffn_out_w": P(MODEL_AXIS, None), "ffn_out_b": P(),
        })
    return {
        "tok_emb": P(None, MODEL_AXIS),
        "pos_emb": P(),
        "type_emb": P(),
        "emb_ln": {"g": P(), "b": P()},
        "layers": [dict(layer) for _ in range(cfg.num_layers)],
        "mlm_bias": P(),
    }


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _dropout(x, rate, key):
    """Inverted dropout from 16-bit random draws: half the RNG bytes of
    bernoulli's f32 uniforms (measured ~3 ms/step at BERT-base shapes).
    Keep probability quantizes to 1/65536 — immaterial for dropout."""
    thresh = np.uint16(round((1.0 - rate) * 65536) - 1)
    bits = jax.random.bits(key, x.shape, jnp.uint16)
    return jnp.where(bits <= thresh, x / (1.0 - rate), 0)


def _dense_attention(q, k, v):
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _attention(q, k, v, mesh, cfg: BertConfig):
    """[B,H,T,D] attention. seq axis -> ring attention; otherwise a Pallas
    flash kernel on TPU (blocked online-softmax, no [B,H,T,T] in HBM;
    sharded over data/model axes via shard_map) with a dense fallback."""
    if mesh is not None and SEQ_AXIS in mesh.axis_names:
        return ring_attention(q, k, v, mesh)
    impl = cfg.attention_impl
    if impl == "auto":
        # measured on v5e (tools/probe_bert): XLA dense attention beats
        # the Pallas flash kernel ~2x at T=512 (head_dim 64 pads the
        # kernel's 128-wide MXU lane), but dense materializes the
        # [B,H,T,T] scores, whose memory grows quadratically — at long T
        # flash's O(T) memory wins regardless of the lane penalty. The
        # kernel is TPU-Mosaic-only and needs T divisible by its 128
        # block; anything else stays dense.
        t = q.shape[-2]
        impl = ("flash" if t > 1024 and t % 128 == 0
                and jax.default_backend() == "tpu" else "dense")
    if impl == "dpa":
        # jax.nn.dot_product_attention expects [B,T,H,D]
        qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        out = jax.nn.dot_product_attention(qt, kt, vt)
        return jnp.swapaxes(out, 1, 2)
    if impl != "flash":
        return _dense_attention(q, k, v)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def local(q_, k_, v_):
        return _pallas_flash(q_, k_, v_, causal=False, sm_scale=scale)

    if mesh is None or math.prod(mesh.devices.shape) == 1:
        return local(q, k, v)
    # batch over 'data', heads over 'model': both are embarrassingly
    # parallel for attention, so the kernel runs per-shard unchanged
    spec = spec_for(mesh, DATA_AXIS, MODEL_AXIS, None, None)
    # check_vma=False: pallas_call outputs carry no varying-axes info
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


def encoder_layer(lp, x, cfg: BertConfig, mesh=None, li=0,
                  deterministic=True, rng=None):
    """One transformer encoder block (post-LN like original BERT).
    x: [B, T, H] in compute dtype -> ([B, T, H], aux_loss scalar).
    aux_loss is the MoE load-balancing loss (0.0 for dense FFN layers)."""
    dtype = x.dtype
    b, t = x.shape[0], x.shape[1]
    nh, hd = cfg.num_heads, cfg.head_dim
    qkv = x @ lp["qkv_w"].astype(dtype) + lp["qkv_b"].astype(dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    to_heads = lambda a: jnp.transpose(  # noqa: E731
        a.reshape(b, t, nh, hd), (0, 2, 1, 3))
    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    att = _attention(q, k, v, mesh, cfg)
    att = jnp.transpose(att, (0, 2, 1, 3)).reshape(b, t, nh * hd)
    att = att @ lp["out_w"].astype(dtype) + lp["out_b"].astype(dtype)
    if not deterministic and cfg.dropout > 0 and rng is not None:
        att = _dropout(att, cfg.dropout, jax.random.fold_in(rng, 2 * li))
    x = _layer_norm((x + att).astype(jnp.float32), lp["ln1"]["g"],
                    lp["ln1"]["b"], cfg.layer_norm_eps).astype(dtype)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in lp:
        from deeplearning4j_tpu.parallel.moe import moe_apply

        # gate_w stays f32: moe_apply's gating math runs in f32 and
        # pre-truncating the gate weights to bf16 would move routing
        # decisions near ties
        mp = {k: (v if k == "gate_w" else v.astype(dtype))
              for k, v in lp["moe"].items()}
        hdn, aux = moe_apply(mp, x.reshape(b * t, -1), k=cfg.moe_k,
                             capacity_factor=cfg.moe_capacity)
        hdn = hdn.reshape(b, t, -1)
        aux = aux.astype(jnp.float32)
    else:
        hdn = jax.nn.gelu(x @ lp["ffn_in_w"].astype(dtype)
                          + lp["ffn_in_b"].astype(dtype))
        hdn = hdn @ lp["ffn_out_w"].astype(dtype) \
            + lp["ffn_out_b"].astype(dtype)
    if not deterministic and cfg.dropout > 0 and rng is not None:
        hdn = _dropout(hdn, cfg.dropout,
                       jax.random.fold_in(rng, 2 * li + 1))
    x = _layer_norm((x + hdn).astype(jnp.float32), lp["ln2"]["g"],
                    lp["ln2"]["b"], cfg.layer_norm_eps).astype(dtype)
    return x, aux


def embed(params, cfg: BertConfig, tokens, type_ids=None):
    """tokens [B, T] -> embedded+LN'd activations [B, T, H] in compute
    dtype."""
    t = tokens.shape[1]
    x = params["tok_emb"][tokens]                       # [B,T,H] f32 gather
    x = x + params["pos_emb"][None, :t, :]
    if type_ids is not None:
        x = x + params["type_emb"][type_ids]
    x = _layer_norm(x, params["emb_ln"]["g"], params["emb_ln"]["b"],
                    cfg.layer_norm_eps)
    return x.astype(jnp.dtype(cfg.compute_dtype))


def forward_with_aux(params, cfg: BertConfig, tokens, type_ids=None,
                     mesh=None, deterministic=True, rng=None):
    """tokens: [B, T] int32 -> (hidden states [B, T, H], total MoE aux
    loss)."""
    x = embed(params, cfg, tokens, type_ids)
    aux_total = jnp.zeros((), jnp.float32)
    for li, lp in enumerate(params["layers"]):
        x, aux = encoder_layer(lp, x, cfg, mesh=mesh, li=li,
                               deterministic=deterministic, rng=rng)
        aux_total = aux_total + aux
    return x, aux_total


def forward(params, cfg: BertConfig, tokens, type_ids=None, mesh=None,
            deterministic=True, rng=None):
    """tokens: [B, T] int32 -> hidden states [B, T, H]."""
    return forward_with_aux(params, cfg, tokens, type_ids, mesh,
                            deterministic, rng)[0]


def mlm_loss(params, cfg: BertConfig, tokens, labels, mesh=None,
             deterministic=False, rng=None):
    """Masked-LM loss; labels = -100 for unmasked positions (ignored).
    LM head ties tok_emb."""
    hs, aux = forward_with_aux(params, cfg, tokens, mesh=mesh,
                               deterministic=deterministic, rng=rng)
    logits = (hs.astype(jnp.float32) @ params["tok_emb"].T
              + params["mlm_bias"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    tok_lp = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    n = jnp.maximum(jnp.sum(valid), 1)
    loss = -jnp.sum(jnp.where(valid, tok_lp, 0.0)) / n
    return loss + cfg.moe_aux_weight * aux


def mlm_loss_masked(params, cfg: BertConfig, tokens, positions, mlm_labels,
                    weights, mesh=None, deterministic=False, rng=None):
    """Masked-LM loss scoring ONLY the masked positions (the standard BERT
    pretraining head: TF BERT's max_predictions_per_seq gather). The full
    [B,T,V] logits tensor is never built — at BERT-base shapes that tensor
    is ~1 GB in f32 and its log_softmax is pure HBM traffic (the round-1
    MFU sink alongside dense attention).

    positions [B,M] int32, mlm_labels [B,M] int32, weights [B,M] f32
    (0 = padding when a row has fewer than M masked tokens)."""
    hs, aux = forward_with_aux(params, cfg, tokens, mesh=mesh,
                               deterministic=deterministic, rng=rng)
    gathered = jnp.take_along_axis(hs, positions[..., None], axis=1)
    # bf16 x bf16 MXU matmul with f32 accumulation
    logits = jnp.einsum(
        "bmh,vh->bmv", gathered, params["tok_emb"].astype(gathered.dtype),
        preferred_element_type=jnp.float32) + params["mlm_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    tok_lp = jnp.take_along_axis(logp, mlm_labels[..., None],
                                 axis=-1)[..., 0]
    n = jnp.maximum(jnp.sum(weights), 1.0)
    loss = -jnp.sum(tok_lp * weights) / n
    return loss + cfg.moe_aux_weight * aux


def mlm_max_preds(seq_len):
    """Stable masked-slot count (like TF BERT max_predictions_per_seq) so
    the executable shape never depends on the random mask draw. Shared by
    BertTrainer and BertPipelineTrainer — their step-for-step parity
    depends on the identical formula."""
    return max(1, int(0.15 * seq_len) + 1)


def mlm_gather(labels, max_preds=None):
    """Host-side: labels [B,T] with -100 at unmasked positions ->
    (positions [B,M], mlm_labels [B,M], weights [B,M]) for
    mlm_loss_masked. M = max_preds or the max masked count in the batch."""
    labels = np.asarray(labels)
    b, t = labels.shape
    counts = (labels >= 0).sum(axis=1)
    m = int(max_preds or max(int(counts.max()), 1))
    positions = np.zeros((b, m), np.int32)
    mlm_labels = np.zeros((b, m), np.int32)
    weights = np.zeros((b, m), np.float32)
    for i in range(b):
        pos = np.nonzero(labels[i] >= 0)[0][:m]
        positions[i, :len(pos)] = pos
        mlm_labels[i, :len(pos)] = labels[i, pos]
        weights[i, :len(pos)] = 1.0
    return positions, mlm_labels, weights


class BertTrainer:
    """One donated jitted step: fwd + bwd + Adam, with dp/tp/sp shardings.
    Thin over `parallel.step_engine.StepEngine`, which `CausalLMTrainer`
    shares: this class brings the masked-LM loss and its batch."""

    def __init__(self, cfg: BertConfig, mesh: Mesh, lr=1e-4, seed=0):
        self.cfg = cfg
        self.mesh = mesh
        self.lr = lr
        key = jax.random.key(seed)
        self.batch_sh = NamedSharding(mesh, spec_for(mesh, DATA_AXIS,
                                                     SEQ_AXIS))
        # masked-position tensors [B,M]: data-sharded only (M != seq axis)
        self.pos_sh = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))

        def step(params, opt, tokens, positions, mlm_labels, weights, rng,
                 t):
            return self._step_math(params, opt, tokens, positions,
                                   mlm_labels, weights, rng, t)

        self._engine = StepEngine(
            mesh, init_params(cfg, key), param_specs(cfg), step,
            (self.batch_sh, self.pos_sh, self.pos_sh, self.pos_sh,
             NamedSharding(mesh, P())))
        self.p_sh, self.o_sh = self._engine.p_sh, self._engine.o_sh

    # the state lives in the engine; the trainer's names for it stay
    params = property(lambda self: self._engine.params,
                      lambda self, v: setattr(self._engine, "params", v))
    opt = property(lambda self: self._engine.opt,
                   lambda self, v: setattr(self._engine, "opt", v))
    _step = property(lambda self: self._engine.steps,
                     lambda self, v: setattr(self._engine, "steps", v))

    def _step_math(self, params, opt, tokens, positions, mlm_labels,
                   weights, rng, t):
        cfg, mesh = self.cfg, self.mesh
        return loss_and_adam(
            lambda p: mlm_loss_masked(
                p, cfg, tokens, positions, mlm_labels, weights, mesh=mesh,
                deterministic=False, rng=rng),
            params, opt, self.lr, t)

    def _build(self):
        return self._engine.build()

    def train_step(self, tokens, labels):
        """tokens [B,T] int32; labels [B,T] with -100 at unmasked
        positions. The masked-position gather happens host-side so the
        device step only scores the ~15% of positions that matter."""
        # rbg PRNG: XLA's RngBitGenerator is far cheaper than threefry
        # for the ~380M dropout bits a BERT-base step draws (~17 ms/step
        # on v5e); dropout only needs statistical, not
        # reproducible-forever, randomness
        return self._engine.run(
            lambda: mlm_gather(
                labels,
                max_preds=self._max_preds(np.asarray(tokens).shape[1])),
            lambda gathered, steps: (
                jnp.asarray(tokens, jnp.int32), *gathered,
                jax.random.key(steps + 1, impl="rbg")))[0]

    def _max_preds(self, seq_len):
        return mlm_max_preds(seq_len)


def synthetic_mlm_batch(cfg: BertConfig, batch, seq_len, seed=0,
                        mask_frac=0.15):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(3, cfg.vocab_size, (batch, seq_len))
    labels = np.full((batch, seq_len), -100, np.int64)
    n_mask = max(1, int(mask_frac * seq_len))
    for i in range(batch):
        pos = rng.choice(seq_len, n_mask, replace=False)
        labels[i, pos] = tokens[i, pos]
        tokens[i, pos] = 1  # [MASK]
    return tokens.astype(np.int32), labels.astype(np.int64)
