"""Plain references for the two BERT configurations: straightforward
`jax.numpy`, float32 with every matmul at `Precision.HIGHEST`, no kernels, no
cache, no batching of requests, and nothing imported from the program.

It follows Devlin et al. 2018 (post-LN encoder block, learned positions, tied
head) with the departures the program makes, which the comparison has to share
to compare anything at all:

- GELU is the tanh approximation (`jax.nn.gelu`'s default), not BERT's erf.
- Training scores only the masked positions (at most `int(0.15*T)+1` a row),
  has no next-sentence head and adds no segment embedding.
- Dropout draws 16-bit words from an `rbg` key made of the step number, folded
  with `2*layer` (attention output) and `2*layer+1` (FFN output); the draw is
  part of what the configuration states, so the reference makes the same one.
- The causal decoder is the same block with a causal mask, no segment
  embedding and greedy argmax over the tied head.
- Weights: normal(0, 0.02) from `jax.random.key(seed)` split as the program
  splits it; this module makes its own and takes none from the program.

`mode` lowers the precision. "f32" is the reference. "bf16" keeps activations
in bfloat16 and multiplies in one bfloat16 pass with float32 accumulation:
what the training configuration states and what the TPU's default does to the
serving configuration's float32 matmuls. "fp8" also rounds both operands of
every matmul to float8_e4m3 with one scale a tensor: the control that has to
fail the comparison, the nearest precision below both configurations'.
"bf16_all" does the layer norms in bfloat16 as well (a witness, not a limit).
The work is done layer by layer, so that the reference fits beside nothing
else on a 16 GB chip and compiles one layer, not twenty-four."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8
STD = 0.02


# -- weights -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def init_params(key, vocab, hidden, ffn, layers, max_len, ffn_gain=1.0):
    """`ffn_gain` multiplies the two FFN matrices of every layer (the serving
    configuration's 4: see its file for why)."""
    keys = jax.random.split(key, 6 + layers)
    norm = lambda k, shape, gain=1.0: (  # noqa: E731
        jax.random.normal(k, shape, jnp.float32) * STD * gain)
    ln = lambda: {"g": jnp.ones((hidden,)), "b": jnp.zeros((hidden,))}  # noqa: E731
    params = {"tok_emb": norm(keys[0], (vocab, hidden)),
              "pos_emb": norm(keys[1], (max_len, hidden)),
              "type_emb": norm(keys[2], (2, hidden)),
              "emb_ln": ln(), "layers": [], "mlm_bias": jnp.zeros((vocab,))}
    for i in range(layers):
        k = jax.random.split(keys[6 + i], 6)
        params["layers"].append({
            "qkv_w": norm(k[0], (hidden, 3 * hidden)),
            "qkv_b": jnp.zeros((3 * hidden,)),
            "out_w": norm(k[1], (hidden, hidden)),
            "out_b": jnp.zeros((hidden,)),
            "ln1": ln(), "ln2": ln(),
            "ffn_in_w": norm(k[2], (hidden, ffn), ffn_gain),
            "ffn_in_b": jnp.zeros((ffn,)),
            "ffn_out_w": norm(k[3], (ffn, hidden), ffn_gain),
            "ffn_out_b": jnp.zeros((hidden,))})
    return params


def leaf_names(tree):
    """{"layers.3.qkv_w": leaf, ...}: one naming for both sides."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    name = lambda path: ".".join(  # noqa: E731
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
    return {name(path): leaf for path, leaf in flat}


# -- the block -----------------------------------------------------------------

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
    out = jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)


def _act(mode):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _ln(x, p, eps, mode):
    # the training configuration normalises in float32 whatever the
    # activations are; the decoder's bfloat16 control does it in bfloat16
    dt = jnp.bfloat16 if mode == "bf16_all" else jnp.float32
    x = x.astype(dt)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * p["g"].astype(dt) \
        + p["b"].astype(dt)
    return y


def _dropout(x, rate, key, full_rows, r0):
    """The rows r0.. of the draw for the whole batch: the step draws one
    [rows, T, H] array of words for all its rows."""
    thresh = np.uint16(round((1.0 - rate) * 65536) - 1)
    bits = jax.random.bits(key, (full_rows,) + x.shape[1:], jnp.uint16)
    bits = jax.lax.dynamic_slice_in_dim(bits, r0, x.shape[0], 0)
    return jnp.where(bits <= thresh, x / (1.0 - rate), 0)


def block(lp, x, heads, eps, mode, causal=False, drop=None):
    """One post-LN encoder block. x [B,T,H]. drop = (rate, rng, layer, rows of
    the whole batch, first row of x in it) or None."""
    mm_mode = "bf16" if mode == "bf16_all" else mode
    act = _act(mm_mode)
    b, t, h = x.shape
    hd = h // heads
    qkv = _mm("bth,hk->btk", x, lp["qkv_w"], mm_mode) + lp["qkv_b"].astype(act)
    q, k, v = (a.reshape(b, t, heads, hd) for a in jnp.split(qkv, 3, -1))
    s = _mm("bqnd,bknd->bnqk", q, k, mm_mode) / math.sqrt(hd)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    att = _mm("bnqk,bknd->bqnd", w, v, mm_mode).reshape(b, t, h)
    att = _mm("bth,hk->btk", att, lp["out_w"], mm_mode) + lp["out_b"].astype(act)
    if drop is not None:
        rate, rng, li, full_rows, r0 = drop
        att = _dropout(att, rate, jax.random.fold_in(rng, 2 * li), full_rows,
                       r0)
    x = _ln(x + att, lp["ln1"], eps, mode).astype(act)
    f = jax.nn.gelu(_mm("bth,hf->btf", x, lp["ffn_in_w"], mm_mode)
                    + lp["ffn_in_b"].astype(act))
    f = _mm("btf,fh->bth", f, lp["ffn_out_w"], mm_mode) + lp["ffn_out_b"].astype(act)
    if drop is not None:
        f = _dropout(f, rate, jax.random.fold_in(rng, 2 * li + 1), full_rows,
                     r0)
    return _ln(x + f, lp["ln2"], eps, mode).astype(act)


def embed(ep, tokens, pos, eps, mode):
    x = ep["tok_emb"][tokens] + ep["pos_emb"][pos]
    mm_mode = "bf16" if mode == "bf16_all" else mode
    return _ln(x, ep["emb_ln"], eps, mode).astype(_act(mm_mode))


# -- masked-LM training: loss, gradients, Adam ---------------------------------

def mlm_gather(labels, max_preds):
    """labels [B,T] with -100 where unmasked -> positions, labels, weights
    [B,M], first M masked positions of each row in order."""
    labels = np.asarray(labels)
    b = labels.shape[0]
    pos = np.zeros((b, max_preds), np.int32)
    lab = np.zeros((b, max_preds), np.int32)
    w = np.zeros((b, max_preds), np.float32)
    for i in range(b):
        p = np.nonzero(labels[i] >= 0)[0][:max_preds]
        pos[i, :len(p)], lab[i, :len(p)], w[i, :len(p)] = p, labels[i, p], 1.0
    return pos, lab, w


def _head_logits(x, tok_emb, mode):
    """The tied head: x [..., H] against every row of the embedding."""
    if mode == "f32":
        return jnp.einsum("...h,vh->...v", x, tok_emb, precision=HI)
    if mode == "fp8":
        x, tok_emb = _q8(x.astype(jnp.float32)), _q8(tok_emb)
    return jnp.einsum("...h,vh->...v", x.astype(jnp.bfloat16),
                      tok_emb.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _head_loss(hp, x, positions, labels, weights, denom, mode):
    g = jnp.take_along_axis(x, positions[..., None], axis=1)
    logits = _head_logits(g, hp["tok_emb"], mode)
    logp = jax.nn.log_softmax(logits + hp["mlm_bias"], axis=-1)
    tok = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(tok * weights) / denom


class MlmReference:
    """Adam steps of masked-LM training, layer by layer and in blocks of
    rows, the gradient summed over the blocks.

    `keep_rows` keeps only the first so many rows of every batch and takes
    the mean over them: the fault 'part of the batch left out' (on four
    chips, with a quarter kept, 'the exchange between chips left out')."""

    def __init__(self, sizes, seed_key, lr, dropout, mode="f32",
                 keep_rows=None, block_rows=16):
        self.lr, self.rate, self.mode = lr, dropout, mode
        self.keep_rows, self.block_rows = keep_rows, block_rows
        self.params = init_params(seed_key, sizes["vocab_size"],
                                  sizes["hidden"], sizes["ffn"],
                                  sizes["num_layers"], sizes["max_len"])
        tm = jax.tree_util.tree_map
        self.p0 = tm(jnp.copy, self.params)
        self.m, self.v = tm(jnp.zeros_like, self.params), \
            tm(jnp.zeros_like, self.params)
        self.t = 0
        heads, eps, rate = sizes["num_heads"], sizes["layer_norm_eps"], dropout

        def fwd(lp, x, rng, li, full_rows, r0):
            drop = (rate, rng, li, full_rows, r0) if rate > 0 else None
            return block(lp, x, heads, eps, mode, drop=drop)

        def bwd(lp, x, rng, li, full_rows, r0, g):
            return jax.vjp(lambda lp_, x_: fwd(lp_, x_, rng, li, full_rows,
                                               r0), lp, x)[1](g)

        def emb(ep, tokens):
            return embed(ep, tokens, jnp.arange(tokens.shape[1])[None, :],
                         eps, mode)

        def emb_bwd(ep, tokens, g):
            return jax.vjp(lambda ep_: emb(ep_, tokens), ep)[1](g)[0]

        def adam(p, m, v, g, t):
            m = tm(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
            v = tm(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, v, g)
            tt = t + 1
            p = tm(lambda p_, m_, v_: p_ - lr * (m_ / (1 - B1 ** tt))
                   / (jnp.sqrt(v_ / (1 - B2 ** tt)) + ADAM_EPS), p, m, v)
            return p, m, v

        self._fwd = jax.jit(fwd, static_argnums=(4,))
        self._bwd = jax.jit(bwd, static_argnums=(4,))
        self._emb, self._emb_bwd = jax.jit(emb), jax.jit(emb_bwd)
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(_head_loss, mode=mode), argnums=(0, 1)))
        self._adam = jax.jit(adam, donate_argnums=(0, 1, 2))
        self._add = jax.jit(lambda a, b: tm(jnp.add, a, b),
                            donate_argnums=(0,))

    def _block_grads(self, tokens, pos, lab, w, denom, rng, full_rows, r0):
        p = self.params
        ep = {k: p[k] for k in ("tok_emb", "pos_emb", "emb_ln")}
        r0 = jnp.int32(r0)
        xs = [self._emb(ep, tokens)]
        for li, lp in enumerate(p["layers"]):
            xs.append(self._fwd(lp, xs[-1], rng, jnp.int32(li), full_rows,
                                r0))
        hp = {k: p[k] for k in ("tok_emb", "mlm_bias")}
        loss, (g_head, g_x) = self._head(hp, xs.pop(), pos, lab, w, denom)
        g_layers = [None] * len(p["layers"])
        for li in reversed(range(len(p["layers"]))):
            g_layers[li], g_x = self._bwd(p["layers"][li], xs.pop(), rng,
                                          jnp.int32(li), full_rows, r0, g_x)
        g_emb = self._emb_bwd(ep, tokens, g_x)
        return loss, {"tok_emb": g_emb["tok_emb"] + g_head["tok_emb"],
                      "pos_emb": g_emb["pos_emb"],
                      "type_emb": jnp.zeros_like(p["type_emb"]),
                      "emb_ln": g_emb["emb_ln"], "layers": g_layers,
                      "mlm_bias": g_head["mlm_bias"]}

    def step(self, tokens, labels):
        """One step on the batch; returns (loss, {leaf: gradient norm}). The
        first step's gradient stays in `self.g1` until it is taken."""
        full_rows = tokens.shape[0]
        pos, lab, w = mlm_gather(labels,
                                 max(1, int(0.15 * tokens.shape[1]) + 1))
        if self.keep_rows is not None:
            tokens, pos, lab, w = (a[:self.keep_rows]
                                   for a in (tokens, pos, lab, w))
        denom = jnp.float32(max(float(w.sum()), 1.0))
        rng = jax.random.key(self.t + 1, impl="rbg")
        loss, grads = 0.0, None
        for r0 in range(0, tokens.shape[0], self.block_rows):
            sl = slice(r0, r0 + self.block_rows)
            l_, g_ = self._block_grads(jnp.asarray(tokens[sl], jnp.int32),
                                       pos[sl], lab[sl], w[sl], denom, rng,
                                       full_rows, r0)
            loss += float(l_)
            grads = g_ if grads is None else self._add(grads, g_)
        norms = tree_norms(grads)
        if self.t == 0:
            self.g1 = grads       # the first gradient, whole
        t = jnp.int32(self.t)
        p, rest = self.params, [k for k in self.params if k != "layers"]
        sub = lambda d: {k: d[k] for k in rest}  # noqa: E731
        new_p, new_m, new_v = self._adam(sub(p), sub(self.m), sub(self.v),
                                         sub(grads), t)
        for d in (new_p, new_m, new_v):
            d["layers"] = []
        for li in range(len(p["layers"])):
            out = self._adam(p["layers"][li], self.m["layers"][li],
                             self.v["layers"][li], grads["layers"][li], t)
            for d, o in zip((new_p, new_m, new_v), out):
                d["layers"].append(o)
        self.params, self.m, self.v = new_p, new_m, new_v
        self.t += 1
        return loss, norms

    def change_norms(self):
        return tree_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, self.params, self.p0))


def unfuse(tree):
    """The published model has a query, a key and a value matrix and bias;
    the program keeps them as one `qkv_w` and one `qkv_b`. Split them back,
    so that each is a leaf of its own: the key's bias has no gradient under
    softmax and moves by round-off alone, which the fused leaf would hide
    from the rule that leaves such leaves out."""
    def layer(lp):
        lp = dict(lp)
        for name in ("qkv_w", "qkv_b"):
            fused = lp.pop(name)      # numpy stays on the host
            split = np.split if isinstance(fused, np.ndarray) else jnp.split
            lp.update({f"{name}.{k}": part
                       for k, part in zip("qkv", split(fused, 3, axis=-1))})
        return lp
    return dict(tree, layers=[layer(lp) for lp in tree["layers"]])


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def tree_norms(tree):
    """{leaf name: its norm}, the fused query-key-value leaves split."""
    return {k: float(v) for k, v in leaf_names(_norms(unfuse(tree))).items()}


# -- the causal decoder: logits of a whole sequence at once --------------------

def decoder_logits(params, sizes, tokens, mode="f32"):
    """tokens [N,T] -> logits [N,T,V] of the next token at every position,
    float32. Lower modes: "fp8" (the control), "bf16_all" (weights,
    activations, layer norms and softmax in bfloat16)."""
    heads, eps = sizes["num_heads"], sizes["layer_norm_eps"]
    fwd = jax.jit(lambda lp, x: block(lp, x, heads, eps, mode, causal=True))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = embed(params, tokens, jnp.arange(tokens.shape[1])[None, :], eps, mode)
    for lp in params["layers"]:
        x = fwd(lp, x)
    logits = _head_logits(x, params["tok_emb"], mode)
    return logits + params["mlm_bias"]
