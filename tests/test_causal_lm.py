"""The causal LM block, the dropless expert share and the step engine at a
small size on the CPU, against the plain reference
`benchmark/reference/laguna_plain.py` (which imports nothing from the
program): widths 64, 8 experts top-2, window 8 at 32 positions, five layers
in the published pattern (full dense, three sliding sparse, full sparse),
seeded weights."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import train_lm  # noqa: E402
from benchmark.reference import laguna_plain as plain  # noqa: E402
from deeplearning4j_tpu.models import causal_lm as lm  # noqa: E402
from deeplearning4j_tpu.parallel import moe  # noqa: E402
from deeplearning4j_tpu.parallel.mesh import MeshConfig  # noqa: E402

with open(os.path.join(ROOT, "tests", "benchmark", "data", "toy-lm",
                       "configs", "toy-laguna.json")) as f:
    TOY = json.load(f)
PUB = TOY["published"]
SEQ = 32


def config(held=(0, 4), dtype="float32"):
    return lm.CausalLMConfig.from_published(
        PUB, num_layers=5, experts_held=held, vocab_held=64,
        compute_dtype=dtype)


def sizes(held=(0, 4)):
    toy = dict(TOY, model=dict(TOY["model"], experts_held=list(held)))
    return train_lm.reference_sizes(toy)


def batch(seed=0, rows=2):
    return next(train_lm.traffic_lm.lm_batches(
        {"rows": rows, "seq": SEQ}, 64, seed))


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30)


def test_layers_follow_the_published_pattern():
    cfg = config()
    assert [(s.attention, s.heads, s.mlp) for s in cfg.layers] == [
        ("full", 4, "dense"), ("sliding", 6, "sparse"),
        ("sliding", 6, "sparse"), ("sliding", 6, "sparse"),
        ("full", 4, "sparse")]
    assert cfg.sparse_layers == [1, 2, 3, 4]
    p = lm.init_params(cfg, jax.random.key(0))
    assert p["layers"][1]["wq"].shape == (64, 6 * 16)
    assert p["layers"][4]["wg"].shape == (64, 4)
    assert p["layers"][2]["moe"]["gate"].shape == (4, 64, 32)
    assert p["layers"][2]["moe"]["router"].shape == (64, 8)
    assert "moe" not in p["layers"][0] and p["embed"].shape == (64, 64)


def test_logits_and_loss_match_the_plain_reference():
    cfg, tok_lab = config(), batch()
    params = lm.init_params(cfg, jax.random.key(1))
    ref_p = train_lm.rename(params)
    got = lm.logits(params, cfg, jnp.asarray(tok_lab[0]))
    nll = 0.0
    for r in range(2):
        want, _ = plain.row_logits(ref_p, sizes(), jnp.asarray(tok_lab[0][r]),
                                   block_q=8)
        assert close(got[r], want, 2e-5)
        nll += float(plain.row_nll(ref_p, sizes(), jnp.asarray(tok_lab[0][r]),
                                   jnp.asarray(tok_lab[1][r]), block_q=8)[0])
    loss, (choices, dropped) = lm.lm_loss(params, cfg, *map(jnp.asarray,
                                                           tok_lab))
    assert abs(float(loss) - nll / (2 * (SEQ - 1))) < 1e-5
    assert choices.shape == (4, 4) and int(dropped.sum()) == 0


def test_the_reference_draws_the_weights_the_program_is_fed():
    """The weights of a run are the reference's own, from the seed, in its
    layout; renamed they are a tree the program's trainer takes."""
    p0 = plain.draw_params(sizes(), 2 ** 31 + 5)
    again, other = plain.draw_params(sizes(), 2 ** 31 + 5), \
        plain.draw_params(sizes(), 6)
    names = plain.leaf_names(p0)
    for name, leaf in names.items():
        assert leaf.dtype == np.float32
        assert (leaf == plain.leaf_names(again)[name]).all(), name
        if leaf.ndim > 1:
            assert (leaf != plain.leaf_names(other)[name]).any(), name
    shapes = jax.eval_shape(lambda: lm.init_params(config(),
                                                   jax.random.key(0)))
    want = {k: v.shape for k, v in plain.leaf_names(
        train_lm.rename(shapes)).items()}
    assert {k: v.shape for k, v in names.items()} == want
    # rows of unit scale, matrices of 0.02, gains of one
    assert abs(float(np.std(p0["embed_tokens"])) - 1.0) < 0.05
    assert abs(float(np.std(p0["lm_head"])) - 0.02) < 0.002
    assert abs(float(np.std(p0["layers"][2]["experts"]["up_proj"]))
               - 0.02) < 0.002
    assert (p0["layers"][3]["input_layernorm"] == 1).all()
    # the two renamings undo each other, leaf for leaf
    back = plain.leaf_names(train_lm.rename(train_lm.to_program(p0)))
    assert all(back[k] is v for k, v in names.items())
    assert jax.tree_util.tree_structure(train_lm.to_program(p0)) == \
        jax.tree_util.tree_structure(shapes)


def test_the_rate_warms_up():
    """Adam's first step moves every element by the rate: a quarter of `lr`
    at the first of four warm-up steps, all of it after them."""
    mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
    for warmup, moved in ((4, 2.5e-4), (0, 1e-3)):
        trainer = lm.CausalLMTrainer(config(), mesh, lr=1e-3, seed=1,
                                     warmup_steps=warmup)
        before = np.asarray(trainer.params["head"])
        trainer.train_step(*batch())
        step = np.abs(np.asarray(trainer.params["head"]) - before)
        assert np.median(step) == pytest.approx(moved, rel=1e-3)


def share_is_dense(held, n=2 * SEQ):
    """Whether a sparse layer over `n` tokens (top-2 of 8 experts, `held`
    here) runs its products dense: half the experts held make the default
    buffer, twice the even share, the worst case."""
    return moe.moe_share_dense(n, 2, moe.moe_share_rows(n, 2, 8, held[1]))


# (0, 4): the 64 tokens' share is dense; (0, 2): sorted into a buffer of 64
# rows and grouped, as every step of a real size is. `draw` seeds the
# weights: with two small experts held, one choice that bfloat16's rounding
# flips (draw 3 flips one of 141 in the first step) moves a sixteenth of an
# expert's tokens and its gradient by 0.2 to 0.5 of its largest entry, in
# either form; draw 4 flips none in three steps and reads 0.014.
@pytest.mark.parametrize("dtype, tol, change_tol, warmup, held, draw", [
    ("float32", 2e-4, 0.05, 0, (0, 4), 3),
    ("bfloat16", 0.1, 0.5, 0, (0, 4), 3),
    ("float32", 2e-4, 0.05, 4, (0, 4), 3),
    ("float32", 2e-4, 0.05, 0, (0, 2), 3),
    ("bfloat16", 0.1, 0.5, 0, (0, 2), 4)])
def test_first_gradient_and_three_adam_steps_match(dtype, tol, change_tol,
                                                   warmup, held, draw):
    """From the reference's own weights, loaded into the trainer."""
    assert share_is_dense(held) == (held == (0, 4))
    cfg = config(held, dtype=dtype)
    mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
    p0 = plain.draw_params(sizes(held), draw)
    trainer = lm.CausalLMTrainer(cfg, mesh, lr=1e-3,
                                 params=train_lm.to_program(p0),
                                 warmup_steps=warmup)
    ref = plain.LmReference(sizes(held), p0, 1e-3, block_q=8,
                            warmup_steps=warmup)
    batches = train_lm.traffic_lm.lm_batches({"rows": 2, "seq": SEQ}, 64, 5)
    for i in range(3):
        tok, lab = next(batches)
        loss = float(trainer.train_step(tok, lab))
        want, _, choices = ref.step(tok, lab)
        assert abs(loss - want) < tol * 0.1 * want
        gap = train_lm.held_choices_gap(
            [np.asarray(trainer.router_counts[0])], [choices])
        assert gap <= (0.0 if dtype == "float32" else 0.05)
        if i == 0:
            g1 = jax.tree_util.tree_map(
                lambda m: m / np.float32(0.1),
                train_lm.rename(jax.device_get(trainer.opt["m"])))
            for name, want_g in plain.leaf_names(ref.g1).items():
                assert close(plain.leaf_names(g1)[name], want_g, tol), name
    got = plain.leaf_names(train_lm.rename(jax.device_get(trainer.params)))
    # an element whose gradient is nought to rounding moves under Adam by
    # its sign alone: the change is compared as a whole, leaf by leaf
    for name, want_p in plain.leaf_names(ref.params).items():
        start = plain.leaf_names(p0)[name]
        moved = np.asarray(want_p) - start
        off = np.linalg.norm(got[name] - start - moved)
        assert off <= change_tol * np.linalg.norm(moved), (name, off)


def _layer_input(seed=0, n=2 * SEQ):
    return jax.random.normal(jax.random.key(seed), (n, 64), jnp.float32)


def _whole_layer(seed=2):
    """An uncut sparse layer's weights in the reference's layout."""
    whole = moe.moe_share_init(jax.random.key(seed), 64, 32, 8, 8)
    shared = lm._mlp_init(jax.random.key(seed + 1), 64, 32, 0.02)
    names = train_lm.MLP_NAMES
    ref_lp = {"router": whole["router"],
              "experts": {names[k]: whole[k] for k in names},
              "shared_expert": {names[k]: shared[k] for k in names}}
    return whole, shared, ref_lp


@pytest.mark.parametrize("count", [1, 2, 8])
def test_the_shares_add_up_to_the_uncut_layer(count):
    """The routed parts that the shares of a layer give (8 shares of one
    expert, 4 of two, or the one share that holds all: `(0, E)` is the
    whole), plus the shared expert once, equal the uncut reference's layer."""
    whole, shared, ref_lp = _whole_layer()
    u = _layer_input()
    total, chosen = lm.mlp_apply(shared, u), 0
    for first in range(0, 8, count):
        share = {"router": whole["router"],
                 **{k: whole[k][first:first + count]
                    for k in ("gate", "up", "down")}}
        y, choices, dropped = moe.moe_share_apply(
            share, u, top_k=2, experts_held=(first, count),
            routed_scale=2.5)
        assert int(dropped) == 0 and choices.shape == (count,)
        total, chosen = total + y, chosen + int(choices.sum())
    want, all_choices = plain.sparse_mlp(ref_lp, u, sizes((0, 8)), "f32")
    assert chosen == 2 * u.shape[0] == int(all_choices.sum())
    assert close(total, want, 2e-5)


@pytest.mark.parametrize("n", [2 * SEQ, 320])
def test_nothing_is_dropped_when_every_token_chooses_one_expert(n):
    """Four of eight experts held: the buffer is the worst case. 64 tokens
    go through every held expert; 320 are past `DENSE_ROWS` and are sorted
    into the buffer, which takes the two full groups."""
    assert share_is_dense((0, 4), n) == (n == 2 * SEQ)
    whole, _, ref_lp = _whole_layer()
    # a router that sends every token to expert 1 first (and 0 second)
    router = jnp.zeros((64, 8)).at[:, 1].set(1.0).at[:, 0].set(0.5)
    u = jnp.abs(_layer_input(n=n))
    share = {"router": router, **{k: whole[k][:4]
                                  for k in ("gate", "up", "down")}}
    y, choices, dropped = moe.moe_share_apply(
        share, u, top_k=2, experts_held=(0, 4), routed_scale=2.5)
    n = u.shape[0]
    assert choices.tolist() == [n, n, 0, 0] and int(dropped) == 0
    want, _ = plain.sparse_mlp(dict(ref_lp, router=router), u,
                               sizes((0, 8)), "f32")
    shared = plain.gated_mlp(ref_lp["shared_expert"], u, "f32")
    assert close(y, want - shared, 2e-5)


def test_a_buffer_that_falls_short_counts_what_it_left_out():
    """Two held experts of eight get twice their even share of rows: with a
    router that sends every token to both, the second's choices do not fit;
    they are counted, and the first's part of the result stands."""
    whole, _, ref_lp = _whole_layer()
    router = jnp.zeros((64, 8)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    u = jnp.abs(_layer_input())
    n = u.shape[0]
    assert moe.moe_share_rows(n, 2, 8, 2) == n
    assert moe.moe_share_rows(n, 2, 8, 8) == 2 * n      # the whole layer
    assert moe.moe_share_rows(16384, 8, 256, 32) == 32768
    share = {"router": router, **{k: whole[k][:2]
                                  for k in ("gate", "up", "down")}}
    y, choices, dropped = moe.moe_share_apply(
        share, u, top_k=2, experts_held=(0, 2), routed_scale=2.5)
    assert choices.tolist() == [n, n] and int(dropped) == n
    # four held experts' buffer takes every choice: there, silence the second
    quiet = {"router": router, "gate": whole["gate"][:4],
             "up": whole["up"][:4], "down": whole["down"][:4].at[1].set(0.0)}
    want, _, none = moe.moe_share_apply(
        quiet, u, top_k=2, experts_held=(0, 4), routed_scale=2.5)
    assert int(none) == 0 and close(y, want, 1e-6)


@pytest.mark.parametrize("n, top_k, rows, dense", [
    (128, 8, 1024, True),           # PR 32's token step: 128 slots
    (256, 8, 2048, True),           # the last batch that is dense
    (257, 8, 2056, False),          # the worst case of a larger batch
    (16384, 8, 32768, False),       # Laguna's step: a quarter of its worst
    (128, 8, 1016, False), (1, 8, 7, False),    # short of the worst case
    (128, 8, 1032, False)])
def test_the_products_form_follows_the_calls_shapes(n, top_k, rows, dense):
    assert moe.moe_share_dense(n, top_k, rows) == dense


def test_every_real_training_call_is_grouped():
    assert moe.moe_share_rows(16384, 8, 256, 32) == 32768 < 16384 * 8
    assert not moe.moe_share_dense(16384, 8, 32768)
    # DeepSeek-V3's share of 16 in 256 trained at any size: an eighth
    assert moe.moe_share_rows(4096, 8, 256, 16) == 4096


@pytest.mark.parametrize("held", [(0, 2), (0, 4)])
def test_a_train_step_keeps_the_grouped_products_short_of_the_worst_case(
        held):
    """The trainer's jitted step, lowered for the TPU (where a grouped
    product is an operation of its own; nothing is compiled): a share that
    asks for less than every choice of every token still sorts and groups,
    forward and backward; half the experts held at 64 tokens is the worst
    case and lowers to none."""
    mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
    trainer = lm.CausalLMTrainer(config(held), mesh, seed=0)
    tok, lab = batch()
    text = jax.jit(trainer._step_math).trace(
        trainer.params, trainer.opt, tok, lab, jnp.int32(0)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert ("ragged_dot" in text) == (not share_is_dense(held))
    assert share_is_dense(held) == (held == (0, 4))


def test_rotary_tables_against_the_closed_form():
    ropes = PUB["rope_parameters"]
    # plain, all 16 dimensions: theta^(-2i/16)
    inv, scale = lm.rope_inv_freq(ropes["sliding_attention"], 16)
    assert scale == 1.0 and np.allclose(
        inv, [10000.0 ** (-2 * i / 16) for i in range(8)], rtol=1e-12)
    # yarn over the first half of the head (8 dimensions, 4 frequencies)
    y = ropes["full_attention"]
    inv, scale = lm.rope_inv_freq(y, 16)
    assert scale == pytest.approx(0.1 * math.log(64) + 1.0, rel=1e-12)
    rot, base, orig = 8, 500000.0, 16
    dim = lambda turns: rot * math.log(  # noqa: E731
        orig / (turns * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(dim(4)), 0), min(math.ceil(dim(1)), rot - 1)
    want = []
    for i in range(rot // 2):
        f = base ** (-2 * i / rot)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 64 * ramp + f * (1 - ramp))
    assert low == 0 and high == 1     # so the blend is not all one side
    assert np.allclose(inv, want, rtol=1e-12) and inv[0] == 1.0
    assert inv[1] == pytest.approx(base ** (-2 / rot) / 64)
    cos, sin = lm.rope_tables(y, 16, 6)
    assert cos.shape == (6, 4) and cos.dtype == jnp.float32
    assert np.allclose(cos[5], scale * np.cos(5 * np.asarray(want)),
                       atol=1e-6)
    # partial rotary: dimensions 8.. of a head pass untouched, 0..7 turn
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 16))
    out = lm.apply_rope(x, cos, sin)
    assert np.array_equal(out[..., 8:], x[..., 8:])
    assert np.allclose(out[0, 5, 0, 0],
                       x[0, 5, 0, 0] * cos[5, 0] - x[0, 5, 0, 4] * sin[5, 0],
                       atol=1e-6)
    # the published full-attention entry: factor 64 over 4,096, 64 dimensions
    pub = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5}
    inv, scale = lm.rope_inv_freq(pub, 128)
    assert inv.shape == (32,) and scale == 1.4158883083359672
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        500000.0 ** (-62 / 64) / 64)


def test_the_sliding_mask_ends_at_the_window():
    """At the published window: key i - 511 is seen, key i - 512 is not."""
    t, window, i = 1024, 512, 900
    q, k, v = (jax.random.normal(jax.random.key(s), (1, t, 2, 8))
               for s in range(3))
    row = lambda v_: lm.causal_attention(  # noqa: E731
        q, k, v_, window=window)[0, i].sum()
    g = np.abs(np.asarray(jax.grad(row)(v))[0]).sum(axis=(1, 2))
    assert g[i - window + 1] > 0 and g[i] > 0
    assert g[i - window] == 0 and g[i + 1] == 0
    full = lambda v_: lm.causal_attention(q, k, v_)[0, i].sum()  # noqa: E731
    g = np.abs(np.asarray(jax.grad(full)(v))[0]).sum(axis=(1, 2))
    assert g[0] > 0 and g[i - window] > 0 and g[i + 1] == 0


def test_grouped_heads_read_their_own_kv_head():
    q, k, v = (jax.random.normal(jax.random.key(s), (1, 8, n, 4))
               for s, n in ((0, 6), (1, 2), (2, 2)))
    out = lm.causal_attention(q, k, v)
    for h in range(6):
        one = lm.causal_attention(q[:, :, h:h + 1], k[:, :, h // 3:h // 3 + 1],
                                  v[:, :, h // 3:h // 3 + 1])
        assert close(out[:, :, h], one[:, :, 0], 1e-5)


@pytest.mark.parametrize("held", [(0, 4), (0, 2)])
def test_router_counts_are_published_one_step_behind(held):
    from deeplearning4j_tpu import telemetry

    old = telemetry.get_registry()
    telemetry.set_registry(telemetry.MetricsRegistry())
    try:
        cfg = config(held)
        mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
        trainer = lm.CausalLMTrainer(cfg, mesh, seed=0)
        trainer.train_step(*batch(1))
        snap = telemetry.get_registry().snapshot()
        assert not any(k.startswith("dl4j_moe") for k in snap)
        first = np.asarray(trainer.router_counts[0])
        trainer.train_step(*batch(2))
        snap = telemetry.get_registry().snapshot()
        # the series carry the publisher's name since the decode engine
        # publishes them too (`model`, the trainer's `name`)
        at = lambda name, layer: snap[  # noqa: E731
            f'{name}{{model="causal_lm",layer="{layer}"}}']
        assert snap['dl4j_moe_steps_total{model="causal_lm"}'] == 1
        assert at("dl4j_moe_choices_total", 1) == 2 * SEQ * 2
        assert at("dl4j_moe_held_choices_total", 4) == first[3].sum()
        assert at("dl4j_moe_load_max_over_mean_sum", 2) == \
            pytest.approx(first[1].max() / first[1].mean())
        trainer.publish_router_counts()
        snap = telemetry.get_registry().snapshot()
        assert snap['dl4j_moe_steps_total{model="causal_lm"}'] == 2
        assert sum(v for k, v in snap.items()
                   if k.startswith("dl4j_moe_dropped_total")) == 0
        # a trainer's steps are grouped (0) but at a toy size whose buffer
        # is the worst case: the series says which form the steps took
        assert snap['dl4j_moe_dense_steps_total{model="causal_lm"}'] == (
            2 if share_is_dense(held) else 0)
    finally:
        telemetry.set_registry(old)


def test_both_trainers_share_one_engine_and_one_adam():
    from deeplearning4j_tpu.models.bert import BertConfig, BertTrainer
    from deeplearning4j_tpu.parallel.step_engine import StepEngine

    mesh = MeshConfig(data=1, devices=jax.devices()[:1]).build()
    bert = BertTrainer(BertConfig(vocab_size=64, hidden=32, num_layers=1,
                                  num_heads=2, ffn=64, max_len=16), mesh)
    causal = lm.CausalLMTrainer(config(), mesh)
    assert isinstance(bert._engine, StepEngine)
    assert isinstance(causal._engine, StepEngine)
    assert bert._build().__wrapped__.__name__ == "step"
    assert causal._engine.build().__wrapped__.__name__ == "step"


# -- what the layer's checkpoint keeps ----------------------------------------

def cut_to(*specs, **widths):
    """The toy configuration cut to these layers, and the rotary tables
    `forward` would hand them at `t` positions."""
    cfg = dataclasses.replace(config(), layers=specs, **widths)
    tables = lambda t: {  # noqa: E731
        kind: lm.rope_tables(cfg.rope[kind], cfg.rotary_width(kind), t)
        for kind in cfg.rope if kind in {s.attention for s in specs}}
    return cfg, tables


@pytest.mark.parametrize("spec, kept", [
    (lm.LayerSpec("full", 4, "dense"), (2, 2, 2, SEQ, 16)),
    (lm.LayerSpec("sliding", 6, "sparse"), (2, 2, 3, SEQ, 16)),
    (lm.LayerSpec("none", 0, "dense"), None)])
def test_the_checkpoint_keeps_the_attention_and_computes_the_same(
        spec, kept, capsys, monkeypatch):
    """`forward`'s policy changes what a layer's checkpoint keeps, never
    what is computed: beside its arguments the layer keeps one array, the
    one `causal_attention` names, and none where there is no attention;
    loss and gradient are the bare checkpoint's bit for bit."""
    cfg, tables = cut_to(spec)
    params = lm.init_params(cfg, jax.random.key(3))
    tok, lab = map(jnp.asarray, batch(4))
    x = params["embed"][tok]
    lp = params["layers"][0]

    def residuals(fn):
        capsys.readouterr()
        jax.ad_checkpoint.print_saved_residuals(fn, lp, x)
        return [line for line in capsys.readouterr().out.splitlines()
                if " from the argument " not in line
                and " from a constant" not in line]

    shape = kept and "f32[" + ",".join(map(str, kept)) + "]"
    layer = lm.checkpointed_layer(cfg, spec, tables(SEQ))
    held = residuals(layer)
    # the checkpoint's own equation: what is named inside it
    named = [line for line in str(jax.make_jaxpr(layer)(lp, x)).splitlines()
             if f"name[name={lm.ATTENTION_SAVED}]" in line]
    if kept is None:
        assert held == [] and named == []
    else:
        assert len(held) == 1 and len(named) == 1, (held, named)
        assert held[0].startswith(shape) and "(causal_attention)" in held[0]
        assert f":{shape} = name[" in named[0]

    # traced anew at each call, so the second sees the policy taken away
    step = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: lm.lm_loss(p, cfg, tok, lab)[0]))(params)
    loss, grad = step()
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    bare_loss, bare_grad = step()
    assert float(loss) == float(bare_loss)
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), grad, bare_grad)
    assert all(jax.tree_util.tree_leaves(same)), same
    assert float(jnp.abs(grad["layers"][0]["mlp_norm"]).sum()) > 0


def test_the_kernel_branch_runs_and_agrees_with_the_plain_one(monkeypatch):
    """The TPU branch with its kernels interpreted on the CPU, a full and a
    sliding layer at the kernel's widths: loss and gradient through
    `forward`'s checkpoint are the plain branch's. A compile alone does not
    show that a step can be called: the kernel's mask tables are values of
    the trace they were made under, and one that escaped it raised only at
    the first call."""
    import functools

    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel)

    cfg, _ = cut_to(lm.LayerSpec("full", 4, "dense"),
                    lm.LayerSpec("sliding", 6, "dense"), head_dim=128,
                    sliding_window=512)
    params = lm.init_params(cfg, jax.random.key(5))
    tok = jax.random.randint(jax.random.key(6), (1, 2 * lm.ATTENTION_BLOCK),
                             3, 64)
    lab = jnp.roll(tok, -1, axis=1)
    step = lambda: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: lm.lm_loss(p, cfg, tok, lab)[0]))(params)
    plain_loss, plain_grad = step()
    monkeypatch.setattr(lm, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        kernel, "make_splash_mqa_single_device", functools.partial(
            kernel.make_splash_mqa_single_device, interpret=True))
    loss, grad = step()
    assert abs(float(loss) - float(plain_loss)) < 1e-5
    for got, want in zip(jax.tree_util.tree_leaves(grad),
                         jax.tree_util.tree_leaves(plain_grad)):
        assert close(got, want, 1e-5)


# -- compiled for a described chip: nothing runs ------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("spec", [lm.LayerSpec("full", 4, "dense"),
                                  lm.LayerSpec("sliding", 6, "dense")])
def test_a_layers_backward_pass_runs_the_forward_kernel_once(
        spec, topo, no_cache, monkeypatch):
    """One layer at the kernel's widths (heads of 128, window 512, 1,024
    positions), forward and backward under `forward`'s checkpoint, compiled
    for a v5e: one forward splash kernel where the bare checkpoint runs it
    again going backward, and both backward kernels."""
    import re

    from jax.sharding import SingleDeviceSharding

    t = 2 * lm.ATTENTION_BLOCK
    cfg, tables = cut_to(spec, head_dim=128, sliding_window=512,
                         compute_dtype="bfloat16")
    monkeypatch.setattr(lm, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    lp = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: lm.init_params(
            cfg, jax.random.key(0))["layers"][0]))
    x = jax.ShapeDtypeStruct((1, t, cfg.hidden), jnp.bfloat16, sharding=one)

    def launches():
        def loss(lp_, x_):
            y, _, _ = lm.checkpointed_layer(cfg, spec, tables(t))(lp_, x_)
            return jnp.sum(y.astype(jnp.float32))

        # tests/conftest.py asks for float32 matmuls everywhere, which a
        # Mosaic kernel's bfloat16 product cannot be
        with jax.default_matmul_precision("default"):
            text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
                lp, x).compile().as_text()
        return {k: len(re.findall(
            rf"%splash_mqa_{k}\S* = .*custom-call\(", text))
            for k in ("fwd", "dkv", "dq")}

    assert launches() == {"fwd": 1, "dkv": 1, "dq": 1}
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    assert launches() == {"fwd": 2, "dkv": 1, "dq": 1}


@pytest.mark.parametrize("S, H, row, page, P, L, kv_rank", [
    (128, 128, 576, 256, 8, 5, 512),    # deepseek-v3.closed-128
    (128, 32, 576, 128, 4, 40, 512)],   # xing4-29b.closed-128
    ids=["deepseek-v3", "xing4"])
def test_the_latent_paged_attention_kernel_compiles_at_the_cells_sizes(
        topo, no_cache, S, H, row, page, P, L, kv_rank):
    """`kernels/latent_attention.py` at a served cell's sizes (128 slots;
    128 heads on pages ``[576, 256]`` of bfloat16, 8 a slot, 5 layers; 32
    heads on pages ``[576, 128]``, 4 a slot, 40 layers), the slots' new
    rows written, compiled for a v5e: the Mosaic compiler takes it (tiles,
    VMEM), the pool is its operand as it lies and its output in place
    (aliased, no copy of it, no second layout) and nothing beside the
    output is made."""
    import re

    from jax.sharding import SingleDeviceSharding

    from deeplearning4j_tpu.kernels import latent_attention as la

    assert la.available(H, row, page, kv_rank, jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)

    def attend(q, pool, pos, table, rows):
        pidx = table[jnp.arange(S), pos // page]
        return la.latent_page_attention(
            q, pool, la.page_walk(pos, table, page), rows, pidx,
            layer=L - 1, kv_rank=kv_rank, scale=0.1)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(attend, donate_argnums=1).lower(
            sd((S, H, row), jnp.bfloat16),
            sd((L, S * P + 1, row, page), jnp.bfloat16),
            sd((S,), jnp.int32), sd((S, P), jnp.int32),
            sd((S, row), jnp.bfloat16)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%latent_page_attention\S* = .*custom-call\(.*",
                       text)
    assert len(calls) == 1
    # the kernel's second output is its pool operand, and the step's
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(\d+, \{\}\)\}",
                     calls[0])
    assert re.search(r"input_output_alias=\{ \{1\}: \(1, \{\}", text)
    pool = rf"bf16\[{L},{S * P + 1},{row},{page}\]"
    assert set(re.findall(pool + r"\{([0-9,]*)", text)) == {"3,2,1,0"}
    assert not re.search(r"= " + pool + r"\S* copy\(", text)
    assert "while(" not in text
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= L * (S * P + 1) * row * page * 2
    assert ma.temp_size_in_bytes < 1 << 20
