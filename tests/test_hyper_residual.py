"""ISSUE 39 tests: a residual path of several streams mixed by
Sinkhorn-normalised maps (manifold-constrained hyper-connections) in the one
block description (`models/causal_lm.py:stream_maps`, `stream_read`,
`stream_write`), trained through `causal_lm.logits` and served token by token
through `serving/latent.py`. The program is compared with the plain reference
`benchmark/reference/xing4_plain.py` (which imports nothing from the program)
at a small size on seeded weights: hidden 64, 4 streams, 4 heads, ranks 24 and
16, rotary 8, 16 experts in one group (4 chosen), a dense and two sparse
layers. The controls that must come out `correct: false` under the toy cell's
limits are `tests/benchmark/test_benchmark_serve_hc.py`'s planted faults."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers import serve_closed_hc as driver  # noqa: E402
from benchmark.reference import deepseek_v3_plain as v3  # noqa: E402
from benchmark.reference import xing4_plain as plain  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.models import causal_lm as lm  # noqa: E402
from deeplearning4j_tpu.parallel import moe  # noqa: E402
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeEngine, HybridDecodeModel, LatentDecodeModel, PagedKVCache)
from deeplearning4j_tpu.serving.decode import DecodeError  # noqa: E402

PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 96,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
WEIGHTS = {"matrix_std": 0.1, "embedding_std": 1.0, "router_bias_std": 0.1,
           "hc_phi_std": 0.1, "hc_alpha": 0.6, "hc_bias_std": 0.5,
           "hc_res_diagonal": 2.0}
# float32 program against the float32 reference: the two sum in other orders
# and read 1e-6 apart at these sizes
TOL = 2e-5
TOKENS = [int(t) for t in np.random.default_rng(0).integers(3, 96, 19)]


def config(experts_held=(0, 16), **published):
    return {"published": dict(PUBLISHED, **published), "weights": WEIGHTS,
            "model": {"layer_ids": [0, 1, 2],
                      "layer_kinds": ["dense", "sparse", "sparse"],
                      "experts_held": list(experts_held),
                      "vocab_size": PUBLISHED["vocab_size"]}}


def model(dtype="float32", seed=1, experts_held=(0, 16), **kw):
    """(the decode model, the reference's weights and sizes); the values are
    the reference's in both."""
    cfg = config(experts_held)
    sizes = driver.reference_sizes(cfg)
    weights = plain.draw_params(seed, sizes)
    geometry = dict(max_slots=3, page=4, max_pages_per_slot=6)
    geometry.update(kw)
    return LatentDecodeModel(
        driver.to_program(weights),
        driver.program_config(cfg, compute_dtype=dtype), dtype=dtype,
        **geometry), weights, sizes


def stepwise(m, tokens):
    """The token step's logits and health at every position of one sequence,
    in slot 1 of the pool, a position a launch: prompt, then decode, through
    the cache."""
    kv = PagedKVCache(m.n_pages, m.page, m.max_pages_per_slot, m.max_slots)
    kv.reserve(1, len(tokens))
    apply = jax.jit(m._apply)
    state, out, health = m.init_state(), [], []
    for p, tok in enumerate(tokens):
        feed = np.zeros(m.max_slots, np.int32)
        pos = np.zeros(m.max_slots, np.int32)
        table = np.zeros_like(kv.table)
        feed[1], pos[1], table[1] = tok, p, kv.table[1]
        pidx = table[np.arange(m.max_slots), pos // m.page]
        logits, state, _, h = apply(m.params, state, feed, pos, table, pidx)
        out.append(np.asarray(logits[1]))
        health.append(np.asarray(h))
    return np.stack(out), np.stack(health)


def test_a_prompt_then_decode_through_the_pool_gives_the_references_logits():
    m, weights, sizes = model()
    ref = np.asarray(plain.forward_logits(weights, sizes, TOKENS))
    got, health = stepwise(m, TOKENS)
    assert np.abs(got - ref).max() < TOL
    # the step's two health numbers: the maps near the manifold, the streams'
    # gain from the embedding to the exit of the order of 1
    assert 0.0 <= health[:, 0].max() < 0.05
    assert 0.5 < health[:, 1].min() and health[:, 1].max() < 10.0
    low, _ = stepwise(model("bfloat16")[0], TOKENS)
    assert np.abs(low - ref).max() > 100 * TOL


def test_the_trainers_forward_gives_the_references_logits():
    cfg = config()
    sizes = driver.reference_sizes(cfg)
    weights = plain.draw_params(2, sizes)
    ref = np.asarray(plain.forward_logits(weights, sizes, TOKENS))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    driver.to_program(weights))
    pc = driver.program_config(cfg, compute_dtype="float32")
    got = lm.logits(params, pc, jnp.asarray([TOKENS, TOKENS[::-1]]))
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL
    # and the description trains: a gradient reaches every leaf of the maps
    own = lm.init_params(pc, jax.random.key(0))
    toks = jnp.asarray([TOKENS[:8]])
    grads = jax.grad(lambda p: lm.lm_loss(p, pc, toks,
                                          jnp.roll(toks, -1, 1))[0])(own)
    for group in ("attn_streams", "mlp_streams"):
        for leaf in grads["layers"][1][group].values():
            assert np.isfinite(np.asarray(leaf)).all()
            assert float(jnp.abs(leaf).max()) > 0.0


def test_a_sequence_decodes_the_same_alone_and_among_strangers():
    reg = telemetry.MetricsRegistry()
    prev = telemetry.set_registry(reg)
    telemetry.enable()
    m, _, _ = model("bfloat16", experts_held=(0, 8))
    eng = DecodeEngine(
        m, name="hc", instruments=telemetry.serving_instruments("hc")).warmup()
    prompts = [TOKENS[:9], TOKENS[2:4], TOKENS[5:16], TOKENS[1:6]]
    try:
        together = [r.result(timeout=120.0) for r in
                    [eng.submit(p, 7) for p in prompts]]
        alone = eng.submit(prompts[2], 7).result(timeout=120.0)
    finally:
        eng.close()
        telemetry.set_registry(prev)
    assert together[2] == alone
    # the step's health rode beside its tokens and the routers' counts: two
    # gauges under the engine's label, scrape-only (not in the snapshot)
    gauges = {f.name: f for f in reg.collect() if f.name.startswith("dl4j_hc")}
    assert set(gauges) == {"dl4j_hc_sinkhorn_residual_max",
                           "dl4j_hc_stream_gain_max"}
    for fam in gauges.values():
        assert fam.local and fam.labels(model="hc").value > 0.0
    assert not any(k.startswith("dl4j_hc") for k in reg.snapshot())
    assert reg.snapshot()['dl4j_moe_steps_total{model="hc"}'] > 0


def maps_of(seed, iters=20, **hp):
    """One sublayer's maps by the program and by the reference, over ten
    positions of random streams, from the reference's draw with `hp` laid
    over it."""
    pc = driver.program_config(config(), compute_dtype="float32",
                               sinkhorn_iters=iters)
    sizes = dict(driver.reference_sizes(config()), sinkhorn_iters=iters)
    own = dict(plain.draw_params(seed, sizes)["layers"][0]["attn_hc"], **hp)
    x = jax.random.normal(jax.random.key(seed), (2, 5, 4, 64))
    return lm.stream_maps(own, x, pc), plain.hyper_maps(
        own, x.reshape(10, 4, 64), sizes), own


def test_the_mixing_map_is_doubly_stochastic_after_twenty_iterations():
    flat = lambda vs: np.stack(  # noqa: E731
        [np.asarray(v).reshape(-1) for v in vs], -1)
    got, (pre, post, res), own = maps_of(3)
    mine = np.asarray(lm.stream_matrix(got)).reshape(10, 4, 4)
    # the program's maps are the reference's
    assert np.abs(mine - np.asarray(res)).max() < 1e-6
    assert np.abs(flat(got["pre"]) - np.asarray(pre)).max() < 1e-6
    assert np.abs(flat(got["post"]) - np.asarray(post)).max() < 1e-6
    # rows are normalised last; the columns are where the iterations stand,
    # and `defect` is their distance: under 1e-5 at most positions of this
    # draw (a stream's own entry e^2 times another's: a slow case is a
    # position whose logits spread widest), a few percent at the worst
    assert np.abs(mine.sum(-1) - 1).max() < 1e-5 and mine.min() > 0.0
    defect = np.asarray(got["defect"]).reshape(-1)
    assert np.allclose(defect, np.abs(mine.sum(-2) - 1).max(-1), atol=2e-6)
    assert np.median(defect) < 1e-5 and defect.max() < 5e-2
    # input-dependent: no two positions mix alike, and none is saturated
    assert np.abs(mine[0] - mine[1]).max() > 1e-2
    assert 0.02 < float(pre.min()) and float(pre.max()) < 0.98
    # a draw of moderate contrast is on the manifold to 1e-5 everywhere
    mild, _, _ = maps_of(3, bias=0.25 * own["bias"],
                         alpha=0.5 * own["alpha"])
    assert float(mild["defect"].max()) < 1e-5
    # one iteration leaves it far from the manifold, and `defect` says so
    once, (_, _, ref_once), _ = maps_of(3, iters=1)
    assert float(once["defect"].max()) > 1e-2
    assert np.abs(np.asarray(lm.stream_matrix(once)).reshape(10, 4, 4)
                  - np.asarray(ref_once)).max() < 1e-6
    # a logit past float32's exp (88.7) is clamped, not overflowed
    clamped, _, _ = maps_of(3, bias=own["bias"].at[9].set(100.0))
    assert np.isfinite(np.asarray(lm.stream_matrix(clamped))).all()


def test_one_stream_is_the_plain_residual_bit_for_bit():
    """`hc_mult` 1, or no such key, is the block as it stood before the
    residual path was part of the description: the sums written out here as
    they were written in `layer_forward`."""
    plain_pub = {k: v for k, v in PUBLISHED.items()
                 if not k.startswith(("hc_", "mhc_"))}
    one = lm.CausalLMConfig.from_latent_published(
        dict(plain_pub, hc_mult=1), compute_dtype="bfloat16")
    assert one == lm.CausalLMConfig.from_latent_published(
        plain_pub, compute_dtype="bfloat16")
    assert one.streams == 1
    params = lm.init_params(one, jax.random.key(4))
    assert "attn_streams" not in params["layers"][0]
    x = jax.random.normal(jax.random.key(5), (2, 8, 64)).astype(jnp.bfloat16)
    tables = {"latent": lm.rope_tables(one.rope["latent"], one.rope_dim, 8)}
    lp, spec = params["layers"][0], one.layers[0]
    u = lm.rms_norm(x, lp["attn_norm"], one.rms_eps).astype(x.dtype)
    h = (x + lm.attention_block(lp, u, one, spec, tables)).astype(x.dtype)
    u = lm.rms_norm(h, lp["mlp_norm"], one.rms_eps).astype(x.dtype)
    before = (h + lm.mlp_apply(lp["mlp"], u)).astype(x.dtype)
    now, _, _ = lm.layer_forward(lp, x, one, spec, tables)
    assert now.dtype == before.dtype and bool(jnp.all(now == before))
    assert lm.stream_maps(None, x, one) is None
    assert lm.streams_enter(x, one) is x and lm.streams_exit(x, one) is x


def test_the_shares_of_all_chips_add_up_to_the_whole_layer():
    """The 8 shares' routed parts (2 of the 16 experts each), with the shared
    expert and the residual mix counted once, give the uncut reference's
    layer."""
    whole = config()
    sizes = driver.reference_sizes(whole)
    weights = plain.draw_params(6, sizes)
    lp_ref = weights["layers"][1]
    T = 12
    X = jax.random.normal(jax.random.key(7), (T, 4, 64))
    angle = jnp.asarray(np.arange(T)[:, None] * v3.yarn_frequencies(sizes)[
        None, :], jnp.float32)
    ref = np.asarray(plain.layer(lp_ref, X, sizes, angle, "f32"))

    lp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        driver.to_program(weights)["layers"][1])
    pc = driver.program_config(whole, compute_dtype="float32")
    tables = {"latent": lm.rope_tables(pc.rope["latent"], pc.rope_dim, T)}
    x = X[None]
    maps = lm.stream_maps(lp["attn_streams"], x, pc)
    u = lm.rms_norm(lm.stream_read(x, maps), lp["attn_norm"], pc.rms_eps)
    h = lm.stream_write(
        x, lm.attention_block(lp, u, pc, pc.layers[1], tables), maps)
    maps = lm.stream_maps(lp["mlp_streams"], h, pc)
    u = lm.rms_norm(lm.stream_read(h, maps), lp["mlp_norm"], pc.rms_eps)[0]
    out, held = lm.mlp_apply(lp["shared"], u), 0
    for first in range(0, 16, 2):
        share = dict(lp["moe"], **{k: lp["moe"][k][first:first + 2]
                                   for k in ("gate", "up", "down")})
        routed, choices, dropped = moe.moe_share_apply(
            share, u, top_k=4, experts_held=(first, 2), routed_scale=2.5)
        out, held = out + routed, held + int(choices.sum())
        assert int(dropped) == 0
    assert held == T * 4        # every choice fell on exactly one share
    got = lm.stream_write(h, out[None], maps)[0]
    assert np.abs(np.asarray(got) - ref).max() < TOL
    # and the program's own uncut layer is the same thing
    full, _, _ = lm.layer_forward(lp, x, pc, pc.layers[1], tables)
    assert np.abs(np.asarray(full[0]) - ref).max() < TOL


@pytest.mark.parametrize("change, named", [
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"n_shared_experts": 0}, "n_shared_experts"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"hc_eps": None}, "hc_eps"),
    ({"hc_sinkhorn_iters": None, "mhc_h_res_clamp_max": None},
     "hc_sinkhorn_iters, mhc_h_res_clamp_max")])
def test_a_published_key_that_cannot_be_honoured_is_refused_by_name(
        change, named):
    published = {k: v for k, v in dict(PUBLISHED, **change).items()
                 if v is not None}
    with pytest.raises(ValueError, match=named):
        lm.CausalLMConfig.from_latent_published(published)


def test_a_model_of_one_stream_refuses_a_description_of_several():
    hybrid = {
        "hybrid_override_pattern": "M*E", "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "intermediate_size": 32,
        "moe_intermediate_size": 32, "moe_latent_size": 24,
        "moe_shared_expert_intermediate_size": 48, "mlp_hidden_act": "relu2",
        "n_routed_experts": 16, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": 2.5, "norm_eps": 1e-5,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4, "vocab_size": 96}
    cfg = lm.CausalLMConfig.from_hybrid_published(hybrid, streams=4)
    shapes = jax.eval_shape(lambda: lm.init_params(
        lm.CausalLMConfig.from_hybrid_published(hybrid), jax.random.key(0)))
    with pytest.raises(DecodeError, match="streams = 4"):
        HybridDecodeModel(shapes, cfg, max_slots=2, page=4)


# -- the maps' kernel ---------------------------------------------------------

from deeplearning4j_tpu.kernels import stream_maps as maps_kernel  # noqa: E402

HOW = dict(n=4, iters=20, eps=1e-6, clamp=(-30, 30))


def test_the_kernel_gives_the_plain_maps_in_the_same_layout():
    z = jax.random.normal(jax.random.key(8), (24, 256)) * 1.5
    z = z.at[8 + 5, 3].set(100.0)       # past float32's exp: clamped
    plainly = maps_kernel.packed_maps(z, **HOW)
    kernel = maps_kernel.sinkhorn_maps(z, interpret=True, **HOW)
    assert plainly.shape == kernel.shape == (maps_kernel.rows_out(4), 256)
    assert np.isfinite(np.asarray(kernel)).all()
    assert float(jnp.abs(plainly - kernel).max()) < 1e-6
    assert float(jnp.abs(kernel[25:]).max()) == 0.0
    # rows of H_res sum to 1; the defect row says what the columns lack
    res = np.asarray(kernel[8:24]).reshape(4, 4, 256)
    assert np.abs(res.sum(1) - 1).max() < 1e-5
    assert np.allclose(np.asarray(kernel[24]),
                       np.abs(res.sum(0) - 1).max(0), atol=2e-6)


@pytest.mark.parametrize("positions, n, fits", [
    (128, 4, True), (256, 2, True), (3, 4, False), (192, 4, False),
    (128, 1, False)])
def test_the_kernels_gate_by_shape(positions, n, fits):
    assert maps_kernel.available(positions, n) is fits


def test_the_step_through_the_kernel_gives_the_plain_steps_logits(
        monkeypatch):
    """128 slots fill the lanes: where the backend is a TPU the decode step's
    maps are the kernel's (here interpreted), the trainer's never."""
    from deeplearning4j_tpu import kernels

    m, _, _ = model(max_slots=128, page=4, max_pages_per_slot=2)
    rng = np.random.default_rng(9)
    tokens = rng.integers(3, 96, 128).astype(np.int32)
    pos = np.zeros(128, np.int32)
    table = np.zeros((128, 2), np.int32)
    table[:, 0] = 1 + np.arange(128)
    pidx = table[np.arange(128), pos // m.page]
    args = (m.params, m.init_state(), tokens, pos, table, pidx)
    plainly, _, _, health = jax.jit(m._apply)(*args)
    calls = []
    kernel = maps_kernel.sinkhorn_maps
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(m, "kernel_fits", False)    # attention: the loop
    monkeypatch.setattr(
        maps_kernel, "sinkhorn_maps",
        lambda z, **how: calls.append(z.shape) or kernel(
            z, interpret=True, **how))
    fused, _, _, fused_health = jax.jit(m._apply)(*args)
    assert calls == [(24, 128)] * 6          # three layers, two sublayers
    assert float(jnp.abs(fused - plainly).max()) < TOL
    assert np.allclose(np.asarray(fused_health), np.asarray(health),
                       atol=1e-5)
    calls.clear()
    pc = driver.program_config(config(), compute_dtype="float32")
    lm.stream_maps(lm.init_params(pc, jax.random.key(0))["layers"][0][
        "attn_streams"], jnp.ones((1, 128, 4, 64)), pc)
    assert calls == []                       # no `fused`: a gradient may come


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


def test_the_served_cells_kernels_compile_for_the_chip(topo, no_cache):
    """At the served cell's sizes, compiled for a v5e (nothing runs): the
    maps' kernel over 128 slots, and the paged-attention kernel over 32
    heads and pages `[576, 128]`, 4 a slot, of a 40-layer pool, which stays
    its operand as it lies and is written in place (the slots' new rows)."""
    import re

    from jax.sharding import SingleDeviceSharding

    from deeplearning4j_tpu.kernels import latent_attention as la

    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)
    with jax.default_matmul_precision("default"):
        maps = jax.jit(lambda z: maps_kernel.sinkhorn_maps(z, **HOW)).lower(
            sd((24, 128), jnp.float32)).compile()
    assert len(re.findall(r"%stream_maps\S* = .*custom-call\(",
                          maps.as_text())) == 1
    S, H, row, page, P, L, kv_rank = 128, 32, 576, 128, 4, 40, 512
    assert la.available(H, row, page, kv_rank, jnp.bfloat16)

    def attend(q, pool, pos, table, rows):
        return la.latent_page_attention(
            q, pool, la.page_walk(pos, table, page), rows,
            table[jnp.arange(S), pos // page], layer=L - 1,
            kv_rank=kv_rank, scale=0.1)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(attend, donate_argnums=1).lower(
            sd((S, H, row), jnp.bfloat16),
            sd((L, S * P + 1, row, page), jnp.bfloat16),
            sd((S,), jnp.int32), sd((S, P), jnp.int32),
            sd((S, row), jnp.bfloat16)).compile()
    text = compiled.as_text()
    pool = rf"bf16\[{L},{S * P + 1},{row},{page}\]"
    assert set(re.findall(pool + r"\{([0-9,]*)", text)) == {"3,2,1,0"}
    assert not re.search(r"= " + pool + r"\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
