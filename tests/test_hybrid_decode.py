"""ISSUE 34 tests: a hybrid state-space causal LM served token by token
(`serving/hybrid.py`): a Mamba-2 state and a convolution tail a slot beside
paged grouped-head K and V, latent experts with an ungated relu² MLP
(`parallel/moe.py:moe_share_apply`). The program is compared with the plain
reference `benchmark/reference/nemotron_h_plain.py` (which imports nothing
from the program) at a small size on seeded weights: hidden 64, pattern
`MEM*EME`, 8 state-space heads of 8 in 2 groups with a state of 16, 4 query on
2 K/V heads of 16, 16 experts of 32 in a latent space of 24 (4 chosen)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers import serve_closed_hybrid as driver  # noqa: E402
from benchmark.reference import nemotron_h_plain as plain  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.models import causal_lm as lm  # noqa: E402
from deeplearning4j_tpu.serving import (  # noqa: E402
    ChunkedPrefill, DecodeEngine, HybridDecodeModel, InferenceSession,
    PagedKVCache, SpeculativeConfig)
from deeplearning4j_tpu.serving.decode import DecodeError  # noqa: E402

PATTERN = "MEM*EME"
PUBLISHED = {
    "hybrid_override_pattern": PATTERN, "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "intermediate_size": 32,
    "moe_intermediate_size": 32, "moe_latent_size": 24,
    "moe_shared_expert_intermediate_size": 48, "mlp_hidden_act": "relu2",
    "n_routed_experts": 16, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 5,
    "norm_eps": 1e-5, "num_hidden_layers": 7, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001, "vocab_size": 96}
WEIGHTS = {"matrix_std": 0.1, "embedding_std": 1.0, "router_bias_std": 0.1}
KINDS = {"M": "mamba", "E": "sparse", "*": "attention"}
# float32 program against the float32 reference: the two sum in other orders
# (the reference's products at Precision.HIGHEST, the state's read-out before
# or after the group's broadcast) and read some 3e-6 apart at these sizes.
# The same program in bfloat16 reads 1e-2 and more and fails it by orders.
TOL = 5e-5


def config(experts_held=(0, 16)):
    return {"published": PUBLISHED, "weights": WEIGHTS,
            "model": {"layer_ids": list(range(len(PATTERN))),
                      "layer_kinds": [KINDS[c] for c in PATTERN],
                      "experts_held": list(experts_held),
                      "vocab_size": PUBLISHED["vocab_size"]}}


def model(dtype="float32", seed=1, experts_held=(0, 16), **kw):
    """(the decode model, the reference's weights and sizes); the values are
    the reference's, bfloat16-rounded, in both."""
    cfg = config(experts_held)
    sizes = driver.reference_sizes(cfg)
    weights = plain.draw_params(seed, sizes)
    geometry = dict(max_slots=3, page=4, max_pages_per_slot=8)
    geometry.update(kw)
    return HybridDecodeModel(
        driver.to_program(weights),
        driver.program_config(cfg, compute_dtype=dtype), dtype=dtype,
        **geometry), weights, sizes


def stepwise_logits(m, tokens, slot=1, state=None):
    """(the token step's logits at every position of one sequence in `slot`,
    a position a launch: prompt, then decode, through pages and slot state;
    the state it leaves)."""
    kv = PagedKVCache(m.n_pages, m.page, m.max_pages_per_slot, m.max_slots)
    kv.reserve(slot, len(tokens))
    apply = jax.jit(m._apply)
    state, out = (m.init_state() if state is None else state), []
    for p, tok in enumerate(tokens):
        feed = np.zeros(m.max_slots, np.int32)
        pos = np.zeros(m.max_slots, np.int32)
        table = np.zeros_like(kv.table)
        feed[slot], pos[slot], table[slot] = tok, p, kv.table[slot]
        pidx = table[np.arange(m.max_slots), pos // m.page]
        logits, state, _ = apply(m.params, state, feed, pos, table, pidx)
        out.append(np.asarray(logits[slot]))
    return np.stack(out), state


RNG = np.random.default_rng(0)
TOKENS = [int(t) for t in RNG.integers(3, 96, 23)]
OTHERS = [[int(t) for t in RNG.integers(3, 96, n)] for n in (5, 9, 14)]


def test_a_prompt_then_decode_through_pages_and_state_gives_the_references_logits():
    m, weights, sizes = model()
    ref = np.asarray(plain.forward_logits(weights, sizes, TOKENS))
    got, _ = stepwise_logits(m, TOKENS)
    assert np.abs(got - ref).max() < TOL
    low, _ = stepwise_logits(model("bfloat16")[0], TOKENS)
    assert np.abs(low - ref).max() > 100 * TOL


def test_the_state_matters_to_the_logits():
    """The weights' scales make the state do work: a second request on a
    state that was not started from nought reads far outside the tolerance,
    so the comparison can tell."""
    m, weights, sizes = model()
    ref = np.asarray(plain.forward_logits(weights, sizes, TOKENS))
    _, left = stepwise_logits(m, OTHERS[2])
    fresh, _ = stepwise_logits(m, TOKENS, state=left)
    assert np.abs(fresh - ref).max() < TOL
    m._request_starts = lambda fed, pos: jnp.zeros_like(fed)
    stale, _ = stepwise_logits(m, TOKENS, state=left)
    assert np.abs(stale - ref).max() > 1000 * TOL


def test_the_engine_serves_the_references_best_token():
    """Through `InferenceSession.register_decoder`, no engine option added;
    six requests on three slots, so every slot is taken over by a second
    request and its state starts from nought again."""
    m, weights, sizes = model()
    session = InferenceSession()
    session.register_decoder("hybrid-ref", m)
    try:
        eng = session.decoder("hybrid-ref")
        prompts = [TOKENS[:7], TOKENS[3:5], TOKENS[6:17]] + OTHERS
        answers = [r.result(timeout=120.0) for r in
                   [eng.submit(p, 6) for p in prompts]]
    finally:
        session.close()
    for prompt, answer in zip(prompts, answers):
        ref = np.asarray(plain.forward_logits(weights, sizes,
                                              prompt + answer[:-1]))
        at = ref[len(prompt) - 1:]
        gaps = at.max(-1) - at[np.arange(len(answer)), answer]
        assert gaps.max() < TOL


def test_the_token_step_equals_the_training_paths_scan():
    """`causal_lm.forward` runs a Mamba layer as a `lax.scan` of the very
    step the engine launches: the same logits from whole sequences."""
    m, weights, _ = model()
    program = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), driver.to_program(weights))
    whole = lm.logits(program, m.cfg, jnp.asarray([TOKENS]))[0]
    got, _ = stepwise_logits(m, TOKENS)
    assert np.abs(got - np.asarray(whole)).max() < TOL


def test_the_mamba_step_against_the_scan_form_over_64_positions():
    """One Mamba-2 layer alone: 64 launches of `mamba_step` carrying tail and
    state against the reference's scan over the 64 positions."""
    m, weights, sizes = model()
    lp_ref = weights["layers"][0]
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                driver.to_program(weights)["layers"][0])
    u = jax.random.normal(jax.random.key(5), (64, 64), jnp.float32)
    ref = np.asarray(plain.mamba(lp_ref, u, sizes, "f32"))
    cfg, heads = m.cfg, PUBLISHED["mamba_num_heads"]
    tail = jnp.zeros((1, cfg.conv_kernel - 1, cfg.conv_width), jnp.float32)
    state = jnp.zeros((1, heads, cfg.mamba_head_dim, cfg.ssm_state),
                      jnp.float32)
    step = jax.jit(lambda u_t, tail, state: lm.mamba_step(
        lp, u_t, tail, state, cfg, heads))
    out = []
    for t in range(64):
        y, tail, state = step(u[t][None], tail, state)
        out.append(np.asarray(y[0]))
    assert np.abs(np.stack(out) - ref).max() < TOL
    assert float(jnp.abs(state).max()) > 1e-2      # and it holds something


def serve(m, jobs):
    eng = DecodeEngine(m, name="hybrid-det").warmup()
    try:
        return [r.result(timeout=120.0) for r in
                [eng.submit(p, n) for p, n in jobs]]
    finally:
        eng.close()


def test_a_sequence_decodes_bit_identically_alone_and_among_strangers():
    m, _, _ = model("bfloat16", max_slots=4)
    alone = serve(m, [(TOKENS[:9], 8)])[0]
    crowd = serve(m, [(OTHERS[0], 5), (TOKENS[:9], 8), (OTHERS[1], 7),
                      (OTHERS[2], 4), (OTHERS[0][:3], 6)])
    assert crowd[1] == alone


def test_a_reused_slot_gives_what_a_fresh_engine_gives():
    """One slot: the second request takes over the slot, pages and state the
    first left; its answer is the one a fresh engine gives."""
    m, _, _ = model("bfloat16", max_slots=1)
    first, second = serve(m, [(OTHERS[2], 6), (TOKENS[:11], 7)])
    assert serve(m, [(TOKENS[:11], 7)])[0] == second
    assert serve(m, [(OTHERS[2], 6)])[0] == first


def test_an_idle_or_masked_slots_state_is_unchanged_bit_for_bit():
    m, _, _ = model("bfloat16")
    kv = PagedKVCache(m.n_pages, m.page, m.max_pages_per_slot, m.max_slots)
    for slot in range(3):
        kv.reserve(slot, 16)
    state = m.init_state()
    feed = np.asarray([5, 7, 9], np.int32)
    for p in range(5):          # every slot holds something
        _, state, _ = m.step(state, feed + p, np.full(3, p, np.int32),
                             kv.table.copy())
    held = jax.tree_util.tree_map(np.asarray, {k: state[k]
                                               for k in ("ssm", "conv")})
    # slot 1 is not fed: the engine gives it a zero row of the table
    table = kv.table.copy()
    table[1] = 0
    _, state, _ = m.step(state, feed, np.asarray([5, 0, 5], np.int32), table)
    # slot 2 is masked out of a prefill block of three positions
    block = ChunkedPrefill(m, 3)
    _, state = block.run(state, np.tile(feed[:, None], (1, 3)),
                         np.asarray([6, 5, 6], np.int32),
                         np.asarray([3, 3, 0], np.int32), kv.table.copy())
    now = jax.tree_util.tree_map(np.asarray, {k: state[k]
                                              for k in ("ssm", "conv")})
    same = lambda a, b, s: np.array_equal(a[s], b[s])  # noqa: E731
    for a, b in zip(held["ssm"], now["ssm"]):
        assert not same(a, b, 0)
        assert not same(a, b, 1) and not same(a, b, 2)   # they moved since
    # so look again at each alone: slot 1 across the idle step, slot 2
    # across the block
    state = jax.tree_util.tree_map(jnp.asarray, held)
    state["kv"] = m.init_state()["kv"]
    _, after, _ = m.step(state, feed, np.asarray([5, 0, 5], np.int32), table)
    for a, b in zip(held["ssm"], after["ssm"]):
        assert same(a, np.asarray(b), 1) and not same(a, np.asarray(b), 0)
    assert same(held["conv"][0], np.asarray(after["conv"])[0], 1)
    state = jax.tree_util.tree_map(jnp.asarray, held)
    state["kv"] = m.init_state()["kv"]
    _, after = block.run(state, np.tile(feed[:, None], (1, 3)),
                         np.asarray([5, 5, 5], np.int32),
                         np.asarray([3, 3, 0], np.int32), kv.table.copy())
    for a, b in zip(held["ssm"], after["ssm"]):
        assert same(a, np.asarray(b), 2) and not same(a, np.asarray(b), 1)
    for layer in range(len(held["ssm"])):
        assert same(held["conv"][layer], np.asarray(after["conv"])[layer], 2)


def test_chunked_prefill_gives_the_token_paths_answers():
    m, _, _ = model("bfloat16")
    jobs = [(TOKENS[:13], 6), (OTHERS[1], 5), (OTHERS[2], 4), (OTHERS[0], 6)]
    plain_path = serve(m, jobs)
    eng = DecodeEngine(m, name="hybrid-chunk", chunk=4).warmup()
    try:
        chunked = [r.result(timeout=120.0) for r in
                   [eng.submit(p, n) for p, n in jobs]]
    finally:
        eng.close()
    assert chunked == plain_path


def test_the_engine_refuses_what_takes_a_position_for_a_page_row():
    m, _, _ = model("bfloat16")
    with pytest.raises(DecodeError, match="state by slot"):
        DecodeEngine(m, name="hybrid-prefix", prefix_cache=True)
    with pytest.raises(DecodeError, match="state by slot"):
        DecodeEngine(m, name="hybrid-draft",
                     speculative=SpeculativeConfig(draft=m, k=2))
    with pytest.raises(DecodeError, match="one mixer"):
        HybridDecodeModel({}, lm.CausalLMConfig.from_published(
            {"layer_types": ["full_attention"], "num_hidden_layers": 1,
             "num_attention_heads_per_layer": [4],
             "mlp_layer_types": ["dense"],
             "rope_parameters": {"full_attention": {"rope_theta": 1e4},
                                 "sliding_attention": {"rope_theta": 1e4}},
             "vocab_size": 96, "hidden_size": 64, "head_dim": 16,
             "num_key_value_heads": 2, "sliding_window": 8,
             "intermediate_size": 32, "moe_intermediate_size": 32,
             "shared_expert_intermediate_size": 32, "num_experts": 4,
             "num_experts_per_tok": 2, "rms_norm_eps": 1e-6}))


def test_the_pattern_is_read_from_the_published_keys():
    cfg = driver.program_config(config((0, 8)))
    assert [(s.attention, s.mlp) for s in cfg.layers] == [
        {"M": ("mamba", "none"), "E": ("none", "sparse"),
         "*": ("full", "none")}[c] for c in PATTERN]
    assert cfg.sparse_layers == [1, 4, 6] and cfg.rope == {}
    assert (cfg.mamba_inner, cfg.conv_width) == (64, 64 + 2 * 2 * 16)
    assert not cfg.expert_gated and cfg.expert_act == "relu2"
    assert cfg.expert_latent == 24 and cfg.experts_held == (0, 8)
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    ref = jax.eval_shape(lambda: plain.draw_params(
        0, driver.reference_sizes(config((0, 8)))))
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes) == \
        jax.tree_util.tree_map(lambda a: a.shape, driver.to_program(ref))
    with pytest.raises(ValueError, match="not written here"):
        lm.CausalLMConfig.from_hybrid_published(
            dict(PUBLISHED, hybrid_override_pattern="M-"))


def test_the_new_series_and_scopes():
    """`dl4j_decode_state_starts_total` counts admissions,
    `dl4j_decode_slot_state_bytes` what the model holds by slot, the
    `dl4j_moe_*` bundle the routers, all under the engine's label and on
    `GET /metrics`; the step's HLO carries the new scopes."""
    from deeplearning4j_tpu.telemetry import prometheus

    old = telemetry.get_registry()
    telemetry.set_registry(telemetry.MetricsRegistry())
    try:
        m, _, _ = model("bfloat16")
        session = InferenceSession()
        session.register_decoder("hybrid-series", m)
        eng = session.decoder("hybrid-series")
        jobs = [(TOKENS[:7], 4), (OTHERS[0], 3), (OTHERS[1], 5),
                (OTHERS[2], 2), (TOKENS[2:6], 3)]
        [r.result(timeout=120.0) for r in [eng.submit(p, n)
                                           for p, n in jobs]]
        assert eng.health()["slot_state_bytes"] == m.slot_state_bytes()
        session.close()
        snap = telemetry.get_registry().snapshot()
        label = '{model="hybrid-series"}'
        assert snap["dl4j_decode_state_starts_total" + label] == len(jobs)
        assert snap["dl4j_decode_slot_state_bytes" + label] == \
            m.slot_state_bytes() == 3 * 3 * (8 * 8 * 16 * 4 + 3 * 128 * 2)
        assert snap["dl4j_moe_steps_total" + label] > 0
        assert snap["dl4j_moe_dense_steps_total" + label] == \
            snap["dl4j_moe_steps_total" + label]
        text = prometheus.render(telemetry.get_registry())
        for name in ("dl4j_decode_state_starts_total",
                     "dl4j_decode_slot_state_bytes",
                     "dl4j_moe_held_choices_total"):
            assert name in text
    finally:
        telemetry.set_registry(old)
    slots = jax.ShapeDtypeStruct((m.max_slots,), jnp.int32)
    table = jax.ShapeDtypeStruct((m.max_slots, m.max_pages_per_slot),
                                 jnp.int32)
    hlo = jax.jit(m._fn).lower(
        m.params, jax.eval_shape(m.init_state), slots, slots,
        table).as_text(debug_info=True)
    for scope in ("ssm.project", "ssm.conv", "ssm.update", "ssm.gate",
                  "gqa.attend", "moe.route", "moe.latent", "moe.experts",
                  "moe.shared", "lm.head"):
        assert scope in hlo, scope
