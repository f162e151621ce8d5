"""ISSUE 32 tests: a latent-attention causal LM served token by token over a
paged pool of latents (`serving/latent.py`), its node-limited, biased expert
selection (`parallel/moe.py:moe_share_apply`), and the router counts the
decode engine publishes. The program is compared with the plain reference
`benchmark/reference/deepseek_v3_plain.py` (which imports nothing from the
program) at a small size on seeded weights: hidden 64, 4 heads, ranks 24 and
16, rotary 8, 16 experts in 4 groups (2 kept, 4 chosen), a dense and a sparse
layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers import serve_closed_lm as driver  # noqa: E402
from benchmark.reference import deepseek_v3_plain as plain  # noqa: E402
from deeplearning4j_tpu import telemetry  # noqa: E402
from deeplearning4j_tpu.models import causal_lm as lm  # noqa: E402
from deeplearning4j_tpu.parallel import moe  # noqa: E402
from deeplearning4j_tpu.serving import (  # noqa: E402
    DecodeEngine, LatentDecodeModel, PagedKVCache)

PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "n_group": 4,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 4, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "topk_group": 2,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 96}
WEIGHTS = {"matrix_std": 0.1, "embedding_std": 1.0, "router_bias_std": 0.1}
# float32 program against the float32 reference: the two sum in other orders
# and read 9e-7 apart at these sizes. The same program in bfloat16 reads
# 1.3e-2 and fails it by three orders.
TOL = 2e-5


def config(experts_held=(0, 16), layer_ids=(0, 1)):
    return {"published": PUBLISHED, "weights": WEIGHTS,
            "model": {"layer_ids": list(layer_ids),
                      "layer_kinds": ["dense" if i < 1 else "sparse"
                                      for i in layer_ids],
                      "experts_held": list(experts_held),
                      "vocab_size": PUBLISHED["vocab_size"]}}


def model(dtype="float32", seed=1, experts_held=(0, 16), **kw):
    """(the decode model, the reference's weights and sizes); the values are
    the reference's, bfloat16-rounded, in both."""
    cfg = config(experts_held)
    sizes = driver.reference_sizes(cfg)
    weights = plain.draw_params(seed, sizes)
    geometry = dict(max_slots=3, page=4, max_pages_per_slot=6)
    geometry.update(kw)
    return LatentDecodeModel(
        driver.to_program(weights),
        driver.program_config(cfg, compute_dtype=dtype), dtype=dtype,
        **geometry), weights, sizes


def stepwise_logits(m, tokens, start=0):
    """The token step's logits at every position of one sequence, in slot 1
    of the pool, a position a launch: prompt, then decode, through the
    cache (from position ``start`` on, over the zeros the pool starts
    with: a short sequence that crosses a page's edge)."""
    kv = PagedKVCache(m.n_pages, m.page, m.max_pages_per_slot, m.max_slots)
    kv.reserve(1, start + len(tokens))
    apply = jax.jit(m._apply)
    state, out = m.init_state(), []
    for p, tok in enumerate(tokens, start):
        feed = np.zeros(m.max_slots, np.int32)
        pos = np.zeros(m.max_slots, np.int32)
        table = np.zeros_like(kv.table)
        feed[1], pos[1], table[1] = tok, p, kv.table[1]
        pidx = table[np.arange(m.max_slots), pos // m.page]
        logits, state, *_ = apply(m.params, state, feed, pos, table, pidx)
        out.append(np.asarray(logits[1]))
    return np.stack(out)


TOKENS = [int(t) for t in np.random.default_rng(0).integers(3, 96, 19)]


def test_a_prompt_then_decode_through_the_pool_gives_the_references_logits():
    m, weights, sizes = model()
    ref = np.asarray(plain.forward_logits(weights, sizes, TOKENS))
    got = stepwise_logits(m, TOKENS)
    assert np.abs(got - ref).max() < TOL
    low = stepwise_logits(model("bfloat16")[0], TOKENS)
    assert np.abs(low - ref).max() > 100 * TOL


def test_the_engine_serves_the_references_best_token():
    m, weights, sizes = model()
    eng = DecodeEngine(m, name="latent-ref").warmup()
    try:
        prompts = [TOKENS[:7], TOKENS[3:5], TOKENS[6:17]]
        answers = [r.result(timeout=120.0) for r in
                   [eng.submit(p, 6) for p in prompts]]
    finally:
        eng.close()
    for prompt, answer in zip(prompts, answers):
        ref = np.asarray(plain.forward_logits(weights, sizes,
                                              prompt + answer[:-1]))
        at = ref[len(prompt) - 1:]
        gaps = at.max(-1) - at[np.arange(len(answer)), answer]
        assert gaps.max() < TOL


def test_the_absorbed_step_equals_the_expanded_block():
    m, weights, _ = model()
    program = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), driver.to_program(weights))
    expanded = lm.logits(program, m.cfg, jnp.asarray([TOKENS]))[0]
    assert np.abs(stepwise_logits(m, TOKENS) - np.asarray(expanded)).max() \
        < TOL


def sparse_layer(seed=3, rows=24):
    """(the reference's sparse layer holding every expert, its sizes, a
    batch of normed rows)."""
    sizes = driver.reference_sizes(config())
    lp = plain.draw_params(seed, sizes)["layers"][1]
    u = jax.random.normal(jax.random.key(seed), (rows, 64), jnp.float32)
    return lp, sizes, u


def share_of(lp, first, count):
    """The program's parameters of the experts first..first+count-1."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return {"router": f32(lp["gate"]),
            "bias": f32(lp["e_score_correction_bias"]),
            **{k: f32(lp["experts"][v][first:first + count])
               for k, v in driver.MLP_NAMES.items()}}


# `rows` of the calls below on 24 rows that choose 4 experts each: the worst
# case (`moe.moe_share_dense`: one batched product over the held experts), or
# a shorter buffer that still takes every held choice of these rows (sorted,
# three `ragged_dot`s)
BOTH_FORMS = pytest.mark.parametrize("rows", [
    pytest.param(96, id="dense"), pytest.param(80, id="grouped")])


@BOTH_FORMS
def test_the_shares_of_all_chips_add_up_to_the_whole_layer(rows):
    assert moe.moe_share_dense(24, 4, rows) == (rows == 96)
    lp, sizes, u = sparse_layer()
    whole = plain.sparse_mlp(lp, u, sizes, "f32")
    shared = plain.gated_mlp(lp["shared_experts"], u, "f32")
    total, chosen = shared, 0
    for first in range(0, 16, 4):
        y, choices, dropped = moe.moe_share_apply(
            share_of(lp, first, 4), u, top_k=4, experts_held=(first, 4),
            routed_scale=2.5, n_group=4, topk_group=2, rows=rows)
        assert int(dropped) == 0
        total, chosen = total + y, chosen + int(choices.sum())
    assert chosen == 24 * 4
    assert np.abs(np.asarray(total - whole)).max() < TOL
    # node-limited: a row's four experts lie in two of the four groups
    w = np.asarray(plain.route(lp, u, sizes))
    groups = (w.reshape(24, 4, 4) > 0).any(-1).sum(-1)
    assert (groups <= 2).all() and ((w > 0).sum(-1) == 4).all()


def test_selection_is_by_biased_scores_and_the_weights_are_unbiased():
    """sigma = (.6, .5, .4, .1) and b = (0, 0, .15, 0): by sigma + b the two
    chosen are experts 0 and 2, by sigma alone 0 and 1; the weights are
    .6 and .4 over their sum, not .6 and .55 over theirs."""
    logit = lambda p: np.log(p / (1 - p))  # noqa: E731
    x = jnp.zeros((1, 4)).at[0, 0].set(1.0)
    router = jnp.zeros((4, 4)).at[0].set(
        jnp.asarray(logit(np.array([.6, .5, .4, .1]))))
    # expert e answers e + 1 in every column: silu(g) * up, down = identity
    rows = jnp.arange(1.0, 5.0)[:, None, None]
    params = {"router": router, "bias": jnp.asarray([0, 0, .15, 0.]),
              "gate": jnp.zeros((4, 4, 4)).at[:, 0, :].set(30.0),
              "up": jnp.zeros((4, 4, 4)).at[:, 0, :].set(1.0) * rows / 30.0,
              "down": jnp.broadcast_to(jnp.eye(4), (4, 4, 4))}
    y, choices, _ = moe.moe_share_apply(params, x, top_k=2,
                                        experts_held=(0, 4))
    assert choices.tolist() == [1, 0, 1, 0]
    right = (.6 * 1 + .4 * 3) / (.6 + .4)
    by_sigma_alone = (.6 * 1 + .5 * 2) / (.6 + .5)
    biased_weights = (.6 * 1 + .55 * 3) / (.6 + .55)
    assert float(y[0, 0]) == pytest.approx(right, abs=1e-5)
    assert abs(float(y[0, 0]) - by_sigma_alone) > 0.1
    assert abs(float(y[0, 0]) - biased_weights) > 0.1


@BOTH_FORMS
def test_one_group_and_no_bias_is_the_plain_top_k_bit_for_bit(rows):
    lp, _, u = sparse_layer()
    share = share_of(lp, 4, 8)
    plain_share = {k: v for k, v in share.items() if k != "bias"}
    kw = dict(top_k=4, experts_held=(4, 8), routed_scale=2.5, rows=rows)
    old = moe.moe_share_apply(plain_share, u, **kw)
    for params, more in (
            (plain_share, dict(n_group=1, topk_group=1)),
            # the new selection where it decides nothing
            (dict(plain_share, bias=jnp.zeros(16)), {}),
            (plain_share, dict(n_group=4, topk_group=4))):
        new = moe.moe_share_apply(params, u, **kw, **more)
        for a, b in zip(old, new):
            assert (np.asarray(a) == np.asarray(b)).all()
    # and the groups and the bias do decide something on these rows
    other = moe.moe_share_apply(share, u, **kw, n_group=4, topk_group=2)
    assert (np.asarray(old[1]) != np.asarray(other[1])).any()


@BOTH_FORMS
def test_idle_rows_are_neither_worked_on_nor_counted(rows):
    lp, _, u = sparse_layer()
    share = share_of(lp, 0, 16)
    kw = dict(top_k=4, experts_held=(0, 16), n_group=4, topk_group=2)
    live = jnp.arange(24) % 3 != 0
    y, choices, dropped = moe.moe_share_apply(share, u, live=live, rows=rows,
                                              **kw)
    full, _, _ = moe.moe_share_apply(share, u, rows=96, **kw)
    assert int(choices.sum()) == 16 * 4 and int(dropped) == 0
    assert (np.asarray(y)[::3] == 0).all()
    assert np.abs(np.asarray(y - full))[np.asarray(live)].max() < 1e-5


@BOTH_FORMS
def test_what_an_idle_row_holds_reaches_no_live_row(rows):
    """A slot that is not fed keeps whatever its last request left in its
    row: NaN and inf there leave every live row's result bit for bit what
    it is beside idle rows of nought, and the idle rows' own result nought."""
    lp, _, u = sparse_layer()
    share = share_of(lp, 0, 16)
    kw = dict(top_k=4, experts_held=(0, 16), n_group=4, topk_group=2,
              rows=rows)
    live = jnp.arange(24) % 3 != 0
    stale = jnp.where(jnp.arange(24) % 2 == 0, jnp.nan, jnp.inf)[:, None]
    for dtype in ("float32", "bfloat16"):
        x = u.astype(dtype)
        clean = moe.moe_share_apply(
            share, jnp.where(live[:, None], x, 0), live=live, **kw)
        dirty = moe.moe_share_apply(
            share, jnp.where(live[:, None], x, stale.astype(dtype)),
            live=live, **kw)
        for a, b in zip(clean, dirty):
            assert (np.asarray(a) == np.asarray(b)).all()
        assert (np.asarray(dirty[0])[::3] == 0).all()
        assert np.abs(np.asarray(dirty[0])).max() > 0.1


# Dense against grouped: the same products of the same operands, summed in
# another order. float32: the two read 2.5e-7 to 3.6e-7 of the largest
# entry apart (eight seeds, with and without idle rows); 2e-6 is five times
# that. bfloat16 operands: 1.1e-7 on the CPU, whose products of bfloat16
# are exact in float32; where an accumulation's order flips the rounding of
# one of a row's 32 `mid` entries to bfloat16 (2^-9 of it), the row moves by
# some 2^-9 / sqrt(32) = 3.5e-4 of itself: 1e-3.
@pytest.mark.parametrize("with_idle", [False, True])
@pytest.mark.parametrize("dtype, tol", [("float32", 2e-6),
                                        ("bfloat16", 1e-3)])
def test_dense_and_grouped_products_of_one_routing_agree(dtype, tol,
                                                         with_idle):
    lp, _, u = sparse_layer(seed=5)
    share, x = share_of(lp, 0, 16), u.astype(dtype)
    live = (jnp.arange(24) % 3 != 0) if with_idle else None
    weight, group = moe._share_route(share, x, 4, (0, 16), 2.5, 4, 2, live)
    dense = moe._dense_products(share, x, weight, group, 16, 4)
    grouped = moe._grouped_products(share, x, weight, group, 16, 4, 96)
    assert dense[0].dtype == grouped[0].dtype == jnp.float32
    gap = np.abs(np.asarray(dense[0] - grouped[0])).max()
    assert gap <= tol * np.abs(np.asarray(grouped[0])).max()
    assert (np.asarray(dense[1]) == np.asarray(grouped[1])).all()
    assert int(dense[1].sum()) == (16 if with_idle else 24) * 4
    assert int(dense[2]) == 0 == int(grouped[2])


@pytest.fixture(scope="module")
def served():
    """One engine over the bfloat16 model as it is served, on a registry of
    its own: the answers of four requests that ran together, the registry's
    snapshot, and how many programs were compiled after `warmup()`."""
    reg = telemetry.MetricsRegistry()
    prev = telemetry.set_registry(reg)
    telemetry.enable()
    m, _, _ = model("bfloat16", experts_held=(0, 8))
    eng = DecodeEngine(
        m, name="latent",
        instruments=telemetry.serving_instruments("latent")).warmup()
    compiles = reg.counter("dl4j_compile_total")
    c0 = compiles.value
    prompts = [TOKENS[:9], TOKENS[2:4], TOKENS[5:16], TOKENS[1:6]]
    try:
        together = [r.result(timeout=120.0) for r in
                    [eng.submit(p, 7) for p in prompts]]
        alone = eng.submit(prompts[2], 7).result(timeout=120.0)
        after = compiles.value - c0
    finally:
        eng.close()
        telemetry.set_registry(prev)
    return {"together": together, "alone": alone, "compiles": after,
            "snap": reg.snapshot(), "prompts": prompts}


def test_a_sequence_decodes_the_same_alone_and_among_strangers(served):
    assert served["together"][2] == served["alone"]
    assert len(set(map(tuple, served["together"]))) > 1


def test_nothing_compiles_after_warmup_and_nothing_is_dropped(served):
    assert served["compiles"] == 0
    assert served["snap"][
        'dl4j_moe_dropped_total{model="latent",layer="1"}'] == 0


def test_the_engine_publishes_the_router_counts_under_its_models_label(
        served):
    snap = served["snap"]
    at = lambda name: snap[f'{name}{{model="latent",layer="1"}}']  # noqa: E731
    steps = snap['dl4j_moe_steps_total{model="latent"}']
    step_boundaries = snap[
        'dl4j_decode_boundaries_total{model="latent",executable="step"}']
    assert steps == step_boundaries > 0
    # every position the token step fed chose four experts
    fed = sum(v for k, v in snap.items()
              if k.startswith("dl4j_decode_positions_total"))
    assert at("dl4j_moe_choices_total") == 4 * fed
    # half the experts are held: some choices fell on them, not all
    assert 0 < at("dl4j_moe_held_choices_total") < at(
        "dl4j_moe_choices_total")
    assert at("dl4j_moe_load_max_over_mean_sum") >= steps
    assert 0 < at("dl4j_moe_touched_experts_total") <= 8 * steps
    # three slots: every step's expert products ran dense
    assert snap['dl4j_moe_dense_steps_total{model="latent"}'] == steps


@pytest.mark.parametrize("slots, dense", [(3, True), (256, True),
                                          (257, False)])
def test_the_token_step_sorts_nothing_up_to_dense_rows_slots(slots, dense):
    """The step asks its share for every choice of every slot: up to
    `moe.DENSE_ROWS` slots neither the token step nor the masked step of a
    block prefill lowers to a grouped product, past them both do."""
    assert moe.DENSE_ROWS == 256
    m, _, _ = model("bfloat16", max_slots=slots, page=4,
                    max_pages_per_slot=1)
    assert m.moe_dense == dense
    state = jax.eval_shape(m.init_state)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    table = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    args = (jax.eval_shape(lambda: m.params), state, ints, ints, table)
    # lowered for the TPU (no chip needed: nothing is compiled), where a
    # grouped product is an operation of its own; the CPU's lowering
    # spells it out in plain products
    text = lambda fn, *more: jax.jit(fn).trace(*args, *more).lower(  # noqa: E731
        lowering_platforms=("tpu",)).as_text()
    step = text(m._fn)
    masked = text(m.masked_fn, jax.ShapeDtypeStruct((slots,), jnp.bool_))
    assert ("ragged_dot" in step) == (not dense)
    assert ("ragged_dot" in masked) == (not dense)


def test_the_model_answers_what_the_engine_asks_of_a_paged_model():
    m, _, _ = model("bfloat16")
    assert m.uses_pages and m.state_donation == (1,)
    pool = jax.eval_shape(m.init_state)["latent"]
    assert pool.shape == (2, 3 * 6 + 1, 16 + 8, 4) and pool.dtype == "bfloat16"
    assert list(m.pool_device_bytes().values()) == [2 * 19 * 4 * 24 * 2]
    assert m.max_len == 24 and m.vocab == 96 and m.moe_layers == (1,)
    assert "LatentDecodeModel" in m._store_program()
    # a model can be built on shapes (an ahead-of-time compile needs no
    # weights): the step's layout comes out as shapes too
    shapes = jax.eval_shape(lambda: m.params)
    on_shapes = LatentDecodeModel(
        jax.eval_shape(lambda: driver.to_program(plain.draw_params(
            1, driver.reference_sizes(config())))),
        m.cfg, max_slots=3, page=4, max_pages_per_slot=6)
    assert on_shapes.params == shapes
    with pytest.raises(Exception, match="latent"):
        LatentDecodeModel({}, lm.CausalLMConfig.from_published(
            LAGUNA_LIKE), max_slots=2)


LAGUNA_LIKE = {
    "num_hidden_layers": 1, "layer_types": ["full_attention"],
    "num_attention_heads_per_layer": [2], "mlp_layer_types": ["dense"],
    "rope_parameters": {
        "full_attention": {"rope_theta": 10000, "rope_type": "default"},
        "sliding_attention": {"rope_theta": 10000, "rope_type": "default"}},
    "vocab_size": 32, "hidden_size": 16, "head_dim": 8,
    "num_key_value_heads": 1, "sliding_window": 4, "intermediate_size": 32,
    "moe_intermediate_size": 8, "shared_expert_intermediate_size": 8,
    "num_experts": 4, "num_experts_per_tok": 2, "rms_norm_eps": 1e-6}


def test_chunked_prefill_gives_the_token_steps_answers():
    """The block executable is a loop of masked token steps: a model behind
    the protocol serves `chunk` too, and the answers do not change."""
    prompts = [TOKENS[:11], TOKENS[4:7]]
    answers = []
    for options in ({}, {"chunk": 4}):
        eng = DecodeEngine(model("bfloat16")[0], name="latent-chunk",
                           **options).warmup()
        try:
            answers.append([r.result(timeout=120.0) for r in
                            [eng.submit(p, 5) for p in prompts]])
        finally:
            eng.close()
    assert answers[0] == answers[1]


# -- ISSUE 37: the paged-attention kernel under the latent step ---------------
# (`kernels/latent_attention.py`, through the Pallas interpreter on the CPU)

from deeplearning4j_tpu import kernels  # noqa: E402
from deeplearning4j_tpu.kernels import latent_attention  # noqa: E402
from deeplearning4j_tpu.serving.decode import live_pages  # noqa: E402

PAGE, PAGES, SLOTS = 128, 3, 4


def paged_model(dtype="float32", rope=8, **kw):
    """A model whose pool the kernel takes: 8 heads on pages ``[24, 128]``
    (latent 16 over 8 rotary numbers: whole float32 tiles; over 16, whole
    bfloat16 tiles)."""
    cfg = config()
    cfg["published"] = dict(PUBLISHED, num_attention_heads=8,
                            num_key_value_heads=8, qk_rope_head_dim=rope)
    sizes = driver.reference_sizes(cfg)
    geometry = dict(max_slots=SLOTS, page=PAGE, max_pages_per_slot=PAGES)
    geometry.update(kw)
    return LatentDecodeModel(
        driver.to_program(plain.draw_params(1, sizes)),
        driver.program_config(cfg, compute_dtype=dtype), dtype=dtype,
        **geometry)


@pytest.fixture
def interpreted(monkeypatch):
    """The step's kernel branch on the CPU: the route steered as a compile
    for a described chip steers it, the kernel through the interpreter."""
    import functools

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        latent_attention, "latent_page_attention",
        functools.partial(latent_attention.latent_page_attention,
                          interpret=True))


def own_tables(rng=None):
    """Every slot its own pages, in order or in an order `rng` draws."""
    ids = np.arange(1, SLOTS * PAGES + 1, dtype=np.int32)
    if rng is not None:
        ids = rng.permutation(ids).astype(np.int32)
    return ids.reshape(SLOTS, PAGES)


def idle(pos, table, *slots):
    """As the engine hands over a slot that is not fed: position 0 and a
    zero row of the table (the scratch page)."""
    pos, table = np.array(pos, np.int32), table.copy()
    for s in slots:
        pos[s], table[s] = 0, 0
    return pos, table


CASES = {
    "a slot at position 0": lambda: ([0, 5, 200, 300], own_tables()),
    "a context ends on a page's last column":
        lambda: ([PAGE - 1, 2 * PAGE - 1, 40, 300], own_tables()),
    "a context ends on the next page's first column":
        lambda: ([PAGE, 2 * PAGE, 40, 300], own_tables()),
    "a full table": lambda: ([PAGES * PAGE - 1] * SLOTS, own_tables()),
    "idle slots between fed ones":
        lambda: idle([77, 0, 290, 0], own_tables(), 1, 3),
    "no shared page, tables in scrambled order":
        lambda: ([383, 130, 255, 256],
                 own_tables(np.random.default_rng(5))),
}


def both_routes(m, pos, table, seed=0, layer=1, pool=None):
    """`_attend` of layer `layer` over one random pool, each fed slot's new
    row written as the step writes it, by the loop and by the (interpreted)
    kernel -> two ``[S, H, v_dim]`` float32 arrays, zero where a slot is
    not fed (its output is discarded, and on the loop's route it reads the
    scratch page the loop wrote)."""
    rng = np.random.default_rng(seed)
    dt = m.dtype
    if pool is None:
        pool = rng.normal(size=m._pool_shape())
    pool = jnp.asarray(pool, dt)
    q = jnp.asarray(rng.normal(size=(m.max_slots, m.n_heads, m.row)), dt)
    row = jnp.asarray(rng.normal(size=(m.max_slots, m.row)), dt)
    wv_b = m.params["layers"][layer]["wv_b"]
    pos, table = jnp.asarray(pos, jnp.int32), jnp.asarray(table, jnp.int32)
    pidx = table[jnp.arange(m.max_slots), pos // m.page]
    fed = (np.asarray(pidx) != 0)[:, None, None]
    (loop, _), (kernel, _) = (
        m._attend(q, row, wv_b, pool, layer, walk, pidx, pos % m.page)
        for walk in (("loop", live_pages(pos, table, m.page)),
                     ("kernel", latent_attention.page_walk(
                         pos, table, m.page))))
    return np.where(fed, loop, 0), np.where(fed, kernel, 0)


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_gives_what_the_loop_gives_on_the_same_pool(
        case, interpreted, assert_rows_close):
    """Float32 on both sides, so the same precision; the kernel sums a
    slot's pages into one running softmax where the loop reduces each page
    on its own and combines: another order of summation, `assert_rows_close`'s
    tolerance (1e-5 relative; a wrong page, column or mask misses by orders
    more: the control below)."""
    m = paged_model()
    assert m.kernel_fits
    pos, table = CASES[case]()
    loop, kernel = both_routes(m, pos, table)
    assert np.isfinite(kernel).all()
    assert_rows_close(kernel, loop)
    # the control: one position fewer is another answer
    shorter = np.maximum(np.asarray(pos) - 1, 0)
    fed = np.asarray(pos) > 0
    _, other = both_routes(m, shorter, table)
    assert np.abs(other - loop)[fed].max() > 1e-3


def test_the_kernel_in_bfloat16_reads_what_the_loop_reads(interpreted):
    """As served: bfloat16 operands, float32 accumulation on both sides.
    The loop rounds each page's unnormalised weighted latent to bfloat16
    before `wv_b`, the kernel a slot's normalised one: the same precision
    at another place, so the two differ by bfloat16's rounding (2^-8) of an
    output, not by more."""
    m = paged_model("bfloat16", rope=16)
    assert m.kernel_fits and not paged_model("bfloat16").kernel_fits
    loop, kernel = both_routes(m, *CASES["a full table"]())
    scale = np.abs(loop).max()
    assert np.abs(kernel - loop).max() < 2 ** -6 * scale


def test_a_slots_output_does_not_change_with_what_its_neighbours_hold(
        interpreted):
    """Exact: the same program, and a slot's pages, running softmax and
    output are its own (what differs is the place of its pages in the walk
    and in the ring of copies)."""
    m = paged_model()
    table = own_tables(np.random.default_rng(7))
    rng = np.random.default_rng(11)
    pool = rng.normal(size=m._pool_shape())
    _, among = both_routes(m, [383, 130, 255, 256], table, pool=pool)
    others = pool.copy()
    for s in (0, 1, 3):                 # slot 2's neighbours: other rows
        others[:, table[s]] = rng.normal(size=others[:, table[s]].shape)
    pos, lone = idle([5, 130, 255, 0], table, 3)
    pos[1] = 0                          # fed, but at another position
    _, alone = both_routes(m, pos, lone, pool=others)
    np.testing.assert_array_equal(among[2], alone[2])


def test_the_step_through_the_kernel_gives_the_loops_logits(
        interpreted, monkeypatch):
    """The whole token step, a sequence that crosses a page's edge through
    the pool, by the kernel branch and by the loop: the same logits at
    float32's tolerance."""
    m = paged_model()
    through_kernel = stepwise_logits(m, TOKENS[:7], start=PAGE - 3)
    monkeypatch.setattr(kernels, "_on_tpu", lambda: False)
    through_loop = stepwise_logits(m, TOKENS[:7], start=PAGE - 3)
    np.testing.assert_allclose(through_kernel, through_loop, rtol=TOL,
                               atol=TOL)


def test_the_step_that_takes_the_kernel_holds_no_loop(monkeypatch):
    """Lowered for the TPU (no chip needed): a custom call a layer and no
    `while` in the step; the loop's step holds its `while`. The benchmark's
    `serve.latent_attention_roofline` reads the step's `while` operations:
    one left in the kernel's step would be read as the whole attention."""
    m = paged_model()
    z = np.zeros(SLOTS, np.int32)
    args = (m.params, m.init_state(), z, z,
            np.zeros((SLOTS, PAGES), np.int32))

    def lowered():
        return jax.jit(m._fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    loop = lowered()
    assert "stablehlo.while" in loop and "tpu_custom_call" not in loop
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    kernel = lowered()
    assert "stablehlo.while" not in kernel
    assert kernel.count("tpu_custom_call") == m.n_layers


def route_count(model, route):
    fam = telemetry.get_registry().counter(
        "dl4j_decode_attention_route_total", kernels.DECODE_ROUTE_HELP,
        ("model", "route"))
    return fam.labels(model=model, route=route).value


@pytest.mark.parametrize("why, geometry, fits", [
    ("on the CPU", dict(page=PAGE), True),
    ("a page of 16", dict(page=16), False),
    ("a page that is no whole lane tile", dict(page=192), False)])
def test_the_gate_says_loop_and_the_route_is_counted(why, geometry, fits):
    """Nothing but what the code can observe decides: the backend, and the
    pool's shape. A traced step is counted once under its route."""
    m = paged_model(max_pages_per_slot=2, **geometry)
    assert m.kernel_fits is fits
    before = route_count("LatentDecodeModel", "loop")
    state = m.init_state()
    z = np.zeros(m.max_slots, np.int32)
    m.step(state, z, z, np.zeros((m.max_slots, 2), np.int32))
    assert route_count("LatentDecodeModel", "loop") == before + 1


@pytest.mark.parametrize("heads, row, page, kv_rank, dtype, fits", [
    (128, 576, 256, 512, "bfloat16", True),     # the served cell's pool
    (128, 576, 16, 512, "bfloat16", False),     # a page of 16
    (8, 24, 128, 16, "bfloat16", False),        # a row that is no whole
    (8, 24, 128, 16, "float32", True),          # bfloat16 tile of 16
    (4, 24, 128, 16, "float32", False),         # heads that fill no tile
    (128, 576, 8192, 512, "bfloat16", False)])  # a ring beyond its VMEM
def test_the_kernels_gate_by_shape(heads, row, page, kv_rank, dtype, fits):
    assert latent_attention.available(heads, row, page, kv_rank,
                                      dtype) is fits


def test_the_kernel_route_is_counted_where_the_backend_is_a_tpu(monkeypatch):
    before = route_count("LatentDecodeModel", "kernel")
    assert kernels.decode_attention_route("LatentDecodeModel",
                                          True) == "loop"
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    assert kernels.decode_attention_route("LatentDecodeModel",
                                          False) == "loop"
    assert route_count("LatentDecodeModel", "kernel") == before
    assert kernels.decode_attention_route("LatentDecodeModel",
                                          True) == "kernel"
    assert route_count("LatentDecodeModel", "kernel") == before + 1


# -- ISSUE 40: the kernel writes the step's rows where the pages lie ----------

def pool_write_count(route):
    fam = telemetry.get_registry().counter(
        "dl4j_decode_pool_write_total", kernels.POOL_WRITE_HELP,
        ("model", "route"))
    return fam.labels(model="LatentDecodeModel", route=route).value


KERNEL = latent_attention.latent_page_attention


def scatter_then_read(q, pool, walk, rows, pidx, *, layer, **kw):
    """The kernel's route as it stood before ISSUE 40: XLA takes each fed
    slot's page out, selects the new column in and scatters the whole page
    back, and the kernel only reads (no ``pidx`` names a page to write)."""
    page = pool.shape[-1]
    cols = jnp.arange(page)[None, :]
    pages = pool[layer, pidx]
    pages = jnp.where((cols == (walk["pos"] % page)[:, None])[:, None, :],
                      rows[:, :, None], pages)
    pool = pool.at[layer, pidx].set(pages)
    return KERNEL(q, pool, walk, rows, jnp.zeros_like(pidx), layer=layer,
                  interpret=True, **kw)


WRITES = {
    # slot 0 fills its first page's last column, then opens its second;
    # slot 1 fills its second page, then opens its third; slot 3 fills the
    # last column of its table
    "a page filled, then a page opened":
        lambda: ([PAGE - 2, 2 * PAGE - 1, 40, 3 * PAGE - 3], own_tables(),
                 None),
    "tables in a drawn order, idle slots between fed ones":
        lambda: (*idle([PAGE - 1, 0, 2 * PAGE + 7, 0],
                       own_tables(np.random.default_rng(3)), 1, 3), None),
    # the masked step zeroes an inactive slot's position and page, not its
    # row of the table: its walk reads a live page, which nobody may write
    "a masked step's inactive slot holds live pages":
        lambda: ([PAGE - 1, PAGE + 5, 2 * PAGE, 70], own_tables(),
                 np.array([True, False, True, True])),
}


@pytest.mark.parametrize("case", list(WRITES))
def test_the_kernel_writes_the_rows_the_scatter_wrote(
        case, interpreted, monkeypatch):
    """Three token steps from one pool by three routes: the kernel writing
    the fed slots' rows where their pages lie, the kernel after XLA's
    gather, select and scatter (the step before ISSUE 40), and the loop.
    The first two are the same numbers in another place: the same tokens
    and a pool bit-identical on every page but the scratch page, which the
    scatter writes for the slots that are not fed and the kernel leaves
    alone. The loop sums the attention in another order: the same tokens,
    the first layer's rows bit-identical (they come before any attention),
    the second's at float32's tolerance. Each route's step is counted once
    under what wrote its rows."""
    pos0, table, active = WRITES[case]()
    rng = np.random.default_rng(17)
    pool0 = rng.normal(size=paged_model()._pool_shape()).astype(np.float32)
    tokens0 = rng.integers(3, 96, SLOTS).astype(np.int32)
    fed = np.asarray(active if active is not None
                     else table[np.arange(SLOTS), np.asarray(pos0) // PAGE]
                     != 0)

    def three_steps(route):
        m = paged_model()           # a model of its own: a trace of its own
        before = pool_write_count(route)
        state = {"latent": jnp.asarray(pool0)}
        pos, tokens, out = np.array(pos0, np.int32), tokens0, []
        for _ in range(3):
            if active is None:
                nxt, state, *_ = m.step(state, tokens, pos, table)
            else:
                nxt, state = m.step_masked(state, tokens, pos, table, active)
            tokens = np.where(fed, np.asarray(nxt), tokens0).astype(np.int32)
            out.append(np.where(fed, np.asarray(nxt), -1))
            pos = np.where(fed, pos + 1, pos).astype(np.int32)
        assert pool_write_count(route) == before + 1
        return np.stack(out), np.asarray(state["latent"])

    written, pool = three_steps("kernel")
    monkeypatch.setattr(latent_attention, "latent_page_attention",
                        scatter_then_read)
    scattered, before = three_steps("kernel")
    monkeypatch.setattr(kernels, "_on_tpu", lambda: False)
    looped, loop_pool = three_steps("scatter")

    np.testing.assert_array_equal(written, scattered)
    np.testing.assert_array_equal(written, looped)
    np.testing.assert_array_equal(pool[:, 1:], before[:, 1:])
    np.testing.assert_array_equal(pool[0, 1:], loop_pool[0, 1:])
    np.testing.assert_allclose(pool[1:, 1:], loop_pool[1:, 1:], rtol=TOL,
                               atol=TOL)
    # the rows went in: every fed slot's three columns changed, and the
    # pages of a slot that was not fed came out as they went in
    changed = np.any(pool != pool0, axis=(0, 2))     # [pages, page]
    for s in range(SLOTS):
        cols = [(table[s, p // PAGE], p % PAGE)
                for p in range(pos0[s], pos0[s] + 3)]
        if fed[s]:
            assert all(changed[c] for c in cols)
        else:
            assert not changed[table[s]].any()
    assert changed[1:].sum() == 3 * fed.sum()
