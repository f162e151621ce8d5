"""The `train_lm` driver, its arithmetic and its readers without a chip: the
driver end to end on a toy manifest of its own (`data/toy-lm`), planted faults
that `correct` has to catch, the least-work counts at the cell's sizes, the
kernel readers over events written by hand, and one sliding sparse layer at
the published widths compiled for a described v5e (nothing runs)."""

import argparse
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TOY = os.path.join(ROOT, "tests", "benchmark", "data", "toy-lm")
CELL = "laguna-xs2.train-2x8192"

from benchmark import run  # noqa: E402
from benchmark.drivers import train_lm  # noqa: E402
from benchmark.lib import arith_lm, compare, readers_lm  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAK = json.load(f)["TPU v5 lite"]


def toy_context(seed=2 ** 31 + 7):
    manifest = run.load_json(TOY, "BENCHMARK.json")
    _, cell, config = run.load_cell(TOY, manifest, "toy-laguna.train-1")
    return run.Context(cell, config, seed, 0.5, 0, jax.devices())


@pytest.fixture(scope="module")
def toy_run():
    """The program's first three steps and the reference's, once."""
    ctx = toy_context()
    st = train_lm.setup(ctx)
    program = st.program
    train_lm.free(st)
    return ctx, program, train_lm.reference_readings(ctx, program)


def test_driver_end_to_end_on_the_toy_manifest():
    args = argparse.Namespace(workload="toy-laguna.train-1",
                              seed=2 ** 31 + 7, seconds=1.0, trace=0)
    result, rows = run.run(args, root=TOY, devices=jax.devices(), peak=PEAK)
    assert result["correct"], rows
    assert set(result["metrics"]) == {"train_tokens_per_s_per_chip",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap", "grad_distance",
        "held_choices_gap", "moe_dropped", "window_compiles"}
    assert result["compared"]["moe_dropped"] == {"value": 0.0, "limit": 0}
    assert result["compared"]["window_compiles"] == {"value": 0, "limit": 0}
    json.dumps(result)


def test_the_program_is_within_the_toy_limits(toy_run):
    ctx, program, ref = toy_run
    numbers, _ = train_lm.compare_with(program, ref)
    numbers.update(moe_dropped=0, window_compiles=0)
    rows = compare.verdict(numbers, ctx.cell["limits"])
    assert all(ok for *_, ok in rows), rows
    # the reference against itself reads nought
    again, _ = train_lm.compare_with(ref, ref)
    assert max(again.values()) == 0.0


@pytest.mark.parametrize("planted", [
    {"fault": "no_window"}, {"fault": "drop_expert"}, {"mode": "fp8"},
    {"fault": "keep_rows"}])
def test_a_planted_fault_is_not_correct(toy_run, planted):
    """The window ignored, one expert left out, float8 operands, or half the
    batch, put in the program's place: at least one limit says no."""
    ctx, program, ref = toy_run
    got = train_lm.reference_readings(ctx, program, **planted)
    numbers, _ = train_lm.compare_with(got, ref)
    numbers.update(moe_dropped=0, window_compiles=0)
    rows = compare.verdict(numbers, ctx.cell["limits"])
    assert not all(ok for *_, ok in rows), rows


def test_the_window_counts_the_choices_the_router_sent_here():
    """The step's FLOPs come from the program's own count of held choices
    over the window's steps, not from the even share."""
    ctx = toy_context(seed=11)
    st = train_lm.setup(ctx)
    st.trainer.publish_router_counts()
    before = train_lm._routed()
    out = train_lm.measure(ctx, st)
    held, steps = train_lm._routed() - before
    train_lm.free(st)
    c, m, tr = out["counters"], ctx.config["model"], ctx.cell["traffic"]
    assert steps == out["attempted"] == c["steps"] and steps > 0
    assert c["held_choices_per_step"] == pytest.approx(held / steps)
    # top-2 of 8 experts, 4 held, 4 sparse layers: 64 a layer if even
    assert 0 < c["held_choices_per_step"] <= 4 * 2 * tr["rows"] * tr["seq"]
    assert c["flops_per_step"] == arith_lm.train_flops_per_step(
        ctx.config["published"], m, tr["rows"], tr["seq"], held / steps)
    assert "held_choices_per_step_traced" not in c


def test_the_seed_decides_the_batches():
    tr = {"rows": 2, "seq": 16}
    a, b, c = (next(train_lm.traffic_lm.lm_batches(tr, 64, s))
               for s in (5, 5, 2 ** 31 + 6))
    assert (a[0] == b[0]).all() and (a[0] != c[0]).any()
    assert a[0].min() >= 3 and a[0].max() < 64
    assert (a[1][:, :-1] == a[0][:, 1:]).all() and (a[1][:, -1] == -100).all()


def test_least_work_counts_at_the_cells_sizes():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    _, cell, config = run.load_cell(ROOT, manifest, CELL)
    pub, m, tr = config["published"], config["model"], cell["traffic"]
    assert arith_lm.attended_pairs(8192) == 8192 * 8193 // 2
    # 512 rows grow to the window, the other 7,680 see 512 keys each
    assert arith_lm.attended_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert arith_lm.attended_pairs(8, 512) == 36
    assert arith_lm.expert_flops_per_choice(pub) == 3 * 2 * 2048 * 512
    attn = arith_lm.attention_flops_per_step(pub, m, tr["rows"], tr["seq"])
    by_hand = 3 * 2 * 4 * 128 * (2 * 48 * (8192 * 8193 // 2)
                                 + 3 * 64 * (512 * 513 // 2 + 7680 * 512))
    assert attn == by_hand
    flops = arith_lm.train_flops_per_step(pub, m, tr["rows"], tr["seq"])
    assert 39.3e12 < flops < 39.5e12
    # that is the expectation, 16,384 held choices a sparse layer; a step is
    # counted from the choices the router did send here
    assert arith_lm.train_flops_per_step(
        pub, m, tr["rows"], tr["seq"], held_choices=4 * 16384) == flops
    assert arith_lm.train_flops_per_step(
        pub, m, tr["rows"], tr["seq"], held_choices=4 * 16384 + 1000) \
        - flops == 3 * 1000 * 3 * 2 * 2048 * 512
    # a kernel that masks the sliding layers' whole product does 16x their
    # least work
    assert 16 < 8192 * 8192 / arith_lm.attended_pairs(8192, 512) < 17
    # the parameters the configuration's file states, from the shapes
    from deeplearning4j_tpu.models import causal_lm as lm

    shapes = jax.eval_shape(lambda: lm.init_params(
        train_lm.build_config(config), jax.random.key(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == m["parameters"] == 691623936


def test_the_configuration_file_holds_the_published_keys():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = run.find(manifest["configs"], "laguna-xs2-ep8-share", "config")
    cfg = run.load_json(ROOT, entry["file"])
    changed = {"num_experts", "vocab_size"}
    assert set(entry["reduced"]) == changed | {"num_layers"}
    for key, value in cfg["published"].items():
        assert (cfg[key] != value) == (key in changed), key
    assert cfg["num_layers"] == cfg["model"]["num_layers"] == 5
    assert cfg["num_experts"] == cfg["model"]["num_experts"] == 32
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"] == 100352 // 8
    assert set(cfg["assumed"]) >= {"gate", "router", "activation", "qk_norm",
                                   "shared_expert"}


def _r(ops, modules):
    return {"trace": {"devices": {0: {"ops": ops, "modules": modules}},
                      "used": [0], "t0": 0.0, "t1": 10.0},
            "counters": {"step_executable": "jit_step", "chips": 1}}


def test_kernel_seconds_are_those_of_whole_launches():
    modules = [("jit_step(1)", 1.0, 2.0), ("jit_step(1)", 4.0, 2.0),
               ("jit_step(1)", 9.0, 2.0), ("jit_other(2)", 3.0, 0.5)]
    ops = [("splash_mqa_fwd_residuals f32[2,8,512,128]", 1.1, 0.3),
           ("splash_mqa_dkv_no_residuals f32[2,8,512,128]", 4.5, 0.5),
           ("ragged-dot-none f32[32768,512]", 4.0, 0.2),
           ("ragged-dot-metadata s32[33]", 5.0, 0.1),
           ("fusion bf16[2,8192,2048]", 2.0, 0.5),
           ("splash_mqa_fwd_residuals f32[2,8,512,128]", 3.1, 0.2),  # other
           ("splash_mqa_dq_no_residuals f32[2,8,512,128]", 9.5, 0.4)]  # cut
    r = _r(ops, modules)
    assert readers_lm.kernel_seconds_per_step(r, ("splash_",)) == \
        pytest.approx(0.4)
    assert readers_lm.kernel_seconds_per_step(r, ("ragged-dot",)) == \
        pytest.approx(0.15)
    assert readers_lm.kernel_seconds_per_step(r, ("megablox",)) is None
    assert readers_lm.kernel_seconds_per_step(_r(ops, []), ("s",)) is None


def test_the_new_readers_read_the_trace_and_the_registry():
    from deeplearning4j_tpu import telemetry

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    _, cell, config = run.load_cell(ROOT, manifest, CELL)
    r = _r([("splash_mqa_fwd_residuals f32[2,8,512,128]", 1.0, 0.1),
            ("ragged-dot-none f32[32768,512]", 1.2, 0.02)],
           [("jit_step(1)", 0.5, 1.0)])
    r.update(cell=cell, config=config, peak=PEAK)
    names = ("train.attention_roofline", "train.expert_matmul_roofline",
             "train.expert_load_max_over_mean")
    old = telemetry.get_registry()
    telemetry.set_registry(telemetry.MetricsRegistry())
    try:
        attention, experts, load = (run.load_reader(n)(r) for n in names)
        # a program without the counters: nothing to read, nothing raised
        assert experts is None and load is None
        assert attention == pytest.approx(
            100 * 12.293555355648e12 / 197e12 / 0.1)
        reg = telemetry.get_registry()
        for layer, held, load_sum in ((1, 3 * 16384, 3.3), (4, 3 * 16000, 3.9)):
            reg.counter("dl4j_moe_held_choices_total", "", ("layer",)).labels(
                layer=str(layer)).inc(held)
            reg.counter("dl4j_moe_load_max_over_mean_sum", "",
                        ("layer",)).labels(layer=str(layer)).inc(load_sum)
        reg.counter("dl4j_moe_steps_total", "").inc(3)
        experts, load = (run.load_reader(n)(r) for n in names[1:])
        assert load == pytest.approx((3.3 + 3.9) / (2 * 3))
        # the grouped products' share needs the traced steps' own count,
        # which the driver hands over: the engine's life does not do
        assert experts is None
        r["counters"]["held_choices_per_step_traced"] = 32384.0
        assert run.load_reader(names[1])(r) == pytest.approx(
            100 * 3 * 32384 * 6 * 2048 * 512 / 197e12 / 0.02)
    finally:
        telemetry.set_registry(old)


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


def test_a_sliding_sparse_layer_compiles_for_v5e(topo, no_cache,
                                                 monkeypatch):
    """Layer 1 of the cell (sliding attention, 64 heads on 8 K and V heads,
    32 of 256 experts) forward and backward at the published widths and the
    cell's 2x8192 tokens: the splash kernels and the grouped products are
    in the program the chip's compiler accepts."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deeplearning4j_tpu.models import causal_lm as lm

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    _, cell, config = run.load_cell(ROOT, manifest, CELL)
    cfg, tr = train_lm.build_config(config), cell["traffic"]
    spec = cfg.layers[1]
    assert (spec.attention, spec.heads, spec.mlp) == ("sliding", 64, "sparse")
    monkeypatch.setattr(lm, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    lp = on(jax.eval_shape(lambda: lm.init_params(
        cfg, jax.random.key(0))["layers"][1]))
    x = jax.ShapeDtypeStruct((tr["rows"], tr["seq"], cfg.hidden),
                             jnp.bfloat16, sharding=one)

    def loss(lp_, x_):
        tables = {"sliding": lm.rope_tables(cfg.rope["sliding"],
                                            cfg.head_dim, tr["seq"])}
        y, choices, dropped = lm.layer_forward(lp_, x_, cfg, spec, tables)
        return jnp.sum(y.astype(jnp.float32)), (choices, dropped)

    # tests/conftest.py asks for float32 matmuls everywhere, which a Mosaic
    # kernel's bfloat16 product cannot be: compile as an entry point does
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)).lower(lp, x).compile()
    text = compiled.as_text()
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq",
                   "ragged-dot"):
        assert kernel in text, kernel
    m = compiled.memory_analysis()
    print(json.dumps({"argument_bytes": m.argument_size_in_bytes,
                      "temp_bytes": m.temp_size_in_bytes}))
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < PEAK["hbm_bytes"]
