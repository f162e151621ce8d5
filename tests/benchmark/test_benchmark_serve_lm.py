"""The `serve_closed_lm` driver, its arithmetic and its readers without a chip:
the manifest's new entries and the cell's files as data, the least-work counts
against a brute-force count at a small size and the parameter total at the
cell's, each new reader over events and counters written by hand, and the
driver end to end on a toy manifest of its own (`data/toy-lm-serve`)."""

import argparse
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TOY = os.path.join(ROOT, "tests", "benchmark", "data", "toy-lm-serve")
CELL, SIBLING = "deepseek-v3.closed-128", "bert-base-decoder.closed-64"
NEW = ("serve.lm_step_mfu", "serve.lm_step_roofline",
       "serve.latent_attention_roofline", "serve.expert_matmul_roofline",
       "serve.expert_load_max_over_mean")

from benchmark import run  # noqa: E402
from benchmark.drivers import serve_closed_lm as driver  # noqa: E402
from benchmark.lib import arith_mla, traffic  # noqa: E402
from benchmark.reference import deepseek_v3_plain as plain  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAK = json.load(f)["TPU v5 lite"]


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def cell(manifest):
    return run.load_cell(ROOT, manifest, CELL)


# -- the manifest and the cell as data ----------------------------------------

def test_the_cell_reports_its_own_metrics_and_the_engines(manifest):
    names = lambda section, c: [m["name"] for m in run.cell_metrics(  # noqa: E731
        manifest, section, c)]
    assert names("end_to_end", CELL) == names("end_to_end", SIBLING) == [
        "decode_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"]
    bert_block = {"serve.step_mfu", "serve.step_roofline"}
    mine, theirs = set(names("per_layer", CELL)), \
        set(names("per_layer", SIBLING))
    assert mine - theirs == set(NEW) and theirs - mine == bert_block
    assert {"serve.live_page_share", "serve.overlapped_boundary_share",
            "device_idle_share.serve", "serve.kv_page_fill"} <= mine
    for m in manifest["per_layer"]:
        if m["name"] in bert_block:
            assert m["workloads"] == [SIBLING]
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "decode_tokens_per_s"


def test_a_new_cell_is_data_alone(manifest):
    """`test_benchmark_manifest.py`'s test of that name as it reads since two
    per-layer metrics name their cell: a cell added as data is a file of its
    own, an appended entry, and its name appended to every list that names
    its sibling, in both sections. Nothing else changes, and it reports what
    its sibling reports."""
    new, like = "bert-base-decoder.short-16", SIBLING
    grown = json.loads(json.dumps(manifest))
    grown["workloads"].append(dict(
        run.find(manifest["workloads"], like, "workload"), name=new,
        traffic="short-16", why="prompts 4-16"))
    for section in ("end_to_end", "per_layer"):
        for m in grown[section]:
            if like in m.get("workloads", []):
                m["workloads"].append(new)
    for section in ("end_to_end", "per_layer"):
        names = lambda man, c: [m["name"] for m in run.cell_metrics(  # noqa: E731
            man, section, c)]
        assert names(grown, new) == names(manifest, like)
        for w in manifest["workloads"]:      # and the others' are as before
            assert names(grown, w["name"]) == names(manifest, w["name"])


def test_the_configuration_file_holds_the_published_keys(manifest, cell):
    entry = run.find(manifest["configs"], "deepseek-v3-ep16-share", "config")
    _, body, cfg = cell
    changed = {"n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert set(entry["reduced"]) == changed | {"num_layers"}
    for key, value in cfg["published"].items():
        assert (cfg[key] != value) == (key in changed), key
    m = cfg["model"]
    assert cfg["num_layers"] == m["num_layers"] == len(m["layer_ids"]) == 5
    assert m["layer_ids"] == [0, 3, 4, 5, 6]
    assert m["layer_kinds"] == ["dense"] + ["sparse"] * 4
    assert cfg["n_routed_experts"] == m["experts_held"][1] == 256 // 16
    assert cfg["vocab_size"] == m["vocab_size"] == 129280 // 8
    assert cfg["num_nextn_predict_layers"] == 0
    assert (m["n_routed_experts_published"], m["vocab_size_published"],
            m["num_hidden_layers_published"]) == (256, 129280, 61)
    assert {"page", "engine options", "rotary", "router"} <= set(cfg["assumed"])
    for key in ("precision", "deployment", "weights"):
        assert cfg[key]
    eng = cfg["engine"]
    assert eng["max_slots"] == body["traffic"]["callers"] == 128
    assert eng["page"] * eng["max_pages_per_slot"] == 2048
    assert set(body["limits"]) == {"logit_gap_max", "logit_gap_mean",
                                   "window_compiles", "moe_dropped"}
    assert body["limits"]["moe_dropped"] == body["limits"][
        "window_compiles"] == 0


def test_the_traffic_fits_the_slots_and_the_vocabulary(cell):
    import itertools

    _, body, cfg = cell
    tr = body["traffic"]
    reqs = list(itertools.islice(traffic.requests(tr, 2 ** 31 + 5), 64))
    longest = cfg["engine"]["page"] * cfg["engine"]["max_pages_per_slot"]
    assert max(len(p) + n for p, n in reqs) <= longest
    assert max(n for _, n in reqs) <= cfg["engine"]["options"][
        "max_new_limit"]
    assert min(len(p) for p, _ in reqs) >= 32 and min(n for _, n in reqs) >= 128
    ids = [t for p, _ in reqs for t in p]
    assert 3 <= min(ids) and max(ids) < cfg["model"]["vocab_size"]


# -- arithmetic ---------------------------------------------------------------

def test_least_work_counts_at_the_cells_sizes(cell):
    _, _, cfg = cell
    pub, m = cfg["published"], cfg["model"]
    assert arith_mla.attention_params(pub) == 187_121_664
    assert arith_mla.layer_params(pub, m, "dense") == 583_483_392
    assert arith_mla.layer_params(pub, m, "sparse") == 937_640_192
    assert arith_mla.total_params(pub, m) == m["parameters"] == 4_565_721_088
    assert arith_mla.latent_row_bytes(pub, 2) == 1152
    assert arith_mla.attend_flops_per_row(pub) == 2 * 128 * (576 + 512)
    # what the reference draws is what is counted, leaf by leaf
    shapes = jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(cfg)))
    assert plain.count_params(shapes) == 4_565_721_088
    # a step of 128 slots at a context of 400: weight-bound, 11 ms
    nbytes = arith_mla.step_bytes(pub, m, 128, 400, 64, 2, 2)
    flops = 128 * arith_mla.flops_per_position(pub, m, 400, 2.0)
    assert 9.0e9 < nbytes < 9.3e9 and 0.4e12 < flops < 0.6e12
    assert nbytes / PEAK["hbm_bytes_per_s"] > flops / PEAK["bf16_flops_per_s"]


def test_flops_a_position_against_a_brute_force_count():
    """Every product of the absorbed step at the toy's sizes, listed by hand
    as (rows, inner, columns)."""
    cfg = run.load_json(TOY, "configs", "toy-latent-lm.json")
    pub, m = cfg["published"], cfg["model"]
    d, h, ctx, held = 64, 4, 11, 3.0
    attention = [(1, d, 24), (1, 24, h * 24), (1, d, 16 + 8)] \
        + [(1, 16, 16)] * h \
        + [(ctx, 24, 1)] * h + [(1, ctx, 16)] * h \
        + [(1, 16, 16)] * h + [(1, h * 16, d)]
    dense = [(1, d, 96), (1, d, 96), (1, 96, d)]
    expert = [(1, d, 32), (1, d, 32), (1, 32, d)]
    products = 3 * attention + dense + 2 * ([(1, d, 16)] + expert) \
        + [(1, d, 96)]
    brute = sum(2 * a * b * c for a, b, c in products) \
        + held * sum(2 * a * b * c for a, b, c in expert)
    assert arith_mla.flops_per_position(pub, m, ctx, held) == brute
    # bytes: every parameter once, less the unread embedding rows and the
    # untouched experts, and the live rows of the pool
    params = plain.count_params(jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(cfg))))
    assert arith_mla.total_params(pub, m) == params
    assert arith_mla.step_bytes(pub, m, 4, ctx, 16, 2, 2) == \
        2 * (params - (96 - 4) * d) + 3 * 4 * (ctx + 1) * 24 * 2
    assert arith_mla.step_bytes(pub, m, 4, ctx, 10, 2, 2) == \
        2 * (params - (96 - 4) * d - 6 * 3 * d * 32) \
        + 3 * 4 * (ctx + 1) * 24 * 2


# -- the readers --------------------------------------------------------------

def _r(cfg, ops, modules, **counters):
    return {"trace": {"devices": {0: {"ops": ops, "modules": modules}},
                      "used": [0], "t0": 0.0, "t1": 10.0, "host": {}},
            "counters": dict(step_executable="jit__fn", w_itemsize=2,
                             kv_itemsize=2, **counters),
            "peak": PEAK, "config": cfg}


def test_the_new_readers_read_the_trace_and_the_counters(cell):
    _, _, cfg = cell
    pub, m = cfg["published"], cfg["model"]
    modules = [("jit__fn(7)", 1.0, 0.020), ("jit__fn(7)", 2.0, 0.020),
               ("jit__pick_token(3)", 1.5, 1e-6)]
    ops = [("while (s32[], f32[1024,128])", 1.001, 0.0010),
           ("while (s32[], f32[1024,128])", 1.010, 0.0014),
           ("ragged-dot-none f32[1024,2048]", 1.012, 0.0003),
           ("ragged-dot-metadata s32[17]", 1.013, 0.0001),
           ("fusion bf16[128,7168]", 1.015, 0.002),
           ("while (s32[], f32[1024,128])", 2.001, 0.0024),
           ("ragged-dot-none f32[1024,2048]", 2.012, 0.0004)]
    traced = {"held_choices_per_step": 256.0, "touched_experts_per_step": 60.0,
              "held_choices_per_position": 2.0}
    r = _r(cfg, ops, modules, positions=128 * 2000, seconds=50.0,
           mean_context=400.0, live_slots=128.0, moe_window=traced,
           moe_traced=traced, moe_model="m")
    mfu, step, attend, experts = (run.load_reader(n)(r) for n in NEW[:4])
    per_pos = arith_mla.flops_per_position(pub, m, 400.0, 2.0)
    assert mfu == pytest.approx(100 * 128 * 2000 * per_pos / 50.0 / 197e12)
    nbytes = arith_mla.step_bytes(pub, m, 128, 400.0, 60.0, 2, 2)
    assert step == pytest.approx(100 * nbytes / 819e9 / 0.020)
    assert 50 < step < 60
    rows = 128 * 401 * 5
    # 242 FLOPs a byte: a hair on the compute side of this chip's ridge
    assert attend == pytest.approx(
        100 * rows * 2 * 128 * (576 + 512) / 197e12 / 0.0024)
    assert attend == pytest.approx(100 * rows * 1152 / 819e9 / 0.0024,
                                   rel=0.01)
    assert experts == pytest.approx(
        100 * 60 * 3 * 7168 * 2048 * 2 / 819e9 / 0.0004)
    # nothing to read: no launch in the traced part, or no counts
    assert run.load_reader(NEW[2])(_r(cfg, ops, [], live_slots=128.0,
                                      mean_context=1.0)) is None
    bare = _r(cfg, ops, modules, positions=10, seconds=1.0, mean_context=1.0,
              live_slots=2.0)
    assert [run.load_reader(n)(bare) for n in (NEW[0], NEW[1], NEW[3])] == \
        [None, None, None]


def test_the_load_reader_reads_its_models_label():
    from deeplearning4j_tpu import telemetry

    two_sparse = {"model": {"layer_kinds": ["dense", "sparse", "sparse"]}}
    r = lambda model: {"counters": {"moe_model": model},  # noqa: E731
                       "config": two_sparse}

    old = telemetry.get_registry()
    telemetry.set_registry(telemetry.MetricsRegistry())
    try:
        read = run.load_reader(NEW[4])
        assert read(r("m")) is None
        mine = telemetry.moe_instruments("m")
        for _ in range(4):
            mine.step((1, 2), [(512, 30, 0, 1.5, 14), (512, 34, 0, 2.5, 15)])
        telemetry.moe_instruments("other").step((1,), [(8, 1, 0, 9.0, 1)])
        assert read(r("m")) == pytest.approx(2.0)
        assert read(r("none")) is None
    finally:
        telemetry.set_registry(old)


# -- the driver at a small size, on the CPU -----------------------------------

def toy_run(trace_=0, seed=2 ** 31 + 11):
    args = argparse.Namespace(workload="toy-latent-lm.closed-4", seed=seed,
                              seconds=1.5, trace=trace_)
    return run.run(args, root=TOY, devices=jax.devices(), peak=PEAK)


def test_driver_end_to_end_on_the_toy_manifest():
    result, rows = toy_run()
    assert result["correct"], rows
    assert set(result["metrics"]) == {"decode_tokens_per_s", "ttft_p95_ms",
                                      "itl_p95_ms", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == {"logit_gap_max", "logit_gap_mean",
                                       "moe_dropped", "window_compiles"}
    assert result["compared"]["moe_dropped"] == {"value": 0.0, "limit": 0}
    assert result["compared"]["window_compiles"] == {"value": 0, "limit": 0}
    json.dumps(result)


def test_the_comparison_passes_what_was_served_and_fails_other_weights():
    """The driver's set-up, a window and its sample; then the comparison on
    what was served (passes), on the reference's own tokens in float8 (the
    control's path), and under other weights than served them (fails)."""
    manifest = run.load_json(TOY, "BENCHMARK.json")
    _, body, cfg = run.load_cell(TOY, manifest, "toy-latent-lm.closed-4")
    ctx = run.Context(body, cfg, 2 ** 31 + 13, 1.0, 0, jax.devices())
    st = driver.setup(ctx)
    win = driver.measure(ctx, st)
    assert win["failed"] == 0 and win["end_to_end"]["decode_tokens_per_s"] > 0
    moe = win["counters"]["moe_window"]
    # 8 of 16 experts held, 4 chosen of 16 a position and sparse layer, two
    # sparse layers: some 4 held choices a position, never more than 8
    assert 1.0 < moe["held_choices_per_position"] < 8.0
    assert 0 < moe["touched_experts_per_step"] <= 16
    sizes, requests = st.sizes, st.requests
    chosen = driver.sample(ctx, st)
    driver.free(st)
    assert len(chosen) == body["check_requests"]
    limit = body["limits"]["logit_gap_max"]
    served, _ = driver.reference_gaps(ctx, sizes, requests, chosen)
    control, _ = driver.reference_gaps(ctx, sizes, requests, chosen,
                                       control="fp8")
    other = run.Context(body, cfg, 5, 1.0, 0, jax.devices())
    wrong, _ = driver.reference_gaps(other, sizes, requests, chosen)
    assert served["logit_gap_max"] <= limit < wrong["logit_gap_max"]
    # at these widths the best token wins by a margin no rounding crosses:
    # the control runs here, and has to fail only at the cell's size
    # (`tools/calibrate.py --what program,control`, PERF.md section 2)
    assert 0.0 <= control["logit_gap_mean"] <= control["logit_gap_max"]
