"""The `serve_closed_hybrid` driver, its arithmetic and its readers without a
chip: the manifest's new entries and the cell's files as data, the least-work
counts against a brute-force count at a small size and the parameter total at
the cell's, each new reader over events and counters written by hand, and the
driver end to end on a toy manifest of its own (`data/toy-hybrid-serve`), with
two planted faults that have to come out `correct: false`."""

import argparse
import itertools
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TOY = os.path.join(ROOT, "tests", "benchmark", "data", "toy-hybrid-serve")
CELL, SIBLING = "nemotron3-super.closed-128", "deepseek-v3.closed-128"
CONFIG = "nemotron3-super-ep4-share"
NEW = ("serve.hybrid_step_mfu", "serve.hybrid_step_roofline",
       "serve.ssm_update_roofline", "serve.latent_expert_roofline")

from benchmark import run  # noqa: E402
from benchmark.drivers import serve_closed_hybrid as driver  # noqa: E402
from benchmark.lib import arith_hybrid, traffic  # noqa: E402
from benchmark.reference import nemotron_h_plain as plain  # noqa: E402

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
    mine, theirs = set(names("per_layer", CELL)), \
        set(names("per_layer", SIBLING))
    assert mine - theirs == set(NEW)
    # what the sibling alone reports lists the sibling; nothing lists both
    assert theirs - mine == {
        "serve.lm_step_mfu", "serve.lm_step_roofline",
        "serve.latent_attention_roofline", "serve.expert_matmul_roofline",
        "serve.expert_load_max_over_mean"}
    assert {"serve.itl_p50_ms", "serve.slot_occupancy", "serve.kv_page_fill",
            "serve.live_page_share", "serve.overlapped_boundary_share",
            "device_idle_share.serve"} <= mine
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
            assert m["moves"] == "decode_tokens_per_s"
            assert m["layer"] == ("decode_step" if m["name"] == NEW[0]
                                  else "kernels")
        elif "workloads" in m:
            assert CELL not in m["workloads"], m["name"]
    entry = run.find(manifest["workloads"], CELL, "workload")
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert manifest["workloads"][-1] is entry
    assert manifest["configs"][-1]["name"] == CONFIG


def test_the_configuration_file_holds_the_published_keys(manifest, cell):
    entry = run.find(manifest["configs"], CONFIG, "config")
    _, body, cfg = cell
    changed = {"n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert set(entry["reduced"]) == changed | {"num_layers"}
    for key, value in cfg["published"].items():
        assert (cfg[key] != value) == (key in changed), key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == entry["source"])
    assert cfg["published"] == row["config"]
    m, pattern = cfg["model"], cfg["published"]["hybrid_override_pattern"]
    assert cfg["num_layers"] == m["num_layers"] == len(m["layer_ids"]) == 11
    assert m["layer_ids"] == list(range(11))
    assert m["pattern"] == pattern[:11] == "MEMEMEM*EME"
    assert m["layer_kinds"] == [
        {"M": "mamba", "E": "sparse", "*": "attention"}[c]
        for c in pattern[:11]]
    # a whole period in the published ratio: 40 M, 40 E, 8 * of 88
    assert [pattern.count(c) for c in "ME*"] == [40, 40, 8]
    assert [m["layer_kinds"].count(k)
            for k in ("mamba", "sparse", "attention")] == [5, 5, 1]
    assert cfg["n_routed_experts"] == m["experts_held"][1] == 512 // 4
    assert m["experts_held"][0] == 0
    assert cfg["vocab_size"] == m["vocab_size"] == 131072 // 4
    assert cfg["num_nextn_predict_layers"] == 0
    assert (m["n_routed_experts_published"], m["vocab_size_published"],
            m["num_hidden_layers_published"]) == (512, 131072, 88)
    assert {"rotary", "latent projections", "mamba", "router", "page",
            "engine options"} <= set(cfg["assumed"])
    assert "rotary" in cfg["assumed"]["rotary"]
    for key in ("precision", "deployment", "weights"):
        assert cfg[key]
    assert "float32" in cfg["precision"]
    eng = cfg["engine"]
    assert eng["max_slots"] == body["traffic"]["callers"] == 128
    assert eng["page"] * eng["max_pages_per_slot"] == 768
    assert "chunk" not in eng["options"] and \
        "prefix_cache" not in eng["options"]
    assert body["driver"] == "serve_closed_hybrid"
    assert (body["trace_seconds"], body["check_requests"]) == (2, 12)
    assert set(body["limits"]) == {"logit_gap_max", "logit_gap_mean",
                                   "window_compiles", "moe_dropped"}
    assert body["limits"]["moe_dropped"] == body["limits"][
        "window_compiles"] == 0


def test_the_traffic_fits_the_slots_and_the_vocabulary(cell):
    _, body, cfg = cell
    tr = body["traffic"]
    assert tr["prompt"] == {"median": 48, "sigma": 0.8, "min": 16, "max": 256}
    assert tr["answer"] == {"median": 128, "sigma": 0.6, "min": 32,
                            "max": 512}
    assert (tr["cycle"], tr["ids"], tr["ramp_timeout_s"]) == (
        32, [3, 32767], 240)
    reqs = list(itertools.islice(traffic.requests(tr, 2 ** 31 + 5), 64))
    longest = cfg["engine"]["page"] * cfg["engine"]["max_pages_per_slot"]
    assert max(len(p) + n for p, n in reqs) <= longest
    assert max(n for _, n in reqs) <= 1024      # the engine's max_new_limit
    assert min(len(p) for p, _ in reqs) >= 16 and \
        min(n for _, n in reqs) >= 32
    ids = [t for p, _ in reqs for t in p]
    assert 3 <= min(ids) and max(ids) < cfg["model"]["vocab_size"]
    # some 210 positions a request: a slot turns over every few seconds
    mean = sum(len(p) + n for p, n in reqs) / len(reqs)
    assert 170 < mean < 260


# -- arithmetic ---------------------------------------------------------------

def test_least_work_counts_at_the_cells_sizes(cell):
    _, _, cfg = cell
    pub, m = cfg["published"], cfg["model"]
    by_layer = m["parameters_by_layer"]
    assert arith_hybrid.mamba_params(pub) == by_layer["mamba"] == 109_640_064
    assert arith_hybrid.attention_params(pub) == by_layer["attention"] \
        == 35_655_680
    assert arith_hybrid.expert_params(pub) == by_layer["routed_expert"] \
        == 5_505_024
    assert arith_hybrid.sparse_params(pub, m) == by_layer["sparse"] \
        == 54_530_560 + 128 * 5_505_024 == 759_173_632
    assert arith_hybrid.total_params(pub, m) == m["parameters"] \
        == 4_648_163_712
    # the whole model by the same equations: the published 120B-A12B
    whole = dict(m, layer_kinds=[
        {"M": "mamba", "E": "sparse", "*": "attention"}[c]
        for c in pub["hybrid_override_pattern"]],
        experts_held=[0, 512], vocab_size=131072)
    total = arith_hybrid.total_params(pub, whole)
    active = total - 40 * (512 - 22) * arith_hybrid.expert_params(pub)
    assert round(total / 1e9, 2) == 120.67 and round(active / 1e9, 2) == 12.77
    # what the reference draws is what is counted, leaf by leaf
    shapes = jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(cfg)))
    assert plain.count_params(shapes) == 4_648_163_712
    assert arith_hybrid.kv_row_bytes(pub, 2) == 1024
    assert arith_hybrid.update_flops_per_position(pub) == 5 * 128 * 64 * 128
    # the state at 128 slots: 4 MB a slot and layer, float32, and the tails
    assert arith_hybrid.slot_state_bytes(pub, m, 128, 2) == \
        5 * 128 * (128 * 64 * 128 * 4 + 3 * 10240 * 2) == 2_723_676_160
    assert arith_hybrid.update_bytes(pub, m, 128) == 2 * 5 * 128 * 4 * 2 ** 20
    # a step of 128 slots at a context of 100: bandwidth-bound, 17.7 ms
    nbytes = arith_hybrid.step_bytes(pub, m, 128, 100, 640, 2, 2)
    flops = 128 * arith_hybrid.flops_per_position(pub, m, 100, 27.5)
    assert 14.3e9 < nbytes < 14.8e9 and 0.28e12 < flops < 0.31e12
    assert nbytes / PEAK["hbm_bytes_per_s"] > flops / PEAK["bf16_flops_per_s"]
    # the attention layer's live rows: under 1% of the step's bytes
    assert 128 * 101 * 1024 < 0.01 * nbytes


def test_flops_a_position_against_a_brute_force_count():
    """Every product of the step at the toy's sizes, listed by hand as
    (rows, inner, columns), and the state's update a number at a time."""
    cfg = run.load_json(TOY, "configs", "toy-hybrid-lm.json")
    pub, m = cfg["published"], cfg["model"]
    d, ctx, held = 64, 11, 3.0
    inner, width, state = 8 * 8, 8 * 8 + 2 * 2 * 16, 8 * 8 * 16
    mamba = [(1, d, inner + width + 8), (1, inner, d)]
    attention = [(1, d, 64), (1, d, 32), (1, d, 32), (1, 64, d)] \
        + [(ctx, 16, 1)] * 4 + [(1, ctx, 16)] * 4
    sparse = [(1, d, 16), (1, d, 24), (1, 24, d), (1, d, 48), (1, 48, d)]
    expert = [(1, 24, 32), (1, 32, 24)]
    products = 3 * mamba + attention + 3 * sparse + [(1, d, 96)]
    brute = sum(2 * a * b * c for a, b, c in products) \
        + 3 * (2 * 4 * width + 5 * state) \
        + held * sum(2 * a * b * c for a, b, c in expert)
    assert arith_hybrid.flops_per_position(pub, m, ctx, held) == brute
    params = plain.count_params(jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(cfg))))
    assert arith_hybrid.total_params(pub, m) == params
    slot = 3 * 4 * (state * 4 + 3 * width * 2)
    assert arith_hybrid.slot_state_bytes(pub, m, 4, 2) == slot
    assert arith_hybrid.step_bytes(pub, m, 4, ctx, 24, 2, 2) == \
        2 * (params - (96 - 4) * d) + 2 * slot + 4 * (ctx + 1) * 64 * 2
    assert arith_hybrid.step_bytes(pub, m, 4, ctx, 20, 2, 2) == \
        2 * (params - (96 - 4) * d - 4 * 2 * 24 * 32) + 2 * slot \
        + 4 * (ctx + 1) * 64 * 2


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
    update, experts = (run.load_reader(n).__globals__["ops"](cfg)
                       for n in NEW[2:])
    assert update == ("fusion f32[128,128,64,128]",)
    assert experts == ("fusion bf16[128,1024]", "ragged-dot")
    modules = [("jit__fn(7)", 1.0, 0.025), ("jit__fn(7)", 2.0, 0.025),
               ("jit__pick_token(3)", 1.5, 1e-6)]
    ops = [(update[0], 1.001, 0.0014), (update[0], 1.004, 0.0016),
           (experts[0], 1.010, 0.0020),
           ("ragged-dot-none f32[2816,2688]", 1.013, 0.0010),
           ("fusion bf16[128,4096]", 1.015, 0.002),
           (update[0], 2.001, 0.0030), (experts[0], 2.012, 0.0030)]
    traced = {"held_choices_per_step": 3520.0,
              "touched_experts_per_step": 630.0,
              "held_choices_per_position": 27.5}
    r = _r(cfg, ops, modules, positions=128 * 1800, seconds=50.0,
           mean_context=100.0, live_slots=128.0, moe_window=traced,
           moe_traced=traced, moe_model="m")
    mfu, step, ssm, moe = (run.load_reader(n)(r) for n in NEW)
    per_pos = arith_hybrid.flops_per_position(pub, m, 100.0, 27.5)
    assert mfu == pytest.approx(100 * 128 * 1800 * per_pos / 50.0 / 197e12)
    assert 4 < mfu < 6
    nbytes = arith_hybrid.step_bytes(pub, m, 128, 100.0, 630.0, 2, 2)
    assert step == pytest.approx(100 * nbytes / 819e9 / 0.025)
    assert 65 < step < 75
    # 5 layers x 128 slots x 4 MB read and written: 6.55 ms at the bandwidth
    assert ssm == pytest.approx(100 * 2 * 5 * 128 * 2 ** 22 / 819e9 / 0.0030)
    # 630 touched experts of 5.5M bfloat16 parameters: bytes, not FLOPs
    assert moe == pytest.approx(100 * 630 * 5_505_024 * 2 / 819e9 / 0.0030)
    assert 630 * 5_505_024 * 2 / 819e9 > 3520 * 2 * 5_505_024 / 197e12
    for value in (mfu, step, ssm, moe):
        assert 0 < value < 400
    # nothing to read: no launch in the traced part, or no counts
    assert run.load_reader(NEW[2])(_r(cfg, ops, [], live_slots=128.0)) is None
    bare = _r(cfg, ops, modules, positions=10, seconds=1.0, mean_context=1.0,
              live_slots=2.0)
    assert [run.load_reader(n)(bare) for n in (NEW[0], NEW[1], NEW[3])] == \
        [None, None, None]
    # a program without the operations (the parent): nothing, and no raise
    other = _r(cfg, [("fusion f32[7]", 1.001, 0.001)], modules,
               live_slots=128.0, moe_traced=traced)
    assert [run.load_reader(n)(other) for n in NEW[2:]] == [None, None]


# -- the driver at a small size, on the CPU -----------------------------------

def toy_run(trace_=0, seed=2 ** 31 + 11):
    args = argparse.Namespace(workload="toy-hybrid-lm.closed-4", seed=seed,
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


def window(seed):
    """The driver's set-up, a window and its sample: (ctx, body, counters,
    sizes, requests, chosen)."""
    manifest = run.load_json(TOY, "BENCHMARK.json")
    _, body, cfg = run.load_cell(TOY, manifest, "toy-hybrid-lm.closed-4")
    ctx = run.Context(body, cfg, seed, 1.0, 0, jax.devices())
    st = driver.setup(ctx)
    win = driver.measure(ctx, st)
    assert win["failed"] == 0 and win["end_to_end"]["decode_tokens_per_s"] > 0
    sizes, requests = st.sizes, st.requests
    chosen = driver.sample(ctx, st)
    driver.free(st)
    assert len(chosen) == body["check_requests"]
    return ctx, body, win["counters"], sizes, requests, chosen


def test_the_comparison_passes_what_was_served_and_the_counters_are_the_models():
    ctx, body, c, sizes, requests, chosen = window(2 ** 31 + 13)
    moe = c["moe_window"]
    # 8 of 16 experts held, 4 chosen of 16 a position and expert layer,
    # three expert layers: some 6 held choices a position, never more than 12
    assert 2.0 < moe["held_choices_per_position"] < 12.0
    assert 0 < moe["touched_experts_per_step"] <= 24
    assert c["moe_model"] == driver.NAME
    assert c["slot_state_bytes"] == 3 * 4 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    # every request's state started from nought, the ramp's too
    assert c["state_starts"] >= len(requests) - 4
    assert c["expert_load_max_over_mean"] >= 1.0
    served, _ = driver.reference_gaps(ctx, sizes, requests, chosen)
    assert served["logit_gap_max"] <= body["limits"]["logit_gap_max"]
    for mode in ("fp8", "bf16_state"):    # the controls' paths run here
        control, _ = driver.reference_gaps(ctx, sizes, requests, chosen,
                                           control=mode)
        assert 0.0 <= control["logit_gap_mean"] <= control["logit_gap_max"]


@pytest.mark.parametrize("fault", ["one expert left out",
                                   "the state not reset"])
def test_a_planted_fault_comes_out_not_correct(fault, monkeypatch):
    """The program is given a fault that the reference does not have: held
    expert 0's output matrix zeroed in every expert layer, or a request's
    slot state taken over from the request before it. The toy's routers
    weigh by 40 (`routed_scaling_factor`), or its experts, two small products
    and a square, would add nothing a served token shows; and its program
    computes in float32 (`engine.dtype`), so that what was served reads 0 and
    not bfloat16's near-ties. Readings over three seeds (widest gap, mean
    gap): as served 0 and 0; the state not reset from 5.6e-3 and 1.4e-4 (a
    stale state fades over tens of positions); the expert left out from 0.89
    and 0.107; the toy's limits are 1e-3 and 5e-5."""
    from deeplearning4j_tpu.serving import HybridDecodeModel

    if fault == "one expert left out":
        to_program = driver.to_program

        def faulty(tree):
            out = to_program(tree)
            for lp in out["layers"]:
                if "moe" in lp:
                    lp["moe"]["down"] = lp["moe"]["down"].at[0].set(0)
            return out

        monkeypatch.setattr(driver, "to_program", faulty)
    else:
        monkeypatch.setattr(
            HybridDecodeModel, "_request_starts",
            staticmethod(lambda fed, pos: jnp.zeros_like(fed)))
    result, rows = toy_run(seed=2 ** 31 + 17)
    assert not result["correct"], rows
    assert result["failed"] == 0
    assert any(not ok for name, _, _, ok in rows
               if name.startswith("logit_gap"))
