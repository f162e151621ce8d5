"""The `serve_closed_hc` driver, its arithmetic and its readers without a chip:
the manifest's new entries and the cell's files as data, the least-work counts
against a brute-force count at a small size and the parameter totals at the
cell's and at the published model's, each new reader over events and counters
written by hand, and the driver end to end on a toy manifest of its own
(`data/toy-hc-serve`), with four planted faults of the residual path that have
to come out `correct: false`."""

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
TOY = os.path.join(ROOT, "tests", "benchmark", "data", "toy-hc-serve")
TOY_CELL = "toy-hc-lm.closed-4"
CELL, SIBLING = "xing4-29b.closed-128", "deepseek-v3.closed-128"
HYBRID = "nemotron3-super.closed-128"
CONFIG = "xing4-29b-a4b-ep8-share"
NEW = ("serve.hc_step_mfu", "serve.hc_step_roofline", "serve.hc_mix_roofline")
LOAD = "serve.expert_load_max_over_mean"

from benchmark import run  # noqa: E402
from benchmark.drivers import serve_closed_hc as driver  # noqa: E402
from benchmark.lib import arith_hc, arith_mla, traffic  # noqa: E402
from benchmark.reference import xing4_plain as plain  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAK = json.load(f)["TPU v5 lite"]


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def cell(manifest):
    return run.load_cell(ROOT, manifest, CELL)


def names(manifest, section, c):
    return [m["name"] for m in run.cell_metrics(manifest, section, c)]


# -- the manifest and the cell as data ----------------------------------------

def order(entries, *wanted):
    """Where each of `wanted` stands among the entries' names: what was
    appended later stands further on, wherever the list ends."""
    listed = [e["name"] for e in entries]
    return [listed.index(w) for w in wanted]


def test_the_cell_reports_its_own_metrics_and_the_engines(manifest):
    assert names(manifest, "end_to_end", CELL) == \
        names(manifest, "end_to_end", SIBLING) == [
            "decode_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"]
    mine, theirs = set(names(manifest, "per_layer", CELL)), \
        set(names(manifest, "per_layer", SIBLING))
    assert set(NEW) <= mine - theirs
    # the sibling's own step metrics and its two silent rooflines list the
    # sibling and not this cell; the experts' load reads either cell (one
    # engine label)
    silent = {"serve.lm_step_mfu", "serve.lm_step_roofline",
              "serve.latent_attention_roofline",
              "serve.expert_matmul_roofline"}
    assert silent <= theirs - mine
    assert {LOAD, "serve.itl_p50_ms", "serve.slot_occupancy",
            "serve.kv_page_fill", "serve.live_page_share",
            "serve.overlapped_boundary_share", "serve.host_chain_ms_mean",
            "device_idle_share.serve"} <= mine
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["unit"] == "%"
            assert m["moves"] == "decode_tokens_per_s"
            assert m["layer"] == ("decode_step" if m["name"] == NEW[0]
                                  else "kernels")
        elif m["name"] == LOAD:
            assert m["workloads"].index(SIBLING) < m["workloads"].index(CELL)
        elif m["name"] in silent:
            assert CELL not in m["workloads"], m["name"]
    entry = run.find(manifest["workloads"], CELL, "workload")
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert len(entry["why"]) <= 200
    # appended: after what stood before it, wherever the lists end now
    assert order(manifest["workloads"], SIBLING, HYBRID, CELL) == sorted(
        order(manifest["workloads"], SIBLING, HYBRID, CELL))
    configs = ("deepseek-v3-ep16-share", "nemotron3-super-ep4-share", CONFIG)
    assert order(manifest["configs"], *configs) == sorted(
        order(manifest["configs"], *configs))


def test_the_siblings_entries_stand_as_their_prs_left_them(manifest):
    """What `test_benchmark_serve_lm.py`, `test_benchmark_serve_hybrid.py`
    and `test_benchmark_program_accounts.py` assert of their own entries, by
    membership and relative order and never by a place counted from a list's
    end, so that it goes on holding when a later PR appends. Three assertions
    there are of the other kind ("my cell is the manifest's last", "the
    experts' load lists my cell alone", "my two metrics are the last two"):
    this PR's appended cell and metrics make them untrue, a PR may not edit
    them, and they fail in the open until a `benchmark` PR mends them in
    place (PERF.md, Open questions)."""
    hybrid = ("serve.hybrid_step_mfu", "serve.hybrid_step_roofline",
              "serve.ssm_update_roofline", "serve.latent_expert_roofline")
    latent = ("serve.lm_step_mfu", "serve.lm_step_roofline",
              "serve.latent_attention_roofline",
              "serve.expert_matmul_roofline")
    for m in manifest["per_layer"]:
        if m["name"] in hybrid:
            assert HYBRID in m["workloads"] and m["unit"] == "%"
            assert m["moves"] == "decode_tokens_per_s"
            assert m["layer"] == ("decode_step" if m["name"] == hybrid[0]
                                  else "kernels")
        elif m["name"] in latent:
            assert SIBLING in m["workloads"]
            assert HYBRID not in m["workloads"]
            assert m["moves"] == "decode_tokens_per_s"
        elif m["name"] in ("serve.step_mfu", "serve.step_roofline"):
            assert "bert-base-decoder.closed-64" in m["workloads"]
            assert not {SIBLING, HYBRID, CELL} & set(m["workloads"])
        elif m["name"] == LOAD:
            assert HYBRID not in m["workloads"]
    bert = set(names(manifest, "per_layer", "bert-base-decoder.closed-64"))
    ds, nm = (set(names(manifest, "per_layer", c)) for c in (SIBLING, HYBRID))
    assert set(latent) | {LOAD} <= ds - bert
    assert {"serve.step_mfu", "serve.step_roofline"} <= bert - ds
    assert set(hybrid) <= nm - ds and set(latent) | {LOAD} <= ds - nm
    entry = run.find(manifest["workloads"], HYBRID, "workload")
    assert entry["chips"] == 1
    assert entry["config"] == "nemotron3-super-ep4-share"
    # PR 36's two: side by side, no list of cells, ahead of PR 39's three
    per = manifest["per_layer"]
    two = ("serve.host_chain_ms_mean", "serve.between_phases_ms_mean")
    at = order(per, *two, *NEW)
    assert at == sorted(at) and at[1] == at[0] + 1
    for i in at[:2]:
        assert per[i] == {"name": per[i]["name"], "unit": "ms",
                          "better": "lower", "source": "program_counter",
                          "layer": "engine", "moves": "itl_p95_ms"}
        assert callable(run.load_reader(per[i]["name"]))


def test_the_configuration_file_holds_the_published_keys(manifest, cell):
    entry = run.find(manifest["configs"], CONFIG, "config")
    _, body, cfg = cell
    changed = {"n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert set(entry["reduced"]) == changed      # no depth is cut
    for key, value in cfg["published"].items():
        assert (cfg[key] != value) == (key in changed), key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == entry["source"])
    assert row["name"] == "Xing4.0-29B-A4B"
    assert cfg["published"] == row["config"]
    pub, m = cfg["published"], cfg["model"]
    assert m["num_layers"] == len(m["layer_ids"]) == \
        pub["num_hidden_layers"] == cfg["num_hidden_layers"] == 40
    assert m["layer_ids"] == list(range(40))
    assert m["layer_kinds"] == ["dense"] * 2 + ["sparse"] * 38
    assert pub["first_k_dense_replace"] == 2
    assert cfg["n_routed_experts"] == m["experts_held"][1] == 64 // 8
    assert m["experts_held"][0] == 0
    assert cfg["vocab_size"] == m["vocab_size"] == 131072 // 8
    assert cfg["num_nextn_predict_layers"] == 0
    assert (m["n_routed_experts_published"], m["vocab_size_published"],
            m["num_hidden_layers_published"]) == (64, 131072, 40)
    assert (pub["hc_mult"], pub["hc_sinkhorn_iters"], pub["hc_eps"],
            pub["mhc_h_res_clamp_min"], pub["mhc_h_res_clamp_max"]) == (
                4, 20, 1e-6, -30, 30)
    assert {"streams", "maps norm", "sinkhorn", "maps precision",
            "maps layout", "page", "engine options", "rotary", "router"} \
        <= set(cfg["assumed"])
    for key in ("precision", "deployment"):
        assert cfg[key]
    assert "8" in cfg["deployment"] and "float32" in cfg["precision"]
    assert set(driver.HC_WEIGHTS) <= set(cfg["weights"])
    assert "alpha 0.4" in cfg["weights"]["why"]
    eng = cfg["engine"]
    assert eng["max_slots"] == body["traffic"]["callers"] == 128
    assert eng["page"] % 128 == 0       # whole tiles for the kernel
    assert eng["page"] * eng["max_pages_per_slot"] == 512
    assert eng["options"] == {"pending_size": 128}
    assert body["driver"] == "serve_closed_hc"
    assert (body["trace_seconds"], body["check_requests"]) == (2, 12)
    assert set(body["limits"]) == set(body["limits_why"]) == {
        "logit_gap_max", "logit_gap_mean", "window_compiles", "moe_dropped"}
    assert body["limits"]["moe_dropped"] == body["limits"][
        "window_compiles"] == 0
    # the program reads the residual path from the same file
    pc = driver.program_config(cfg)
    assert (pc.streams, pc.sinkhorn_iters, pc.sinkhorn_eps, pc.res_clamp) \
        == (4, 20, 1e-6, (-30, 30))
    assert len(pc.layers) == 40 and pc.n_group == 1 and pc.router_bias


def test_the_traffic_fits_the_slots_and_the_vocabulary(cell):
    _, body, cfg = cell
    tr = body["traffic"]
    assert tr["prompt"] == {"median": 48, "sigma": 0.8, "min": 16, "max": 128}
    assert tr["answer"] == {"median": 192, "sigma": 0.5, "min": 64,
                            "max": 384}
    assert (tr["callers"], tr["cycle"], tr["ids"], tr["ramp_timeout_s"]) == (
        128, 32, [3, 16383], 240)
    reqs = list(itertools.islice(traffic.requests(tr, 2 ** 31 + 5), 64))
    longest = cfg["engine"]["page"] * cfg["engine"]["max_pages_per_slot"]
    assert max(len(p) + n for p, n in reqs) <= longest == 512
    assert max(n for _, n in reqs) <= 1024      # the engine's max_new_limit
    assert min(len(p) for p, _ in reqs) >= 16 and \
        min(n for _, n in reqs) >= 64
    ids = [t for p, _ in reqs for t in p]
    assert 3 <= min(ids) and max(ids) < cfg["model"]["vocab_size"]
    # some 270 positions a request: a slot turns over every few seconds
    mean = sum(len(p) + n for p, n in reqs) / len(reqs)
    assert 230 < mean < 310


# -- arithmetic ---------------------------------------------------------------

def test_least_work_counts_at_the_cells_sizes(cell):
    _, _, cfg = cell
    pub, m = cfg["published"], cfg["model"]
    # attention with its two inner and the layer's two outer norms
    assert arith_mla.attention_params(pub) == 28_411_136 + 2 * 3584
    assert arith_mla.expert_params(pub) == 11_010_048
    assert 2 * arith_hc.sublayer_map_params(pub) == 688_182
    assert arith_hc.layer_params(pub, m, "dense") == 128_196_918
    assert arith_hc.layer_params(pub, m, "sparse") == 128_426_358
    assert arith_hc.total_params(pub, m) == m["parameters"] == 5_254_039_536
    # the whole model by the same equations: the published 29B-A4B
    whole = dict(m, experts_held=[0, 64], vocab_size=131072)
    total = arith_hc.total_params(pub, whole)
    active = total - 38 * (64 - 4) * arith_mla.expert_params(pub)
    assert total == m["parameters_published"] == 29_505_505_264
    assert active == m["parameters_active_published"] == 4_402_595_824
    # what the reference draws is what is counted, leaf by leaf
    shapes = jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(cfg)))
    assert plain.count_params(shapes) == 5_254_039_536
    assert arith_mla.latent_row_bytes(pub, 2) == 1152
    # the pool: 128 slots x 512 positions x 40 layers, and the scratch page
    eng = cfg["engine"]
    assert 40 * (128 * eng["max_pages_per_slot"] + 1) * eng["page"] * 1152 \
        == 3_025_797_120
    # a sublayer's residual path for a position
    assert arith_hc.mix_flops_per_position(pub) == \
        2 * 14336 * 24 + 2 * 14336 + 2 * 16 * 3584 + 2 * 14336
    # a launch's: 80 sublayers, phi and the streams there and back
    assert arith_hc.mix_bytes(pub, m, 128, 2) == 80 * (
        4 * 344_091 + 2 * 128 * 14336 * 2) == 697_311_680
    # a step of 128 slots at a context of 135: weight-bound, some 14 ms
    nbytes = arith_hc.step_bytes(pub, m, 128, 135, 38 * 8, 2, 2)
    flops = 128 * arith_hc.flops_per_position(pub, m, 135, 38 * 0.5)
    assert 11.0e9 < nbytes < 11.6e9 and 0.5e12 < flops < 0.65e12
    assert nbytes / PEAK["hbm_bytes_per_s"] > flops / PEAK["bf16_flops_per_s"]
    assert 13e-3 < nbytes / PEAK["hbm_bytes_per_s"] < 14.5e-3


def test_flops_a_position_against_a_brute_force_count():
    """Every product of the absorbed step at the toy's sizes, listed by hand
    as (rows, inner, columns), and the residual path's of every sublayer."""
    cfg = run.load_json(TOY, "configs", "toy-hc-lm.json")
    pub, m = cfg["published"], cfg["model"]
    d, h, n, ctx, held = 64, 4, 4, 11, 3.0
    attention = [(1, d, 24), (1, 24, h * 24), (1, d, 16 + 8)] \
        + [(1, 16, 16)] * h \
        + [(ctx, 24, 1)] * h + [(1, ctx, 16)] * h \
        + [(1, 16, 16)] * h + [(1, h * 16, d)]
    dense = [(1, d, 96), (1, d, 96), (1, 96, d)]
    expert = [(1, d, 32), (1, d, 32), (1, 32, d)]
    maps = [(1, n * d, 2 * n + n * n),      # xbar phi
            (1, n, d),                      # sum_i H_pre[i] X_i
            (n, n, d), (n, 1, d)]           # H_res X and H_post y
    products = 3 * attention + dense + 2 * ([(1, d, 16)] + expert) \
        + [(1, d, 96)] + 6 * maps
    brute = sum(2 * a * b * c for a, b, c in products) \
        + held * sum(2 * a * b * c for a, b, c in expert)
    assert arith_hc.flops_per_position(pub, m, ctx, held) == brute
    params = plain.count_params(jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(cfg))))
    assert arith_hc.total_params(pub, m) == params
    maps_params = 6 * (n * d * 24 + 3 + 24)
    # bytes: every parameter once (the maps' at 4 B), less the unread
    # embedding rows and the untouched experts, and the pool's live rows
    assert arith_hc.step_bytes(pub, m, 4, ctx, 10, 2, 2) == \
        2 * (params - maps_params - (96 - 4) * d - 6 * 3 * d * 32) \
        + 4 * maps_params + 3 * 4 * (ctx + 1) * 24 * 2
    assert arith_hc.mix_bytes(pub, m, 4, 4) == \
        4 * maps_params + 6 * 2 * 4 * n * d * 4


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
    mix_ops = run.load_reader(NEW[2]).__globals__["ops"](cfg)
    assert mix_ops and all(isinstance(o, str) for o in mix_ops)
    modules = [("jit__fn(7)", 1.0, 0.018), ("jit__fn(7)", 2.0, 0.018),
               ("jit__pick_token(3)", 1.5, 1e-6)]
    ops = [(mix_ops[0], 1.001, 0.0004), (mix_ops[-1], 1.004, 0.0006),
           ("latent_page_attention bf16[128,32,512]", 1.010, 0.0020),
           ("fusion f32[7]", 1.013, 0.0010),
           (mix_ops[0], 2.001, 0.0012), (mix_ops[-1], 2.012, 0.0008),
           # outside any launch: not the step's
           (mix_ops[0], 3.5, 0.5)]
    traced = {"held_choices_per_step": 2432.0,
              "touched_experts_per_step": 300.0,
              "held_choices_per_position": 19.0}
    r = _r(cfg, ops, modules, positions=128 * 2800, seconds=50.0,
           mean_context=135.0, live_slots=128.0, moe_window=traced,
           moe_traced=traced, moe_model="m")
    mfu, step, mix = (run.load_reader(name)(r) for name in NEW)
    per_pos = arith_hc.flops_per_position(pub, m, 135.0, 19.0)
    assert mfu == pytest.approx(100 * 128 * 2800 * per_pos / 50.0 / 197e12)
    assert 12 < mfu < 20
    nbytes = arith_hc.step_bytes(pub, m, 128, 135.0, 300.0, 2, 2)
    assert step == pytest.approx(100 * nbytes / 819e9 / 0.018)
    assert 70 < step < 80
    # 697 MB of maps and streams: 0.85 ms at the bandwidth, over 1.5 ms
    assert mix == pytest.approx(100 * 697_311_680 / 819e9 / 0.0015)
    for value in (mfu, step, mix):
        assert 0 < value < 100
    # nothing to read: no launch in the traced part, or no counts
    assert run.load_reader(NEW[2])(_r(cfg, ops, [], live_slots=128.0)) is None
    bare = _r(cfg, ops, modules, positions=10, seconds=1.0, mean_context=1.0,
              live_slots=2.0)
    assert [run.load_reader(name)(bare) for name in NEW[:2]] == [None, None]
    # a program without the operations (the parent): nothing, and no raise
    other = _r(cfg, [("fusion f32[7]", 1.001, 0.001)], modules,
               live_slots=128.0, moe_traced=traced)
    assert run.load_reader(NEW[2])(other) is None


# -- the driver at a small size, on the CPU -----------------------------------

def toy_run(trace_=0, seed=2 ** 31 + 11):
    args = argparse.Namespace(workload=TOY_CELL, seed=seed, seconds=1.5,
                              trace=trace_)
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


def test_the_comparison_passes_what_was_served_and_metrics_shows_the_gauges():
    manifest = run.load_json(TOY, "BENCHMARK.json")
    _, body, cfg = run.load_cell(TOY, manifest, TOY_CELL)
    ctx = run.Context(body, cfg, 2 ** 31 + 13, 1.0, 0, jax.devices())
    st = driver.setup(ctx)
    win = driver.measure(ctx, st)
    assert win["failed"] == 0 and win["end_to_end"]["decode_tokens_per_s"] > 0
    c = win["counters"]
    assert 1.0 < c["moe_window"]["held_choices_per_position"] < 8.0
    assert c["moe_model"] == driver.NAME == "bench-latent-lm"
    sizes, requests = st.sizes, st.requests
    chosen = driver.sample(ctx, st)
    driver.free(st)
    assert len(chosen) == body["check_requests"] == 8
    # the two gauges, under the engine's label, as `GET /metrics` has them
    lines = dict(line.rsplit(" ", 1) for line in driver.residual_health())
    label = '{model="%s"}' % driver.NAME
    defect = float(lines["dl4j_hc_sinkhorn_residual_max" + label])
    gain = float(lines["dl4j_hc_stream_gain_max" + label])
    assert 0.0 <= defect < 0.05 and 0.5 < gain < 10.0
    served, _ = driver.reference_gaps(ctx, sizes, requests, chosen)
    assert served["logit_gap_max"] <= body["limits"]["logit_gap_max"]
    for mode in ("fp8", "bf16"):        # the control's and the witness' paths
        low, _ = driver.reference_gaps(ctx, sizes, requests, chosen,
                                       control=mode)
        assert 0.0 <= low["logit_gap_mean"] <= low["logit_gap_max"]
    # one token of each request from another request's: the widest gap's
    # fault, far under what the mean is held to at the cell's size
    swapped, notes = driver.reference_gaps(ctx, sizes, requests, chosen,
                                           control="slot")
    assert len(notes["planted_gaps"]) == len(chosen)
    assert swapped["logit_gap_max"] == pytest.approx(
        max(notes["planted_gaps"]), abs=1e-3)
    assert swapped["logit_gap_max"] > body["limits"]["logit_gap_max"]
    other = run.Context(body, cfg, 5, 1.0, 0, jax.devices())
    wrong, _ = driver.reference_gaps(other, sizes, requests, chosen)
    assert wrong["logit_gap_max"] > body["limits"]["logit_gap_max"]


FAULTS = ["the maps frozen at their biases", "one Sinkhorn iteration",
          "the clamp left out", "the streams not summed at the exit"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault, monkeypatch):
    """The program is given a fault of the residual path that the reference
    does not have; the toy's program computes in float32 (`engine.dtype`), so
    that what was served reads 0 against limits of 1e-3 and 5e-5 and each
    fault reads orders above them. The maps frozen: every `alpha` zeroed in
    the program's weights, so that no map depends on the position. One
    iteration: the program's description says `sinkhorn_iters` 1. The clamp
    left out: one logit of `B_res` drawn at 100 on both sides, past
    float32's `exp` (88.7), which the reference clamps to e^30 and the
    program, told no clamp, turns into inf and NaN. Not summed: the exit
    takes the first stream alone."""
    from deeplearning4j_tpu.models import causal_lm

    to_program, program_config = driver.to_program, driver.program_config
    if fault == FAULTS[0]:
        def frozen(tree):
            out = to_program(tree)
            for lp in out["layers"]:
                for group in ("attn_streams", "mlp_streams"):
                    lp[group] = dict(lp[group],
                                     alpha=jnp.zeros_like(lp[group]["alpha"]))
            return out

        monkeypatch.setattr(driver, "to_program", frozen)
    elif fault == FAULTS[1]:
        monkeypatch.setattr(
            driver, "program_config",
            lambda config, **kw: program_config(config, sinkhorn_iters=1,
                                                **kw))
    elif fault == FAULTS[2]:
        draw = plain.draw_params

        def past_the_clamp(seed, sizes):
            params = draw(seed, sizes)
            hp = params["layers"][1]["mlp_hc"]
            hp["bias"] = hp["bias"].at[2 * 4 + 1].set(100.0)
            return params

        monkeypatch.setattr(plain, "draw_params", past_the_clamp)
        monkeypatch.setattr(
            driver, "program_config",
            lambda config, **kw: program_config(
                config, res_clamp=(-float("inf"), float("inf")), **kw))
    else:
        monkeypatch.setattr(causal_lm, "streams_exit",
                            lambda x, cfg: x[..., 0, :])
    result, rows = toy_run(seed=2 ** 31 + 17)
    assert not result["correct"], rows
    assert result["failed"] == 0
    assert any(not ok for name, _, _, ok in rows
               if name.startswith("logit_gap"))
