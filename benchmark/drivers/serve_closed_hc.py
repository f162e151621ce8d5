"""Driver `serve_closed_hc`: `drivers/serve_closed_lm.py`'s closed loop against
the decode engine serving a latent-attention causal LM whose residual path
holds several streams a position (hyper-connections):
`InferenceSession.register_decoder(name, LatentDecodeModel(...))`, the same
model class under the same label, read from a description with `hc_mult` > 1.

`serve_closed_lm`'s window with the router's counts (`measure`, and through
it its counts a step, `_per_step`), its sample and its freeing are used as
they are. What is written here names this model's reference (`reference/xing4_plain.py`):
set-up (the reference draws the run's weights from the seed in its own
layout, the matrices rounded once to bfloat16 and the residual maps' leaves
float32, and the driver renames them into the program's layout and loads
them), the sizes the reference takes, and the comparison: the served tokens
of a sample of the finished requests under the reference's full forward
pass, a request at a time, once the engine is freed."""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.drivers import serve_closed_lm as lm_driver
from benchmark.lib import compare, traffic
from benchmark.lib import program_spans as ps
from benchmark.reference import xing4_plain as plain

# the engine's label, and so the `model` label of its series, is the
# sibling driver's: `serve.expert_load_max_over_mean` reads either cell
NAME = lm_driver.NAME
HC_WEIGHTS = ("hc_phi_std", "hc_alpha", "hc_bias_std", "hc_res_diagonal")

program_config = lm_driver.program_config
measure = lm_driver.measure
sample, free = base.sample, base.free


def reference_sizes(config):
    """The reference's plain dict of sizes, from the configuration's file:
    the sublayers' as `serve_closed_lm.reference_sizes` reads them, and the
    residual path's from the published keys beside them."""
    pub = config["published"]
    sizes = lm_driver.reference_sizes(config)
    sizes.update(
        streams=pub["hc_mult"], sinkhorn_iters=pub["hc_sinkhorn_iters"],
        hc_eps=pub["hc_eps"], res_clamp=[pub["mhc_h_res_clamp_min"],
                                         pub["mhc_h_res_clamp_max"]])
    sizes["weights"] = dict(sizes["weights"],
                            **{k: config["weights"][k] for k in HC_WEIGHTS})
    return sizes


def to_program(tree):
    """The reference's parameters in the program's layout
    (`models/causal_lm.py:init_params`): other names, the same arrays."""
    out = lm_driver.to_program(tree)
    for lp, src in zip(out["layers"], tree["layers"]):
        lp["attn_streams"], lp["mlp_streams"] = src["attn_hc"], src["mlp_hc"]
    return out


def setup(ctx):
    import jax

    from deeplearning4j_tpu.serving import (InferenceSession,
                                            LatentDecodeModel)

    st = base.State()
    tr, eng = ctx.cell["traffic"], ctx.config["engine"]
    # before anything is drawn: a program whose block description knows no
    # residual streams would serve one stream under this model's name
    description = program_config(ctx.config)
    if getattr(description, "streams", 1) != ctx.config["published"][
            "hc_mult"]:
        raise RuntimeError(
            "this program's CausalLMConfig does not carry the "
            f"{ctx.config['published']['hc_mult']} residual streams the "
            "configuration asks for: it cannot run this cell")
    st.sizes = reference_sizes(ctx.config)
    weights = plain.draw_params(ctx.seed, st.sizes)
    ctx.say(f"weights drawn: {plain.count_params(weights)} parameters")
    model = LatentDecodeModel(
        to_program(weights), description,
        max_slots=eng["max_slots"], page=eng["page"],
        max_pages_per_slot=eng["max_pages_per_slot"],
        dtype=eng.get("dtype", "bfloat16"))
    del weights
    st.session = InferenceSession()
    st.session.register_decoder(NAME, model, **eng.get("options", {}))
    st.engine = st.session.decoder(NAME)
    # as `serve_closed.setup`: let the warm-up's throwaway steps end before
    # the first real one, or `memory_peak_bytes` counts a pool too many
    jax.block_until_ready(jax.device_put(np.int32(0), ctx.devices[0]) + 1)
    ctx.say("decoder registered and warmed")

    # the callers and the ramp as `serve_closed_lm.setup` has them, which
    # builds its model inline (PERF.md, Open questions: one function for the
    # four drivers is a `benchmark` issue's)
    st.stream, st.requests = traffic.requests(tr, ctx.seed), []
    st.records, st.lock, st.stop = [], threading.Lock(), threading.Event()
    st.samples, st.max_slots = [], eng["max_slots"]
    st.threads = [threading.Thread(target=base._caller, args=(st,),
                                   daemon=True, name=f"bench:caller-{i}")
                  for i in range(tr["callers"])]
    st.threads.append(threading.Thread(target=base._sampler, args=(st,),
                                       daemon=True, name="bench:sampler"))
    for t in st.threads:
        t.start()
    t_ramp = time.perf_counter()
    deadline = t_ramp + tr["ramp_timeout_s"]
    full = False
    while True:
        full = full or st.engine.active_slots >= st.max_slots
        ended = sum(1 for r in list(st.records) if r.get("t_end"))
        if full and ended >= tr["callers"]:
            break
        if time.perf_counter() > deadline:
            raise RuntimeError(f"ramp not over after {tr['ramp_timeout_s']}s:"
                               f" slots full {full}, {ended} requests ended")
        time.sleep(0.05)
    ctx.say(f"ramp over in {time.perf_counter() - t_ramp:.1f}s: "
            f"{ended} requests ended")
    return st


def residual_health():
    """The sample lines of the program's two `dl4j_hc_*` gauges as `GET
    /metrics` gives them (scrape-only series, which the registry's snapshot
    leaves out): of the engine's last delivered token step, how far its
    worst mixing map was from doubly stochastic and the streams' largest
    gain from entry to exit. Nothing where the program has no such gauge."""
    from deeplearning4j_tpu.telemetry import prometheus

    text = prometheus.render(collect_system=False, name_prefix="dl4j_hc_")
    return [line for line in text.splitlines() if not line.startswith("#")]


def reference_gaps(ctx, sizes, requests, chosen, control=None):
    """`serve_closed_lm.reference_gaps` on this model's reference, which
    draws the seed's weights for itself (or, for a control, the gaps of the
    tokens that the reference computed in the mode `control` puts first at
    the same positions). The control `"slot"` plants the fault that the
    widest gap's limit is there for and the mean cannot see: in the middle
    of each sampled request one served token gives way to the one that
    another request was served there, as a step that read another slot's
    rows once would have it; the notes carry those positions' gaps."""
    weights = plain.draw_params(ctx.seed, sizes)
    n = ctx.cell["check_requests"]
    t = max(len(requests[r["i"]][0]) + len(r["tokens"]) - 1 for r in chosen)
    seqs = np.zeros((n, t), np.int32)
    served = np.zeros((n, t), np.int32)
    first, counts, lengths = (np.zeros(n, int) for _ in range(3))
    for j, r in enumerate(chosen):
        prompt, toks = requests[r["i"]][0], r["tokens"]
        whole = prompt + toks[:-1]
        seqs[j, :len(whole)] = whole
        first[j], counts[j], lengths[j] = len(prompt) - 1, len(toks), \
            len(whole)
        served[j, first[j]:first[j] + counts[j]] = toks
    ref = plain.decoder_logits(weights, sizes, seqs, lengths)
    if control == "slot":
        rows = np.arange(len(chosen))
        at = (first + counts // 2)[rows]
        served[rows, at] = np.roll(served[rows, at], 1)
        there = np.asarray(ref[rows, at])
        planted = there.max(axis=-1) - there[rows, served[rows, at]]
        numbers, notes = compare.serving(ref, served, first, counts)
        return numbers, dict(notes, planted_gaps=sorted(
            round(float(g), 3) for g in planted))
    if control:
        served = np.argmax(plain.decoder_logits(
            weights, sizes, seqs, lengths, mode=control), axis=-1)
    return compare.serving(ref, served, first, counts)


def check(ctx, st):
    sizes, requests = st.sizes, st.requests
    chosen = sample(ctx, st)
    free(st)
    numbers = {"moe_dropped": ps.sample_sum(ps.snapshot(),
                                            "dl4j_moe_dropped_total")}
    ctx.say("residual path on /metrics: " + "; ".join(residual_health()))
    if not chosen:
        ctx.say("no request finished inside the window: nothing to compare")
        return numbers
    gaps, notes = reference_gaps(ctx, sizes, requests, chosen)
    ctx.say(f"compared {len(chosen)} requests: {notes}")
    return dict(numbers, **gaps)
