"""Driver `serve_closed_hybrid`: `drivers/serve_closed.py`'s closed loop of
callers (its callers, sampler, window and positions) and
`drivers/serve_closed_lm.py`'s window with the router's counts, against the
decode engine serving a hybrid state-space causal LM:
`InferenceSession.register_decoder(name, HybridDecodeModel(...))`.

What is written here is what differs. Set-up: the plain reference
(`reference/nemotron_h_plain.py`) draws the run's weights from the seed in its
own layout, rounded once to bfloat16, and the driver renames them into the
program's layout and loads them. Counters: the sizes this model's readers
take, the bytes of state held by slot, and what the engine's `dl4j_moe_*`
series counted over the window and over the traced part. The comparison: the
served tokens of a sample of the finished requests under the reference's full
forward pass (a scan over positions from a zero state), a request at a time,
once the engine is freed: prefill and decoding through pages and slot state
have to agree with it, for requests that took over a slot another had left."""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.drivers import serve_closed_lm as lm_driver
from benchmark.lib import arith_hybrid, compare, traffic
from benchmark.lib import program_spans as ps
from benchmark.reference import nemotron_h_plain as plain

NAME = "bench-hybrid-lm"
# reference leaf -> program leaf, a layer of each kind
MAMBA_NAMES = {"norm": "attn_norm", "in_proj": "w_in",
               "conv1d_weight": "conv_w", "conv1d_bias": "conv_b",
               "dt_bias": "dt_bias", "A_log": "A_log", "D": "D",
               "mixer_norm": "gate_norm", "out_proj": "w_out"}
ATTENTION_NAMES = {"norm": "attn_norm", "q_proj": "wq", "k_proj": "wk",
                   "v_proj": "wv", "o_proj": "wo"}
MLP_NAMES = {"up_proj": "up", "down_proj": "down"}


def reference_sizes(config):
    """The reference's plain dict of sizes, from the configuration's file:
    widths from `published`, the cut from `model`, the weights' scales from
    `weights`."""
    pub, m = config["published"], config["model"]
    return {
        "hidden": pub["hidden_size"], "heads": pub["mamba_num_heads"],
        "head_dim": pub["mamba_head_dim"], "groups": pub["n_groups"],
        "state": pub["ssm_state_size"], "conv": pub["conv_kernel"],
        "attn_heads": pub["num_attention_heads"],
        "kv_heads": pub["num_key_value_heads"],
        "attn_head_dim": pub["head_dim"], "latent": pub["moe_latent_size"],
        "expert_ffn": pub["moe_intermediate_size"],
        "shared_ffn": (pub["n_shared_experts"]
                       * pub["moe_shared_expert_intermediate_size"]),
        "num_experts": pub["n_routed_experts"],
        "top_k": pub["num_experts_per_tok"],
        "routed_scale": pub["routed_scaling_factor"],
        "experts_held": list(m["experts_held"]), "eps": pub["norm_eps"],
        "time_step": [pub["time_step_min"], pub["time_step_max"],
                      pub["time_step_floor"]],
        "layers": list(m["layer_kinds"]), "vocab": m["vocab_size"],
        "weights": {k: config["weights"][k] for k in (
            "matrix_std", "embedding_std", "router_bias_std")}}


def program_config(config, **kw):
    """The program's block description from the same file."""
    from deeplearning4j_tpu.models.causal_lm import CausalLMConfig

    m = config["model"]
    return CausalLMConfig.from_hybrid_published(
        config["published"], layer_ids=m["layer_ids"],
        experts_held=m["experts_held"], vocab_held=m["vocab_size"], **kw)


def to_program(tree):
    """The reference's parameters in the program's layout
    (`models/causal_lm.py:init_params`): other names, the same arrays."""
    mlp = lambda p: {v: p[k] for k, v in MLP_NAMES.items()}  # noqa: E731

    def layer(lp):
        for names in (MAMBA_NAMES, ATTENTION_NAMES):
            if set(lp) == set(names):
                return {names[k]: v for k, v in lp.items()}
        return {"mlp_norm": lp["norm"],
                "moe": dict(mlp(lp["experts"]), router=lp["gate"],
                            bias=lp["e_score_correction_bias"],
                            latent_in=lp["fc1_latent_proj"],
                            latent_out=lp["fc2_latent_proj"]),
                "shared": mlp(lp["shared_experts"])}

    return {"embed": tree["embed_tokens"], "head": tree["lm_head"],
            "final_norm": tree["norm_f"],
            "layers": [layer(lp) for lp in tree["layers"]]}


def setup(ctx):
    import jax

    from deeplearning4j_tpu.serving import (HybridDecodeModel,
                                            InferenceSession)

    st = base.State()
    tr, eng = ctx.cell["traffic"], ctx.config["engine"]
    st.sizes = reference_sizes(ctx.config)
    weights = plain.draw_params(ctx.seed, st.sizes)
    ctx.say(f"weights drawn: {plain.count_params(weights)} parameters")
    model = HybridDecodeModel(
        to_program(weights), program_config(ctx.config),
        max_slots=eng["max_slots"], page=eng["page"],
        max_pages_per_slot=eng["max_pages_per_slot"],
        dtype=eng.get("dtype", "bfloat16"))
    del weights
    st.slot_state_bytes = model.slot_state_bytes()
    st.session = InferenceSession()
    st.session.register_decoder(NAME, model, **eng.get("options", {}))
    st.engine = st.session.decoder(NAME)
    # as `serve_closed.setup`: let the warm-up's throwaway steps end before
    # the first real one, or `memory_peak_bytes` counts a state too many
    jax.block_until_ready(jax.device_put(np.int32(0), ctx.devices[0]) + 1)
    ctx.say("decoder registered and warmed")

    # the callers and the ramp as `serve_closed_lm.setup` has them, which
    # builds its model inline (PERF.md, Open questions: one function for the
    # three drivers is a `benchmark` issue's)
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


def measure(ctx, st):
    """`serve_closed_lm.measure`'s window (the router's counts as they stood
    when the window opened, when the tracer started and when it stopped),
    under this model's label, with what it holds by slot and how evenly the
    router loaded the held experts beside them."""
    win = lm_driver.measure(ctx, st)
    snap = ps.snapshot()
    label = {"model": NAME}
    load = ps.ratio(
        ps.sample_sum(snap, "dl4j_moe_load_max_over_mean_sum", **label),
        ps.sample_sum(snap, "dl4j_moe_steps_total", **label))
    sparse = arith_hybrid.count(ctx.config["model"], "sparse")
    c = win["counters"]
    c.update(
        moe_model=NAME, slot_state_bytes=st.slot_state_bytes,
        state_starts=ps.sample_sum(snap, "dl4j_decode_state_starts_total",
                                   **label),
        expert_load_max_over_mean=None if load is None else load / sparse)
    ctx.say(f"slot state {c['slot_state_bytes']} B, started from nought "
            f"{c['state_starts']} times; experts' load max over mean "
            f"{c['expert_load_max_over_mean']}; a token step "
            f"{c.get('moe_traced') or c['moe_window']}")
    return win


sample, free = base.sample, base.free


def reference_gaps(ctx, sizes, requests, chosen, control=None):
    """`serve_closed_lm.reference_gaps` on this model's reference, which
    draws the seed's weights for itself (or, for a control, the gaps of the
    tokens that the reference computed in the mode `control` puts first at
    the same positions)."""
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
    if not chosen:
        ctx.say("no request finished inside the window: nothing to compare")
        return numbers
    gaps, notes = reference_gaps(ctx, sizes, requests, chosen)
    ctx.say(f"compared {len(chosen)} requests: {notes}")
    return dict(numbers, **gaps)
