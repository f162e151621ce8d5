"""Driver `serve_closed_lm`: `drivers/serve_closed.py`'s closed loop of callers
(its callers, sampler, window and positions, imported from there) against the
decode engine serving a latent-attention causal LM:
`InferenceSession.register_decoder(name, LatentDecodeModel(...))`.

What is written here is what differs. Set-up: the plain reference draws the
run's weights from the seed in its own layout, rounded once to bfloat16, and
the driver renames them into the program's layout and loads them: both sides
hold the same values, and the program's tree shares the drawn buffers
(4.57B parameters fit the chip once; the comparison draws them again from the
seed once the engine is freed). Counters: the sizes the
readers of this model's metrics take, and what the engine's `dl4j_moe_*`
series counted over its life and over the traced part. The comparison: the
served tokens of a sample of the finished requests under the reference's full
forward pass, a request at a time, once the engine is freed."""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.drivers import serve_closed as base
from benchmark.lib import compare, traffic
from benchmark.lib import program_spans as ps
from benchmark.reference import deepseek_v3_plain as plain

NAME = "bench-latent-lm"
# program leaf -> reference leaf
LAYER_NAMES = {"attn_norm": "input_layernorm",
               "mlp_norm": "post_attention_layernorm", "wq_a": "q_a_proj",
               "q_norm": "q_a_layernorm", "wq_b": "q_b_proj",
               "wkv_a": "kv_a_proj_with_mqa", "kv_norm": "kv_a_layernorm",
               "wkv_b": "kv_b_proj", "wo": "o_proj"}
MLP_NAMES = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
MOE_COUNTS = ("dl4j_moe_choices_total", "dl4j_moe_held_choices_total",
              "dl4j_moe_touched_experts_total", "dl4j_moe_steps_total")


def reference_sizes(config):
    """The reference's plain dict of sizes, from the configuration's file:
    widths from `published`, the cut from `model`, the weights' scales from
    `weights`."""
    pub, m = config["published"], config["model"]
    return {
        "hidden": pub["hidden_size"], "heads": pub["num_attention_heads"],
        "q_rank": pub["q_lora_rank"], "kv_rank": pub["kv_lora_rank"],
        "nope": pub["qk_nope_head_dim"], "rope": pub["qk_rope_head_dim"],
        "v": pub["v_head_dim"], "dense_ffn": pub["intermediate_size"],
        "expert_ffn": pub["moe_intermediate_size"],
        "shared_ffn": pub["n_shared_experts"] * pub["moe_intermediate_size"],
        "num_experts": pub["n_routed_experts"], "n_group": pub["n_group"],
        "topk_group": pub["topk_group"], "top_k": pub["num_experts_per_tok"],
        "routed_scale": pub["routed_scaling_factor"],
        "experts_held": list(m["experts_held"]),
        "rms_eps": pub["rms_norm_eps"], "rope_theta": pub["rope_theta"],
        "yarn": {k: pub["rope_scaling"][k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        "layers": list(m["layer_kinds"]), "vocab": m["vocab_size"],
        "weights": {k: config["weights"][k] for k in (
            "matrix_std", "embedding_std", "router_bias_std")}}


def program_config(config, **kw):
    """The program's block description from the same file."""
    from deeplearning4j_tpu.models.causal_lm import CausalLMConfig

    m = config["model"]
    return CausalLMConfig.from_latent_published(
        config["published"], layer_ids=m["layer_ids"],
        experts_held=m["experts_held"], vocab_held=m["vocab_size"], **kw)


def to_program(tree):
    """The reference's parameters in the program's layout
    (`models/causal_lm.py:init_params`): other names, the same arrays."""
    names = {v: k for k, v in LAYER_NAMES.items()}
    mlp = lambda p: {k: p[v] for k, v in MLP_NAMES.items()}  # noqa: E731

    def layer(lp):
        out = {names[k]: v for k, v in lp.items() if k in names}
        if "mlp" in lp:
            out["mlp"] = mlp(lp["mlp"])
        else:
            out["moe"] = dict(mlp(lp["experts"]), router=lp["gate"],
                              bias=lp["e_score_correction_bias"])
            out["shared"] = mlp(lp["shared_experts"])
        return out

    return {"embed": tree["embed_tokens"], "head": tree["lm_head"],
            "final_norm": tree["norm"],
            "layers": [layer(lp) for lp in tree["layers"]]}


def _routed():
    """What the engine's `dl4j_moe_*` series hold now, all sparse layers
    together (the engine publishes a launch's counts when it delivers it,
    one boundary behind the dispatch)."""
    snap = ps.snapshot()
    return [ps.sample_sum(snap, name) or 0.0 for name in MOE_COUNTS]


def setup(ctx):
    import jax

    from deeplearning4j_tpu.serving import (InferenceSession,
                                            LatentDecodeModel)

    st = base.State()
    tr, eng = ctx.cell["traffic"], ctx.config["engine"]
    st.sizes = reference_sizes(ctx.config)
    weights = plain.draw_params(ctx.seed, st.sizes)
    ctx.say(f"weights drawn: {plain.count_params(weights)} parameters")
    model = LatentDecodeModel(
        to_program(weights), program_config(ctx.config),
        max_slots=eng["max_slots"], page=eng["page"],
        max_pages_per_slot=eng["max_pages_per_slot"])
    del weights
    st.session = InferenceSession()
    st.session.register_decoder(NAME, model, **eng.get("options", {}))
    st.engine = st.session.decoder(NAME)
    # as `serve_closed.setup`: let the warm-up's throwaway steps end before
    # the first real one, or `memory_peak_bytes` counts a pool too many
    jax.block_until_ready(jax.device_put(np.int32(0), ctx.devices[0]) + 1)
    ctx.say("decoder registered and warmed")

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
    """`serve_closed.measure`'s window, with this model's sizes beside its
    counters and the router's counts as they stood when the window opened,
    when the tracer started and when it stopped."""
    routed = {"open": _routed()}
    start, stop = ctx.start_trace, ctx.stop_trace

    def noted(key, call):
        def wrapped():
            routed[key] = _routed()
            call()
        return wrapped

    ctx.start_trace, ctx.stop_trace = noted("start", start), \
        noted("stop", stop)
    try:
        win = base.measure(ctx, st)
    finally:
        ctx.start_trace, ctx.stop_trace = start, stop
    routed["end"] = _routed()
    c = win["counters"]
    # the other counters stop where the tracer starts; so do these
    c.update(w_itemsize=2, kv_itemsize=2, sizes=st.sizes, moe_model=NAME,
             moe_window=_per_step(routed["open"],
                                  routed.get("start", routed["end"]),
                                  st.sizes))
    if "stop" in routed:
        c["moe_traced"] = _per_step(routed["start"], routed["stop"],
                                    st.sizes)
    return win


def _per_step(before, after, sizes):
    """Between two readings of the series, all sparse layers together: held
    choices a fed position, and held choices and touched experts a token
    step; None where no step was published between them.
    `dl4j_moe_choices_total` adds `rows fed x top_k` a step and sparse
    layer, so the rows fed are that over `top_k` and the sparse layers."""
    every, held, touched, steps = (b - a for a, b in zip(before, after))
    if not steps or not every:
        return None
    sparse = sum(1 for kind in sizes["layers"] if kind == "sparse")
    return {"held_choices_per_position":
            held * sizes["top_k"] * sparse / every,
            "held_choices_per_step": held / steps,
            "touched_experts_per_step": touched / steps}


sample, free = base.sample, base.free


def reference_gaps(ctx, sizes, requests, chosen, control=None):
    """The widest and the mean gap of the served tokens under the reference,
    which draws the seed's weights for itself (or, for the control, of the
    tokens that the reference computed in the lower precision `control` puts
    first at the same positions)."""
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
