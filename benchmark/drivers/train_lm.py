"""Driver `train_lm`: next-token pretraining through
`CausalLMTrainer.train_step`, the call a user's loop makes, on `chips`
devices as `MeshConfig(data=chips)`.

As `drivers/train.py` (which names BERT throughout): the plain reference
draws the run's weights from the seed in its own layout, set-up loads them
into one trainer and drives it through its first three steps by the window's
own call on a new batch each; the window goes on with that same trainer from
step four. The comparison (`check`) runs the reference from those weights
over the same three batches once the window has closed."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import arith, arith_lm, compare, traffic_lm
from benchmark.lib import program_spans as ps
from benchmark.reference import laguna_plain

CHECKED_STEPS = 3
KINDS = {"full_attention": "full", "sliding_attention": "sliding"}
# program leaf -> reference leaf
LAYER_NAMES = {"attn_norm": "input_layernorm",
               "mlp_norm": "post_attention_layernorm", "wq": "q_proj",
               "wk": "k_proj", "wv": "v_proj", "wg": "g_proj", "wo": "o_proj"}
MLP_NAMES = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}


def reference_sizes(config):
    """The reference's plain dict of sizes, from the configuration's file:
    widths from `published`, the cut from `model`, the weights' scales from
    `weights`."""
    pub, m = config["published"], config["model"]
    return {
        "vocab": m["vocab_size"], "dense_ffn": pub["intermediate_size"],
        "expert_ffn": pub["moe_intermediate_size"],
        "shared_ffn": pub["shared_expert_intermediate_size"],
        "weights": {k: config["weights"][k]
                    for k in ("matrix_std", "embedding_std")},
        "hidden": pub["hidden_size"], "head_dim": pub["head_dim"],
        "kv_heads": pub["num_key_value_heads"],
        "sliding_window": pub["sliding_window"],
        "num_experts": pub["num_experts"],
        "top_k": pub["num_experts_per_tok"],
        "routed_scale": pub["moe_routed_scaling_factor"],
        "experts_held": list(m["experts_held"]),
        "rms_eps": pub["rms_norm_eps"],
        "rope": {KINDS[k]: v for k, v in pub["rope_parameters"].items()
                 if k in KINDS},
        "layers": [{"attention": KINDS[a], "heads": h, "mlp": mlp}
                   for a, h, mlp in arith_lm.layer_specs(pub, m)]}


def rename(tree):
    """A tree shaped as the program's parameters, in the reference's layout
    (benchmark/reference/laguna_plain.py's docstring)."""
    mlp = lambda p: {MLP_NAMES[k]: v for k, v in p.items()}  # noqa: E731

    def layer(lp):
        out = {LAYER_NAMES[k]: v for k, v in lp.items() if k in LAYER_NAMES}
        if "mlp" in lp:
            out["mlp"] = mlp(lp["mlp"])
        else:
            moe = dict(lp["moe"])
            out["router"] = moe.pop("router")
            out["experts"] = mlp(moe)
            out["shared_expert"] = mlp(lp["shared"])
        return out

    return {"embed_tokens": tree["embed"], "lm_head": tree["head"],
            "norm": tree["final_norm"],
            "layers": [layer(lp) for lp in tree["layers"]]}


def to_program(tree):
    """The reference's parameters in the program's layout: `rename` backward."""
    names = {v: k for k, v in LAYER_NAMES.items()}
    mlp = lambda p: {k: p[v] for k, v in MLP_NAMES.items()}  # noqa: E731

    def layer(lp):
        out = {names[k]: v for k, v in lp.items() if k in names}
        if "mlp" in lp:
            out["mlp"] = mlp(lp["mlp"])
        else:
            out["moe"] = dict(mlp(lp["experts"]), router=lp["router"])
            out["shared"] = mlp(lp["shared_expert"])
        return out

    return {"embed": tree["embed_tokens"], "head": tree["lm_head"],
            "final_norm": tree["norm"],
            "layers": [layer(lp) for lp in tree["layers"]]}


class State:
    """The trainer with the window's own call and feed around it."""

    def __init__(self, trainer, batches):
        self.trainer, self.batches = trainer, batches
        self.loss, self.steps, self.fed = None, 0, []

    def one_step(self, keep=False):
        import jax

        with jax.profiler.TraceAnnotation("bench.make_batch"):
            tok, lab = next(self.batches)
        if keep:
            self.fed.append((tok, lab))
        with jax.profiler.TraceAnnotation("bench.train_step"):
            self.loss = self.trainer.train_step(tok, lab)
        self.steps += 1

    def readback(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.readback"):
            return float(self.loss)


def build_config(config):
    from deeplearning4j_tpu.models.causal_lm import CausalLMConfig

    m = config["model"]
    return CausalLMConfig.from_published(
        config["published"], num_layers=m["num_layers"],
        experts_held=tuple(m["experts_held"]), vocab_held=m["vocab_size"],
        compute_dtype=m["compute_dtype"])


def setup(ctx):
    from deeplearning4j_tpu.models.causal_lm import CausalLMTrainer
    from deeplearning4j_tpu.parallel.mesh import MeshConfig

    n, m = ctx.cell["chips"], ctx.config["model"]
    mesh = MeshConfig(data=n, devices=ctx.devices[:n]).build()
    p0 = laguna_plain.draw_params(reference_sizes(ctx.config), ctx.seed)
    ctx.say("the reference's weights drawn")
    trainer = CausalLMTrainer(build_config(ctx.config), mesh, lr=m["lr"],
                              params=to_program(p0),
                              warmup_steps=m["warmup_steps"])
    st = State(trainer, traffic_lm.lm_batches(
        ctx.cell["traffic"], m["vocab_size"], ctx.seed))
    ctx.say(f"trainer built on {n} device(s)")
    st.program = _first_steps(st, p0)
    ctx.say(f"first {CHECKED_STEPS} steps read back: losses "
            f"{st.program['loss']}")
    return st


def _first_steps(st, p0):
    """The program's readings from the weights `p0` (the reference's, in its
    layout): each of the first steps' losses and router counts, the first
    gradient as Adam got it (its first moment after one step is 0.1 of it),
    whole on the host and as norms, and the norm of the parameters' change
    after the three."""
    import jax

    out = {"loss": [], "choices": [], "p0": p0}
    for i in range(CHECKED_STEPS):
        st.one_step(keep=True)
        out["loss"].append(st.readback())
        out["choices"].append(np.asarray(st.trainer.router_counts[0]))
        if i == 0:
            out["g1"] = jax.tree_util.tree_map(
                lambda m: m / np.float32(1 - laguna_plain.B1),
                rename(jax.device_get(st.trainer.opt["m"])))
            out["grad"] = _host_norms(out["g1"])
    p3 = rename(jax.device_get(st.trainer.params))
    out["change"] = _host_norms(jax.tree_util.tree_map(np.subtract, p3, p0))
    out["fed"] = st.fed
    return out


def _host_norms(tree):
    """{leaf: norm} of numpy arrays, on the host: nothing of the comparison
    is left on the device to count as the program's memory."""
    return {k: float(np.linalg.norm(v.ravel()))
            for k, v in laguna_plain.leaf_names(tree).items()}


def _routed():
    """(held choices, steps) of the program's own counts so far, all sparse
    layers together: `dl4j_moe_held_choices_total` and
    `dl4j_moe_steps_total`, which the trainer publishes one step behind its
    dispatch."""
    snap = ps.snapshot()
    return np.array([ps.sample_sum(snap, name) or 0.0 for name in (
        "dl4j_moe_held_choices_total", "dl4j_moe_steps_total")])


def _per_step(a, b):
    """Held choices a step between two readings of `_routed`."""
    held, steps = b - a
    return held / steps if steps else None


def measure(ctx, st):
    """The window: step after step for `seconds`, the loss read back every
    `readback_every`-th step as a logging loop does, closed by waiting for
    the last step. A traced run traces the window's last `trace_seconds`.
    The step's FLOPs are counted from the choices the router sent to held
    experts over the counted steps, as the program counted them; a traced
    run also hands the readers the mean over the traced part's steps (from
    the step dispatched before the tracer started to the last)."""
    import jax

    every = ctx.cell["traffic"]["readback_every"]
    st.trainer.publish_router_counts()      # set-up's last step: read back
    steps0, routed0 = st.steps, _routed()
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    t_trace = t_end - ctx.cell["trace_seconds"] if ctx.trace else None
    syncs, t_traced, routed_traced, held, loads = [], None, None, [], []
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if t_trace is not None and t_traced is None and now >= t_trace:
            t_traced, routed_traced = now, _routed()
            ctx.start_trace()
        st.one_step()
        if (st.steps - steps0) % every == 0:
            st.readback()
            syncs.append((st.steps - steps0, time.perf_counter(), _routed()))
            counts = np.asarray(st.trainer.router_counts[0], np.float64)
            held.append([int(c.sum()) for c in counts])
            loads.append([round(float(c.max() / max(c.mean(), 1e-9)), 2)
                          for c in counts])
    jax.block_until_ready(st.loss)
    t1 = time.perf_counter()
    if t_traced is not None:
        ctx.stop_trace()
    st.trainer.publish_router_counts()      # the last step's, now it is done
    steps = st.steps - steps0
    tr, pub, m = ctx.cell["traffic"], ctx.config["published"], \
        ctx.config["model"]
    # a traced run's counters stop at the last read-back before the tracer
    # started, so that its stall is in none of them
    c_steps, c_t1, c_routed = steps, t1, _routed()
    if t_traced is not None:
        before = [s for s in syncs if s[1] <= t_traced]
        if before:
            c_steps, c_t1, c_routed = before[-1]
    held_a_step = _per_step(routed0, c_routed)
    intervals = [(b[1] - a[1]) / (b[0] - a[0])
                 for a, b in zip(syncs, syncs[1:])]
    ctx.say(f"window: {steps} steps in {t1 - t0:.3f}s; host clock between "
            f"read-backs, per step, median "
            f"{1e3 * float(np.median(intervals)) if intervals else 0:.2f} ms; "
            f"held choices a step {held_a_step}; at each read-back, by "
            f"sparse layer: held choices {held}; fullest held expert over "
            f"the mean {loads}")
    counters = {"flops_per_step": arith_lm.train_flops_per_step(
                    pub, m, tr["rows"], tr["seq"], held_a_step),
                "steps": c_steps, "seconds": c_t1 - t0,
                "chips": ctx.cell["chips"], "step_executable": "jit_step",
                "held_choices_per_step": held_a_step}
    if t_traced is not None:
        counters["held_choices_per_step_traced"] = _per_step(
            routed_traced, _routed())
    return {"t0": t0, "t1": t1, "attempted": steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s_per_chip": arith.rate(
                steps * tr["rows"] * tr["seq"], t0, t1) / ctx.cell["chips"]},
            "counters": counters}


def free(st):
    st.trainer = st.loss = None
    gc.collect()


def reference_readings(ctx, program, mode="f32", fault=None):
    """The plain reference's readings over the batches the program was fed,
    from the weights it drew for both; with a lower `mode` or a `fault` it
    is the control or a planted fault, put in the program's place."""
    m = ctx.config["model"]
    ref = laguna_plain.LmReference(
        reference_sizes(ctx.config), program["p0"], m["lr"], mode=mode,
        fault=fault, block_q=ctx.cell["reference_block_q"],
        warmup_steps=m["warmup_steps"])
    out = {"loss": [], "choices": []}
    for i, (tok, lab) in enumerate(program["fed"]):
        loss, norms, choices = ref.step(tok, lab)
        out["loss"].append(loss)
        out["choices"].append(choices)
        if i == 0:
            out["grad"], out["g1"] = norms, ref.g1
            ref.g1 = None
    out["change"] = ref.change_norms()
    return out


def held_choices_gap(got, ref):
    """Over the checked steps, the L1 distance between the two sides' choices
    per held expert and layer, over the reference's sum."""
    diff = sum(int(np.abs(a - b).sum()) for a, b in zip(got, ref))
    return diff / max(1, sum(int(b.sum()) for b in ref))


def compare_with(program, ref):
    numbers, notes = compare.training(program, ref)
    numbers["held_choices_gap"] = held_choices_gap(program["choices"],
                                                   ref["choices"])
    return numbers, notes


def check(ctx, st):
    program = st.program
    free(st)
    ref = reference_readings(ctx, program)
    numbers, notes = compare_with(program, ref)
    numbers["moe_dropped"] = ps.sample_sum(ps.snapshot(),
                                           "dl4j_moe_dropped_total")
    ctx.say(f"reference losses {ref['loss']} program {program['loss']} "
            f"{notes}")
    return numbers
