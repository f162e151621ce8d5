"""Driver `train`: masked-LM pretraining through `BertTrainer.train_step`, the
call a user's loop makes, on `chips` devices as `MeshConfig(data=chips)`.

Set-up builds one trainer and drives it through its first three steps, each
through the window's own call on a new batch; the window goes on with that
same trainer from step four. The comparison (`check`) runs the plain
reference over the same three batches once the window has closed."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import arith, compare, traffic
from benchmark.reference import bert_plain

CHECKED_STEPS = 3


def _sizes(config):
    keys = ("vocab_size", "hidden", "num_layers", "num_heads", "ffn",
            "max_len", "dropout", "layer_norm_eps")
    return {k: config["model"][k] for k in keys}


class State:
    """The trainer with the window's own call and feed around it."""

    def __init__(self, trainer, batches, sizes):
        self.trainer, self.batches, self.sizes = trainer, batches, sizes
        self.loss, self.steps = None, 0

    def one_step(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.make_batch"):
            tok, lab = next(self.batches)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            self.loss = self.trainer.train_step(tok, lab)
        self.steps += 1

    def readback(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.readback"):
            return float(self.loss)


def setup(ctx):
    from deeplearning4j_tpu.models.bert import BertConfig, BertTrainer
    from deeplearning4j_tpu.parallel.mesh import MeshConfig

    sizes, n = _sizes(ctx.config), ctx.cell["chips"]
    cfg = BertConfig(compute_dtype=ctx.config["model"]["compute_dtype"],
                     **sizes)
    mesh = MeshConfig(data=n, devices=ctx.devices[:n]).build()
    trainer = BertTrainer(cfg, mesh, lr=ctx.config["model"]["lr"],
                          seed=ctx.seed31)
    st = State(trainer, traffic.mlm_batches(
        ctx.cell["traffic"], sizes["vocab_size"], ctx.seed), sizes)
    ctx.say(f"trainer built on {n} device(s)")
    st.program = _first_steps(st)
    return st


def _first_steps(st):
    """The program's readings: each of the first steps' losses, the first
    gradient as Adam got it (its first moment after one step is 0.1 of it),
    whole on the host and as norms, and the norm of the parameters' change
    after the three."""
    import jax

    p0 = jax.device_get(st.trainer.params)
    out = {"loss": []}
    for i in range(CHECKED_STEPS):
        st.one_step()
        out["loss"].append(st.readback())
        if i == 0:
            out["g1"] = jax.tree_util.tree_map(
                lambda m: m / np.float32(1 - bert_plain.B1),
                jax.device_get(st.trainer.opt["m"]))
            out["grad"] = _host_norms(out["g1"])
    p3 = jax.device_get(st.trainer.params)
    out["change"] = _host_norms(jax.tree_util.tree_map(np.subtract, p3, p0))
    return out


def _host_norms(tree):
    """{leaf: norm} of numpy arrays, on the host: nothing of the comparison
    is left on the device to count as the program's memory."""
    return {k: float(np.linalg.norm(v.ravel())) for k, v in
            bert_plain.leaf_names(bert_plain.unfuse(tree)).items()}


def measure(ctx, st):
    """The window: step after step for `seconds`, the loss read back every
    `readback_every`-th step as a logging loop does, closed by waiting for
    the last step. A traced run traces the window's last `trace_seconds`."""
    import jax

    every = ctx.cell["traffic"]["readback_every"]
    steps0 = st.steps
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    t_trace = t_end - ctx.cell["trace_seconds"] if ctx.trace else None
    syncs, t_traced = [], None
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if t_trace is not None and t_traced is None and now >= t_trace:
            t_traced = now
            ctx.start_trace()
        st.one_step()
        if (st.steps - steps0) % every == 0:
            st.readback()
            syncs.append((st.steps - steps0, time.perf_counter()))
    jax.block_until_ready(st.loss)
    t1 = time.perf_counter()
    if t_traced is not None:
        ctx.stop_trace()
    steps = st.steps - steps0
    tr = ctx.cell["traffic"]
    flops = arith.bert_train_flops_per_step(
        st.sizes["hidden"], st.sizes["ffn"], st.sizes["num_layers"],
        st.sizes["vocab_size"], tr["rows"], tr["seq"],
        arith.mlm_max_preds(tr["seq"]))
    # a traced run's counters stop at the last read-back before the tracer
    # started, so that its stall is in none of them
    if t_traced is not None:
        before = [s for s in syncs if s[1] <= t_traced]
        c_steps, c_t1 = before[-1] if before else (steps, t1)
    else:
        c_steps, c_t1 = steps, t1
    intervals = [(b[1] - a[1]) / (b[0] - a[0])
                 for a, b in zip(syncs, syncs[1:])]
    ctx.say(f"window: {steps} steps in {t1 - t0:.3f}s; host clock between "
            f"read-backs, per step, median "
            f"{1e3 * float(np.median(intervals)) if intervals else 0:.2f} ms")
    return {"t0": t0, "t1": t1, "attempted": steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s_per_chip": arith.rate(
                steps * tr["rows"] * tr["seq"], t0, t1) / ctx.cell["chips"]},
            "counters": {"flops_per_step": flops, "steps": c_steps,
                         "seconds": c_t1 - t0, "chips": ctx.cell["chips"],
                         "step_executable": "jit_step"}}


def free(st):
    st.trainer = st.loss = None
    gc.collect()


def reference_readings(ctx, sizes, mode="f32", keep_rows=None):
    """The plain reference's readings over the seed's first three batches;
    with a lower `mode` or `keep_rows` it is the control or a planted fault,
    put in the program's place."""
    import jax

    tr = ctx.cell["traffic"]
    ref = bert_plain.MlmReference(
        sizes, jax.random.key(ctx.seed31), ctx.config["model"]["lr"],
        sizes["dropout"], mode=mode, keep_rows=keep_rows,
        block_rows=ctx.cell["reference_block_rows"])
    batches = traffic.mlm_batches(tr, sizes["vocab_size"], ctx.seed)
    out = {"loss": []}
    for i in range(CHECKED_STEPS):
        loss, norms = ref.step(*next(batches))
        out["loss"].append(loss)
        if i == 0:
            out["grad"], out["g1"] = norms, jax.device_get(ref.g1)
            ref.g1 = None
    out["change"] = ref.change_norms()
    return out


def check(ctx, st):
    program, sizes = st.program, st.sizes
    free(st)
    ref = reference_readings(ctx, sizes)
    numbers, notes = compare.training(program, ref)
    ctx.say(f"reference losses {ref['loss']} program {program['loss']} "
            f"{notes}")
    return numbers
