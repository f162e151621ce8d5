"""The train step that `BertTrainer` and `CausalLMTrainer` are thin over.

A trainer brings its parameters, their PartitionSpecs and its own traced
math: a plain function `step(params, opt, *batch, t)` that calls
`loss_and_adam` on its loss. The engine owns what was welded to one loss
before: the shardings, Adam's state, the donated `jax.jit` of that function
(named `step`, so the executable is `jit_step` whoever trains), the step
counter handed over as a traced scalar, and the two host spans
`dl4j.train.gather` / `dl4j.train.dispatch` on the profiler's clock."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# at import, not inside `run`: the process's compile listeners go in when
# `telemetry` is first imported, and a trainer's weights are compiled before
# its engine exists (telemetry.registry.watch_process)
from deeplearning4j_tpu import telemetry

B1, B2, EPS = 0.9, 0.999, 1e-8


def adam(params, opt, grads, lr, t):
    """Adam(0.9, 0.999, 1e-8) with bias correction and no decay; `t` is the
    traced count of steps already taken."""
    m = jax.tree_util.tree_map(
        lambda m_, g: B1 * m_ + (1 - B1) * g, opt["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v_, g: B2 * v_ + (1 - B2) * g * g, opt["v"], grads)
    tt = t + 1
    mhat = jax.tree_util.tree_map(lambda m_: m_ / (1 - B1 ** tt), m)
    vhat = jax.tree_util.tree_map(lambda v_: v_ / (1 - B2 ** tt), v)
    params = jax.tree_util.tree_map(
        lambda p, mh, vh: p - lr * mh / (jnp.sqrt(vh) + EPS),
        params, mhat, vhat)
    return params, {"m": m, "v": v}


def loss_and_adam(loss_fn, params, opt, lr, t, has_aux=False):
    """fwd + bwd + Adam over `loss_fn(params)`: (loss, params, opt), with
    the loss's auxiliary output last where it has one."""
    out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(params)
    params, opt = adam(params, opt, grads, lr, t)
    if has_aux:
        return out[0], params, opt, out[1]
    return out, params, opt


def shardings(mesh: Mesh, specs):
    """NamedShardings for a tree of PartitionSpecs, dropping the axes this
    mesh does not have."""
    to_sharding = lambda s: NamedSharding(  # noqa: E731
        mesh, P(*[a if a in mesh.axis_names else None for a in (s or P())]))
    return jax.tree_util.tree_map(
        to_sharding, specs, is_leaf=lambda x: isinstance(x, P))


class StepEngine:
    """Parameters and Adam's moments on `mesh`, and one donated step.

    `step(params, opt, *batch, t)` returns (loss, params, opt) and then one
    output for each entry of `aux_sh`; `batch_sh` gives a sharding for each
    batch argument. `params` is the tree itself or a function that makes it,
    which is then compiled as one program that lays each leaf out where it
    belongs."""

    def __init__(self, mesh: Mesh, params, specs, step, batch_sh, aux_sh=()):
        self.mesh = mesh
        self.p_sh = shardings(mesh, specs)
        self.params = (jax.jit(params, out_shardings=self.p_sh)()
                       if callable(params)
                       else jax.device_put(params, self.p_sh))
        zeros = lambda: jax.tree_util.tree_map(  # noqa: E731
            jnp.zeros_like, self.params)
        self.opt = {"m": zeros(), "v": zeros()}
        self.o_sh = {"m": self.p_sh, "v": self.p_sh}
        self.batch_sh, self.aux_sh = tuple(batch_sh), tuple(aux_sh)
        self._step = step
        self.fn = None
        self.steps = 0

    def build(self):
        repl = NamedSharding(self.mesh, P())
        return jax.jit(
            self._step,
            in_shardings=(self.p_sh, self.o_sh, *self.batch_sh, repl),
            out_shardings=(repl, self.p_sh, self.o_sh, *self.aux_sh),
            donate_argnums=(0, 1),
        )

    def run(self, gather, place):
        """One step. `gather()` is the trainer's host work on the batch;
        `place(gathered, steps)` makes the step's batch arguments, inside
        the dispatch span with the call. Returns what the step returns
        after the state: the loss, then any auxiliary outputs."""
        if self.fn is None:
            self.fn = self.build()
        # the step's two host phases as spans on the profiler's clock
        # (pure annotations; nothing is made when telemetry is off)
        on = telemetry.enabled()
        span = telemetry.span if on else contextlib.nullcontext
        with span("dl4j.train.gather"):
            gathered = gather()
        with span("dl4j.train.dispatch"):
            # step counter as a traced scalar — a static arg would
            # recompile the executable every step
            loss, self.params, self.opt, *aux = self.fn(
                self.params, self.opt, *place(gathered, self.steps),
                jnp.asarray(self.steps, jnp.int32))
        self.steps += 1
        if on:
            # the process's start-up account freezes once its first step is
            # traced, compiled and queued (one flag read ever after)
            telemetry.startup_done()
        return (loss, *aux)
