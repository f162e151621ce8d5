"""GPipe-style pipeline parallelism over the `pipe` mesh axis.

Reference capability: ABSENT in the reference (SURVEY.md §2.6 marks
pipeline parallel "NO", with the prescribed TPU mapping "XLA
multi-computation + collective permute") — this is additive capability,
built the TPU-native way:

- the network is split into S equal-structure STAGES whose params are
  stacked on a leading axis sharded over `pipe` (device s holds stage s);
- a microbatched forward runs S + M - 1 ticks inside `shard_map`; each
  tick every device applies its stage to its current activation and
  `ppermute`s the result to the next device (the bubble is the standard
  GPipe (S-1)/(S+M-1) overhead);
- backward needs no hand scheduling: `jax.grad` through the functional
  forward reverses every `ppermute` automatically, yielding the GPipe
  backward pipeline.

Composes with data parallelism: build a dp x pp mesh and shard the batch
over `data` as usual; the pipeline loop runs per data-shard.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, PIPE_AXIS, MeshConfig, spec_for)


def _stage_spec(mesh):
    """Stage-stacked arrays [S, ...]: leading axis over pipe."""
    return spec_for(mesh, PIPE_AXIS)


def pipeline_apply(stage_fn, stage_params, x_mb, mesh):
    """Run the S-stage pipeline over M microbatches.

    stage_fn:      (params_one_stage, x, microbatch_index) -> y (same
                   structure per stage); microbatch_index is the
                   microbatch the stage is consuming at that tick (a
                   traced int32 scalar) — stage bodies needing
                   per-microbatch state (dropout RNG) key off it, others
                   ignore it.
    stage_params:  pytree with leading axis S (sharded over `pipe`)
    x_mb:          [M, mb, ...] microbatches (replicated over `pipe`,
                   shardable over `data`)
    returns        [M, mb, ...] outputs of the last stage.
    """
    n_stages = mesh.shape.get(PIPE_AXIS, 1)
    if n_stages == 1:
        def seq(params, x, mb_idx):
            s = jax.tree_util.tree_leaves(params)[0].shape[0]
            y = x
            for i in range(s):
                p_i = jax.tree_util.tree_map(lambda a: a[i], params)
                y = stage_fn(p_i, y, mb_idx)
            return y
        m1 = x_mb.shape[0]
        return jax.vmap(lambda mb, i: seq(stage_params, mb, i))(
            x_mb, jnp.arange(m1, dtype=jnp.int32))

    m = x_mb.shape[0]
    p_spec = _stage_spec(mesh)
    x_spec = spec_for(mesh, None, DATA_AXIS)   # [M, mb(data-sharded), ...]
    param_specs = jax.tree_util.tree_map(lambda _: p_spec, stage_params)

    @partial(shard_map, mesh=mesh,
             in_specs=(param_specs, x_spec), out_specs=x_spec,
             check_vma=False)
    def run(params_local, x_local):
        # params_local leaves: [1, ...] (this device's stage)
        p_here = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(PIPE_AXIS)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        state = jnp.zeros_like(x_local[0])
        outs = jnp.zeros_like(x_local)
        for t in range(m + n_stages - 1):
            # first stage consumes microbatch t; others consume the
            # activation handed to them last tick. Stage s at tick t is
            # working on microbatch t - s (clipped; out-of-range ticks
            # are pipeline-bubble work that never reaches the output).
            mb_idx = jnp.clip(t - stage, 0, m - 1).astype(jnp.int32)
            inp = jnp.where(stage == 0,
                            x_local[jnp.minimum(t, m - 1)], state)
            out = stage_fn(p_here, inp, mb_idx)
            # collect on the LAST stage once the pipe is full
            is_ready = jnp.logical_and(stage == n_stages - 1,
                                       t >= n_stages - 1)
            slot = jnp.clip(t - (n_stages - 1), 0, m - 1)
            outs = jnp.where(
                is_ready,
                jax.lax.dynamic_update_index_in_dim(
                    outs, out, slot, axis=0),
                outs)
            state = jax.lax.ppermute(out, PIPE_AXIS, perm)
        # every device holds an `outs` buffer but only the last stage's is
        # real; zero the rest and psum to broadcast (ppermute cannot
        # one-to-many)
        outs = jnp.where(stage == n_stages - 1, outs,
                         jnp.zeros_like(outs))
        return jax.lax.psum(outs, PIPE_AXIS)

    return run(stage_params, x_mb)


class PipelineMlp:
    """A pipelined MLP: S stages x [hidden -> hidden] blocks, demonstrating
    dp x pp training end-to-end (VERDICT.md round-1 item 8)."""

    def __init__(self, mesh: Mesh, hidden: int, n_stages: int | None = None,
                 microbatches: int = 4, lr: float = 1e-2, seed: int = 0):
        self.mesh = mesh
        self.hidden = hidden
        self.n_stages = n_stages or mesh.shape.get(PIPE_AXIS, 1)
        self.microbatches = microbatches
        self.lr = lr
        key = jax.random.key(seed)
        k1, k2 = jax.random.split(key)
        scale = 1.0 / np.sqrt(hidden)
        params = {
            "W": jax.random.normal(
                k1, (self.n_stages, hidden, hidden), jnp.float32) * scale,
            "b": jnp.zeros((self.n_stages, hidden), jnp.float32),
        }
        sh = NamedSharding(mesh, _stage_spec(mesh))
        self.params = jax.device_put(params, {"W": sh, "b": sh})
        self._step_fn = None

    @staticmethod
    def stage_fn(p, x, mb_idx):
        del mb_idx  # stateless stage
        return jnp.tanh(x @ p["W"] + p["b"])

    def forward(self, params, x_mb):
        return pipeline_apply(self.stage_fn, params, x_mb, self.mesh)

    def loss(self, params, x_mb, y_mb):
        out = self.forward(params, x_mb)
        return jnp.mean((out - y_mb) ** 2)

    def _build(self):
        mesh = self.mesh
        x_sh = NamedSharding(mesh, spec_for(mesh, None, DATA_AXIS))
        p_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, _stage_spec(mesh)), self.params)
        repl = NamedSharding(mesh, P())

        def step(params, x_mb, y_mb):
            loss, grads = jax.value_and_grad(self.loss)(params, x_mb, y_mb)
            params = jax.tree_util.tree_map(
                lambda p, g: p - self.lr * g, params, grads)
            return loss, params

        return jax.jit(step, in_shardings=(p_sh, x_sh, x_sh),
                       out_shardings=(repl, p_sh), donate_argnums=(0,))

    def train_step(self, x, y):
        """x/y: [batch, hidden]; batch is split into `microbatches`."""
        if self._step_fn is None:
            self._step_fn = self._build()
        m = self.microbatches
        x_mb = np.asarray(x).reshape(m, -1, self.hidden)
        y_mb = np.asarray(y).reshape(m, -1, self.hidden)
        loss, self.params = self._step_fn(self.params, x_mb, y_mb)
        return loss


def pipeline_dryrun(devices):
    """dp x pp leg of the driver's multichip dryrun: 2-stage pipeline with
    data parallelism, two training steps, loss must fall."""
    n = len(devices)
    pp = 2 if n % 2 == 0 else 1
    dp = n // pp
    mesh = MeshConfig(data=dp, pipe=pp, devices=devices).build()
    hidden, mb, per_mb = 16, 4, max(2 * dp, dp)
    model = PipelineMlp(mesh, hidden, microbatches=mb, lr=5e-2, seed=1)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(mb * per_mb, hidden)).astype(np.float32)
    y = np.tanh(rng.normal(size=(mb * per_mb, hidden))).astype(np.float32)
    l1 = float(model.train_step(x, y))
    l2 = float(model.train_step(x, y))
    print(f"pipeline_dryrun: mesh={dict(mesh.shape)} "
          f"loss {l1:.4f} -> {l2:.4f}")
    assert l2 < l1, "pipeline training did not reduce loss"
