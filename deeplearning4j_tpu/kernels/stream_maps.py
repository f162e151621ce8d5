"""The residual path's maps of one sublayer, from their logits, as one
in-repo Pallas TPU kernel.

What it replaces (`models/causal_lm.py:stream_maps`' plain form): two
sigmoids, an `exp` under a clamp and twenty Sinkhorn iterations on a 4 x 4
matrix a position. Written in `jax.numpy` the iterations are reductions and
divisions that the TPU compiler keeps apart, some forty small device
operations a sublayer and eighty sublayers a token step; unrolled over
sixteen vectors they do fuse, and the step's program grows by a thousand
instructions a sublayer (minutes of compile time and tens of GiB at forty
layers: PERF.md, PR 39). Here a sublayer's maps are one call: the logits
``[2n + n*n, R]`` (R positions, the lanes) come to VMEM once, a row of the
matrix is one ``[n, lanes]`` block (its column sums are sums of such blocks,
its row sums a sum over a block's sublanes), and what goes back is one
packed array.

Layout of the result, ``[rows_out(n), R]`` float32: rows ``0..n-1`` H_pre,
``n..2n-1`` H_post, ``2n + i*n + j`` H_res[i, j], row ``2n + n*n`` the
defect (the largest ``|row or column sum - 1|`` of H_res), zeros behind it
up to a whole sublane tile. `packed_maps` is the same function in plain
`jax.numpy`, with the same layout: the CPU's path, the trainer's (a kernel
has no gradient), and what the kernel is tested against."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def rows_out(n: int) -> int:
    """Rows of the packed result: the maps' entries and the defect, up to a
    whole tile of 8 sublanes."""
    return -(-(2 * n + n * n + 1) // 8) * 8


def available(positions: int, n: int) -> bool:
    """Whether the kernel takes this many positions: whole lanes."""
    return positions > 0 and positions % LANES == 0 and n >= 2


def packed_maps(z, *, n, iters, eps, clamp):
    """z [2n + n*n, R] float32, the maps' logits (columns of `phi` by rows
    here: a, b, then c row by row) -> the packed maps [rows_out(n), R]."""
    lo, hi = clamp
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    m = jnp.exp(jnp.clip(z[2 * n:], lo, hi)).reshape(n, n, -1)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    defect = jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0), axis=0),
        jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0), axis=0))
    out = jnp.concatenate([pre, post, m.reshape(n * n, -1), defect[None]])
    return jnp.pad(out, ((0, rows_out(n) - out.shape[0]), (0, 0)))


def _kernel(z_ref, o_ref, *, n, iters, eps, lo, hi):
    z = z_ref[...]
    o_ref[...] = jnp.zeros_like(o_ref)
    o_ref[0:n, :] = jax.nn.sigmoid(z[0:n])
    o_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    # row i of the matrix: [n columns, lanes]
    rows = [jnp.exp(jnp.clip(z[2 * n + i * n:2 * n + (i + 1) * n], lo, hi))
            for i in range(n)]
    by_column = lambda: functools.reduce(jnp.add, rows)  # noqa: E731
    by_row = lambda r: jnp.sum(r, axis=0, keepdims=True)  # noqa: E731
    for _ in range(iters):
        under = by_column() + eps
        rows = [r / under for r in rows]
        rows = [r / (by_row(r) + eps) for r in rows]
    worst = jnp.max(jnp.abs(by_column() - 1.0), axis=0, keepdims=True)
    for i, r in enumerate(rows):
        o_ref[2 * n + i * n:2 * n + (i + 1) * n, :] = r
        worst = jnp.maximum(worst, jnp.abs(by_row(r) - 1.0))
    o_ref[2 * n + n * n:2 * n + n * n + 1, :] = worst


def sinkhorn_maps(z, *, n, iters, eps, clamp, interpret=False):
    """`packed_maps` as one kernel call: z [2n + n*n, R] float32 with R a
    multiple of 128 -> [rows_out(n), R] float32; a grid step a block of 128
    positions."""
    cols, positions = z.shape
    kernel = functools.partial(_kernel, n=n, iters=int(iters),
                               eps=float(eps), lo=float(clamp[0]),
                               hi=float(clamp[1]))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows_out(n), positions), jnp.float32),
        grid=(positions // LANES,),
        in_specs=[pl.BlockSpec((cols, LANES), lambda b: (0, b))],
        out_specs=pl.BlockSpec((rows_out(n), LANES), lambda b: (0, b)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="stream_maps",
        interpret=interpret,
    )(z.astype(jnp.float32))
