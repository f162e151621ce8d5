"""Fused LSTM recurrence as an in-repo Pallas TPU kernel.

Why a custom kernel (SURVEY.md §7: "Pallas-style custom kernels enter as
XLA custom-calls if/when generic HLO can't hit MFU targets"): the
XLA-lowered lax.scan recurrence measured ~80-155 us PER SEQUENTIAL STEP
on v5e (tools/RESNET_MFU.md section 4: July 2026, not re-measured)
while the step's actual work — one [N,H]x[H,4H] MXU matmul plus
elementwise gates — rooflines at single-digit microseconds. The scan
pays per-iteration HBM round-trips for the carried h/c; this kernel
keeps h, c and R resident in VMEM across ALL timesteps (the cuDNN-LSTM
design; reference analog: libnd4j's cudnn platform helper for lstmLayer,
SURVEY.md §2.1 platform-helper tier) and runs the whole recurrence in ONE
kernel launch. An A/B on the char-RNN
bench config (b1024, T=100, H=256) in July 2026, on another libtpu
build, read 13.3 ms/step against the scan lowering's 24.4; on this
installation: not measured (PERF.md).

Scope: the recurrence only. The input projection xw = x @ W + b (with
forgetBias folded into the f-gate columns) stays OUTSIDE — it is one
large MXU matmul XLA already runs at high efficiency.

Gradients: jax.custom_vjp with a reverse-sweep Pallas kernel (BPTT):
the forward saves post-activation gates and cell states; the backward
walks time in reverse via index maps, carrying dh/dc in VMEM and
accumulating dR on-chip. dxw flows back into the outer graph, which
differentiates the hoisted projection automatically.

Layouts: xw [T, N, 4H] f32, R [H, 4H] f32, h0/c0 [N, H] f32 ->
(hs [T, N, H], hT, cT). Gate packing i,f,g,o (DL4J order).
Constraints: f32, H % 128 == 0, N % 8 == 0 (MXU/VPU tiling); callers
route to the lax.scan path otherwise (`lstm_seq_available`, and
`kernels.recurrence_route`, which counts the decision).
`interpret=True` runs the same kernels on CPU — the parity tests in
tests/test_kernels.py use it, and TPU-gated tests cover the compiled
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_VMEM_BUDGET = 90 * 1024 * 1024


def lstm_seq_available(n, h, dtype) -> bool:
    if not (jnp.dtype(dtype) == jnp.float32
            and h % 128 == 0 and n % 8 == 0):
        return False
    # the backward kernel's worst-case resident VMEM: R + dR scratch +
    # dR output block (H x 4H each) plus the per-step N-blocks (several
    # N x 4H / N x H buffers, double-buffered) — fall back to the scan
    # path rather than die in the Mosaic compiler on big-H configs
    weights = 3 * (h * 4 * h * 4)
    blocks = 6 * (n * 4 * h * 4) + 12 * (n * h * 4)
    return weights + blocks < _VMEM_BUDGET


def _dotT_rhs(a, b):
    """a @ b.T without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dotT_lhs(a, b):
    """a.T @ b without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_body(xw_ref, r_ref, h_scr, c_scr):
    hsz = h_scr.shape[1]
    z = xw_ref[0] + jnp.dot(h_scr[:], r_ref[:],
                            preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(z[:, :hsz])
    f = jax.nn.sigmoid(z[:, hsz:2 * hsz])
    g = jnp.tanh(z[:, 2 * hsz:3 * hsz])
    o = jax.nn.sigmoid(z[:, 3 * hsz:])
    c = f * c_scr[:] + i * g
    h = o * jnp.tanh(c)
    return i, f, g, o, c, h


def _fwd_kernel(xw_ref, r_ref, h0_ref, c0_ref,
                hs_ref, gates_ref, cs_ref,
                h_scr, c_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    i, f, g, o, c, h = _fwd_body(xw_ref, r_ref, h_scr, c_scr)
    gates_ref[0] = jnp.concatenate([i, f, g, o], axis=1)
    cs_ref[0] = c
    hs_ref[0] = h
    h_scr[:] = h
    c_scr[:] = c


def _fwd_infer_kernel(xw_ref, r_ref, h0_ref, c0_ref,
                      hs_ref, hT_ref, cT_ref,
                      h_scr, c_scr):
    """Inference variant: no gate/cell residuals hit HBM (dead outputs
    of a pallas custom call are NOT DCE'd by XLA, so the primal must
    simply not emit them)."""
    t = pl.program_id(0)
    t_total = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    _i, _f, _g, _o, c, h = _fwd_body(xw_ref, r_ref, h_scr, c_scr)
    hs_ref[0] = h
    h_scr[:] = h
    c_scr[:] = c

    @pl.when(t == t_total - 1)
    def _():
        hT_ref[:] = h
        cT_ref[:] = c


def _fwd_call(xw, r, h0, c0, interpret, save_residuals=True):
    t, n, four_h = xw.shape
    hsz = four_h // 4
    in_specs = [
        pl.BlockSpec((1, n, four_h), lambda i: (i, 0, 0)),
        pl.BlockSpec((hsz, four_h), lambda i: (0, 0)),
        pl.BlockSpec((n, hsz), lambda i: (0, 0)),
        pl.BlockSpec((n, hsz), lambda i: (0, 0)),
    ]
    params = None if interpret else pltpu.CompilerParams(
        vmem_limit_bytes=100 * 1024 * 1024)
    if save_residuals:
        return pl.pallas_call(
            _fwd_kernel,
            grid=(t,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, n, hsz), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, n, four_h), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, n, hsz), lambda i: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
                jax.ShapeDtypeStruct((t, n, four_h), jnp.float32),
                jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((n, hsz), jnp.float32),
                pltpu.VMEM((n, hsz), jnp.float32),
            ],
            compiler_params=params,
            interpret=interpret,
        )(xw, r, h0, c0)
    return pl.pallas_call(
        _fwd_infer_kernel,
        grid=(t,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n, hsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
            jax.ShapeDtypeStruct((n, hsz), jnp.float32),
            jax.ShapeDtypeStruct((n, hsz), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, hsz), jnp.float32),
            pltpu.VMEM((n, hsz), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(xw, r, h0, c0)


# ---------------------------------------------------------------------------
# backward (reverse time sweep; grid index ti walks t = T-1-ti)
# ---------------------------------------------------------------------------

def _bwd_kernel(dhs_ref, gates_ref, cs_ref, cprev_ref, hprev_ref, r_ref,
                h0_ref, c0_ref, dhT_ref, dcT_ref,
                dxw_ref, dr_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, dr_scr):
    ti = pl.program_id(0)
    t_total = pl.num_programs(0)
    hsz = dh_scr.shape[1]
    is_first_step = ti == t_total - 1   # t == 0 in forward time

    @pl.when(ti == 0)
    def _():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]
        dr_scr[:] = jnp.zeros_like(dr_scr)

    gates = gates_ref[0]
    i = gates[:, :hsz]
    f = gates[:, hsz:2 * hsz]
    g = gates[:, 2 * hsz:3 * hsz]
    o = gates[:, 3 * hsz:]
    c = cs_ref[0]
    # c_{t-1}/h_{t-1}: shifted views of cs/hs (clamped at t=0; replaced
    # by the true initial state there)
    first = jnp.where(is_first_step, jnp.float32(1.0), jnp.float32(0.0))
    c_prev = first * c0_ref[:] + (1.0 - first) * cprev_ref[0]
    h_prev = first * h0_ref[:] + (1.0 - first) * hprev_ref[0]

    tc = jnp.tanh(c)
    dh = dhs_ref[0] + dh_scr[:]
    do = dh * tc
    dc = dc_scr[:] + dh * o * (1.0 - tc * tc)
    di = dc * g
    df = dc * c_prev
    dg = dc * i
    dz = jnp.concatenate([
        di * i * (1.0 - i),
        df * f * (1.0 - f),
        dg * (1.0 - g * g),
        do * o * (1.0 - o),
    ], axis=1)
    dxw_ref[0] = dz
    dh_scr[:] = _dotT_rhs(dz, r_ref[:])          # dz @ R^T
    dc_scr[:] = dc * f
    dr_scr[:] = dr_scr[:] + _dotT_lhs(h_prev, dz)  # h_{t-1}^T @ dz

    @pl.when(is_first_step)
    def _():
        dr_ref[:] = dr_scr[:]
        dh0_ref[:] = dh_scr[:]
        dc0_ref[:] = dc_scr[:]


def _bwd_call(t, n, hsz, interpret, dhs, gates, cs, hs, r, h0, c0,
              dhT, dcT):
    four_h = 4 * hsz
    rev = lambda i: (t - 1 - i, 0, 0)            # noqa: E731
    rev_prev = lambda i: (jnp.maximum(t - 2 - i, 0), 0, 0)  # noqa: E731
    return pl.pallas_call(
        _bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, n, hsz), rev),        # dhs
            pl.BlockSpec((1, n, four_h), rev),     # gates
            pl.BlockSpec((1, n, hsz), rev),        # cs
            pl.BlockSpec((1, n, hsz), rev_prev),   # cs shifted (c_{t-1})
            pl.BlockSpec((1, n, hsz), rev_prev),   # hs shifted (h_{t-1})
            pl.BlockSpec((hsz, four_h), lambda i: (0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),   # h0
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),   # c0
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),   # dhT
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),   # dcT
        ],
        out_specs=[
            pl.BlockSpec((1, n, four_h), rev),     # dxw
            pl.BlockSpec((hsz, four_h), lambda i: (0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, n, four_h), jnp.float32),
            jax.ShapeDtypeStruct((hsz, four_h), jnp.float32),
            jax.ShapeDtypeStruct((n, hsz), jnp.float32),
            jax.ShapeDtypeStruct((n, hsz), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, hsz), jnp.float32),
            pltpu.VMEM((n, hsz), jnp.float32),
            pltpu.VMEM((hsz, four_h), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(dhs, gates, cs, cs, hs, r, h0, c0, dhT, dcT)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def lstm_seq(xw, r, h0, c0, interpret=False):
    """Full LSTM recurrence: xw [T,N,4H] (input projections, biases and
    forgetBias pre-folded), R [H,4H], h0/c0 [N,H] -> (hs [T,N,H], hT,
    cT)."""
    # inference primal: no gate/cell residuals are written to HBM
    hs, hT, cT = _fwd_call(xw, r, h0, c0, interpret,
                           save_residuals=False)
    return hs, hT, cT


def _lstm_seq_fwd(xw, r, h0, c0, interpret):
    hs, gates, cs = _fwd_call(xw, r, h0, c0, interpret)
    return (hs, hs[-1], cs[-1]), (gates, cs, hs, r, h0, c0)


def _lstm_seq_bwd(interpret, res, cts):
    gates, cs, hs, r, h0, c0 = res
    dhs, dhT, dcT = cts
    t, n, hsz = dhs.shape
    dxw, dr, dh0, dc0 = _bwd_call(
        t, n, hsz, interpret, dhs, gates, cs, hs, r, h0, c0,
        dhT, dcT)
    return dxw, dr, dh0, dc0


lstm_seq.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)
