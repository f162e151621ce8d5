"""Fused GRU recurrence as an in-repo Pallas TPU kernel.

Same design as kernels/lstm.py (VMEM-resident carry + recurrent weights,
one launch for the whole sequence, custom-VJP reverse-sweep backward) —
the libnd4j gruCell packing: gates r,u then candidate c; input and
recurrent biases SEPARATE (b = [rb_input | rb_recurrent] is split by the
caller; this kernel takes the recurrent half explicitly because it
contributes inside the recurrence).

Math per step (matching autodiff/ops.py _gru_cell):
    rz   = h @ R + rb                      [N, 3H]
    r, u = sigmoid(xw_ru + rz_ru)          (first 2H columns)
    cand = tanh(xw_c + r * rz_c)           (last H columns)
    h'   = u * h + (1 - u) * cand

Residuals saved for backward: ru [T,N,2H], cand [T,N,H], rz_c [T,N,H].
Backward returns (dxw, dR, drb, dh0).

Constraints mirror the LSTM kernel: f32, H % 128 == 0, N % 8 == 0,
VMEM-bounded; callers route to the lax.scan lowering otherwise
(`kernels.recurrence_route` counts the decision).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.lstm import _VMEM_BUDGET, _dotT_lhs, _dotT_rhs


def gru_seq_available(n, h, dtype) -> bool:
    if not (jnp.dtype(dtype) == jnp.float32
            and h % 128 == 0 and n % 8 == 0):
        return False
    weights = 3 * (h * 3 * h * 4)
    blocks = 6 * (n * 3 * h * 4) + 12 * (n * h * 4)
    return weights + blocks < _VMEM_BUDGET


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _step(xw_t, rz, hsz, h_prev):
    ru = jax.nn.sigmoid(xw_t[:, :2 * hsz] + rz[:, :2 * hsz])
    rz_c = rz[:, 2 * hsz:]
    cand = jnp.tanh(xw_t[:, 2 * hsz:] + ru[:, :hsz] * rz_c)
    u = ru[:, hsz:]
    h = u * h_prev + (1.0 - u) * cand
    return ru, rz_c, cand, h


def _fwd_kernel(xw_ref, r_ref, rb_ref, h0_ref,
                hs_ref, ru_ref, rzc_ref, cand_ref,
                h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]

    hsz = h_scr.shape[1]
    rz = jnp.dot(h_scr[:], r_ref[:],
                 preferred_element_type=jnp.float32) + rb_ref[0]
    ru, rz_c, cand, h = _step(xw_ref[0], rz, hsz, h_scr[:])
    ru_ref[0] = ru
    rzc_ref[0] = rz_c
    cand_ref[0] = cand
    hs_ref[0] = h
    h_scr[:] = h


def _fwd_infer_kernel(xw_ref, r_ref, rb_ref, h0_ref,
                      hs_ref, hT_ref, h_scr):
    t = pl.program_id(0)
    t_total = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]

    hsz = h_scr.shape[1]
    rz = jnp.dot(h_scr[:], r_ref[:],
                 preferred_element_type=jnp.float32) + rb_ref[0]
    _ru, _rzc, _cand, h = _step(xw_ref[0], rz, hsz, h_scr[:])
    hs_ref[0] = h
    h_scr[:] = h

    @pl.when(t == t_total - 1)
    def _():
        hT_ref[:] = h


def _fwd_call(xw, r, rb, h0, interpret, save_residuals=True):
    t, n, three_h = xw.shape
    hsz = three_h // 3
    rb2 = rb.reshape(1, three_h)
    in_specs = [
        pl.BlockSpec((1, n, three_h), lambda i: (i, 0, 0)),
        pl.BlockSpec((hsz, three_h), lambda i: (0, 0)),
        pl.BlockSpec((1, three_h), lambda i: (0, 0)),
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
                pl.BlockSpec((1, n, 2 * hsz), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, n, hsz), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, n, hsz), lambda i: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
                jax.ShapeDtypeStruct((t, n, 2 * hsz), jnp.float32),
                jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
                jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((n, hsz), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
        )(xw, r, rb2, h0)
    return pl.pallas_call(
        _fwd_infer_kernel,
        grid=(t,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n, hsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, n, hsz), jnp.float32),
            jax.ShapeDtypeStruct((n, hsz), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, hsz), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(xw, r, rb2, h0)


# ---------------------------------------------------------------------------
# backward (reverse sweep)
# ---------------------------------------------------------------------------

def _bwd_kernel(dhs_ref, ru_ref, rzc_ref, cand_ref, hprev_ref, r_ref,
                h0_ref, dhT_ref,
                dxw_ref, dr_ref, drb_ref, dh0_ref,
                dh_scr, dr_scr, drb_scr):
    ti = pl.program_id(0)
    t_total = pl.num_programs(0)
    hsz = dh_scr.shape[1]
    is_first_step = ti == t_total - 1   # forward t == 0

    @pl.when(ti == 0)
    def _():
        dh_scr[:] = dhT_ref[:]
        dr_scr[:] = jnp.zeros_like(dr_scr)
        drb_scr[:] = jnp.zeros_like(drb_scr)

    ru = ru_ref[0]
    rgate = ru[:, :hsz]
    u = ru[:, hsz:]
    rz_c = rzc_ref[0]
    cand = cand_ref[0]
    first = jnp.where(is_first_step, jnp.float32(1.0), jnp.float32(0.0))
    h_prev = first * h0_ref[:] + (1.0 - first) * hprev_ref[0]

    dh = dhs_ref[0] + dh_scr[:]
    dcand = dh * (1.0 - u)
    du = dh * (h_prev - cand)
    dh_carry = dh * u
    dc_pre = dcand * (1.0 - cand * cand)
    drgate = dc_pre * rz_c
    drz_c = dc_pre * rgate
    dru_r = drgate * rgate * (1.0 - rgate)
    dru_u = du * u * (1.0 - u)
    dz = jnp.concatenate([dru_r, dru_u, dc_pre], axis=1)    # input side
    drz = jnp.concatenate([dru_r, dru_u, drz_c], axis=1)    # recurrent
    dxw_ref[0] = dz
    dh_scr[:] = dh_carry + _dotT_rhs(drz, r_ref[:])
    dr_scr[:] = dr_scr[:] + _dotT_lhs(h_prev, drz)
    drb_scr[:] = drb_scr[:] + jnp.sum(drz, axis=0, keepdims=True)

    @pl.when(is_first_step)
    def _():
        dr_ref[:] = dr_scr[:]
        drb_ref[:] = drb_scr[:]
        dh0_ref[:] = dh_scr[:]


def _bwd_call(t, n, hsz, interpret, dhs, ru, rzc, cand, hs, r, h0, dhT):
    three_h = 3 * hsz
    rev = lambda i: (t - 1 - i, 0, 0)            # noqa: E731
    rev_prev = lambda i: (jnp.maximum(t - 2 - i, 0), 0, 0)  # noqa: E731
    return pl.pallas_call(
        _bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, n, hsz), rev),          # dhs
            pl.BlockSpec((1, n, 2 * hsz), rev),      # ru
            pl.BlockSpec((1, n, hsz), rev),          # rz_c
            pl.BlockSpec((1, n, hsz), rev),          # cand
            pl.BlockSpec((1, n, hsz), rev_prev),     # h_{t-1}
            pl.BlockSpec((hsz, three_h), lambda i: (0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),    # h0
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),    # dhT
        ],
        out_specs=[
            pl.BlockSpec((1, n, three_h), rev),      # dxw
            pl.BlockSpec((hsz, three_h), lambda i: (0, 0)),
            pl.BlockSpec((1, three_h), lambda i: (0, 0)),
            pl.BlockSpec((n, hsz), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, n, three_h), jnp.float32),
            jax.ShapeDtypeStruct((hsz, three_h), jnp.float32),
            jax.ShapeDtypeStruct((1, three_h), jnp.float32),
            jax.ShapeDtypeStruct((n, hsz), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, hsz), jnp.float32),
            pltpu.VMEM((hsz, three_h), jnp.float32),
            pltpu.VMEM((1, three_h), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(dhs, ru, rzc, cand, hs, r, h0, dhT)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def gru_seq(xw, r, rb, h0, interpret=False):
    """Full GRU recurrence: xw [T,N,3H] (input projection + input bias
    pre-added), R [H,3H], rb [3H] recurrent bias, h0 [N,H] ->
    (hs [T,N,H], hT)."""
    hs, hT = _fwd_call(xw, r, rb, h0, interpret, save_residuals=False)
    return hs, hT


def _gru_seq_fwd(xw, r, rb, h0, interpret):
    hs, ru, rzc, cand = _fwd_call(xw, r, rb, h0, interpret,
                                  save_residuals=True)
    return (hs, hs[-1]), (ru, rzc, cand, hs, r, h0)


def _gru_seq_bwd(interpret, res, cts):
    ru, rzc, cand, hs, r, h0 = res
    dhs, dhT = cts
    t, n, hsz = dhs.shape
    dxw, dr, drb, dh0 = _bwd_call(t, n, hsz, interpret, dhs, ru, rzc,
                                  cand, hs, r, h0, dhT)
    return dxw, dr, drb.reshape(-1), dh0


gru_seq.defvjp(_gru_seq_fwd, _gru_seq_bwd)
