"""Fused ResNet bottleneck block: Pallas vs XLA forward probe (round 4).

RESNET_MFU.md bounds XLA-lowered ResNet-50 at ~16% MFU and names a fused
custom backbone (conv+BN+relu chains in one kernel) as the untested
remaining lever; VERDICT r3 item 1 demands that hypothesis be proven or
broken. This probe measures ONE identity bottleneck block — the unit 12
of ResNet-50's 16 blocks reduce to — at stage shapes, comparing:

  xla:    conv1x1 -> affine -> relu -> conv3x3 -> affine -> relu
          -> conv1x1 -> affine -> +residual -> relu  (XLA-scheduled)
  pallas: the same math in ONE kernel, all intermediates VMEM-resident,
          per-image-group grid (halo = image border zero-pad, exact).

BN is folded to affine scale/shift in BOTH paths (isolates the fusion
question from batch-stats reduction strategy, which RESNET_MFU.md
bounds at ~1.4 MFU points).

Arithmetic intensity (s2 shape, b256): unfused, each conv round-trips
HBM for ~204 FLOP/byte < v5e ridge ~240 -> HBM-bound; fused reads X +
weights and writes OUT once: ~546 FLOP/byte -> compute-bound.

Run: python tools/probe_fused_block.py [--stage s2] [--g 8] [--k 20]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

V5E_PEAK_BF16 = 197e12

# (H, C, F): spatial, block channels, bottleneck width
STAGES = {
    "s0": (56, 256, 64),
    "s1": (28, 512, 128),
    "s2": (14, 1024, 256),
    "s3": (7, 2048, 512),
}


def block_flops(h, c, f):
    return 2 * h * h * (c * f + 9 * f * f + f * c)


# ---------------------------------------------------------------------------
# Pallas fused forward
# ---------------------------------------------------------------------------

def _fused_kernel(h, g, x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref,
                  b2_ref, w3_ref, s3_ref, b3_ref, o_ref, pad_ref):
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    x = x_ref[...]                                   # (g,h,h,C) bf16
    y1 = dot(x, w1_ref[...])                         # (g,h,h,F) f32
    y1 = y1 * s1_ref[...].reshape(1, 1, 1, -1) + \
        b1_ref[...].reshape(1, 1, 1, -1)
    y1 = jnp.maximum(y1, 0.0).astype(jnp.bfloat16)
    pad_ref[...] = jnp.zeros_like(pad_ref)
    pad_ref[:, 1:h + 1, 1:h + 1, :] = y1
    acc = jnp.zeros(y1.shape, jnp.float32)
    for ky in range(3):
        for kx in range(3):
            acc += dot(pad_ref[:, ky:ky + h, kx:kx + h, :],
                       w2_ref[ky * 3 + kx])
    y2 = acc * s2_ref[...].reshape(1, 1, 1, -1) + \
        b2_ref[...].reshape(1, 1, 1, -1)
    y2 = jnp.maximum(y2, 0.0).astype(jnp.bfloat16)
    y3 = dot(y2, w3_ref[...])
    y3 = y3 * s3_ref[...].reshape(1, 1, 1, -1) + \
        b3_ref[...].reshape(1, 1, 1, -1)
    o_ref[...] = jnp.maximum(
        y3 + x.astype(jnp.float32), 0.0).astype(jnp.bfloat16)


def fused_block(x, params, g):
    """x: (N,H,H,C) bf16; params: w1 (C,F) w2 (9,F,F) w3 (F,C) bf16 +
    affine (1,F)/(1,C) f32 pairs; g images per grid cell."""
    n, h, _, c = x.shape
    f = params["w1"].shape[1]
    wspec = lambda shp: pl.BlockSpec(shp, lambda i: (0,) * len(shp))
    return pl.pallas_call(
        functools.partial(_fused_kernel, h, g),
        grid=(n // g,),
        in_specs=[
            pl.BlockSpec((g, h, h, c), lambda i: (i, 0, 0, 0)),
            wspec((c, f)), wspec((1, f)), wspec((1, f)),
            wspec((9, f, f)), wspec((1, f)), wspec((1, f)),
            wspec((f, c)), wspec((1, c)), wspec((1, c)),
        ],
        out_specs=pl.BlockSpec((g, h, h, c), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, h, c), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((g, h + 2, h + 2, f), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(x, params["w1"], params["s1"], params["b1"], params["w2"],
      params["s2"], params["b2"], params["w3"], params["s3"], params["b3"])



# ---------------------------------------------------------------------------
# Pallas fused forward, 2D formulation: all matmuls get M = g*h*h rows
# (the 4D variant leaves Mosaic looping tiny M=h dots). The 3x3 conv is
# 9 row-shifted masked 2D matmuls over one contiguous padded scratch:
# flat row index r = (img*h + y)*h + x, shift (dy,dx) = r + dy*h + dx;
# contributions whose (y+dy, x+dx) fall outside the image are zeroed by
# a mask computed from iota (exact: equals zero-padded SAME conv).
# ---------------------------------------------------------------------------

def _fused_kernel2d(h, g, x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref,
                    b2_ref, w3_ref, s3_ref, b3_ref, o_ref, pad_ref):
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m = g * h * h
    pad = h + 1                       # max |shift| = h + 1
    x = x_ref[...]                                   # (m, C) bf16
    y1 = dot(x, w1_ref[...])                         # (m, F) f32
    y1 = y1 * s1_ref[...] + b1_ref[...]
    y1 = jnp.maximum(y1, 0.0).astype(jnp.bfloat16)
    pad_ref[...] = jnp.zeros_like(pad_ref)
    pad_ref[pad:pad + m, :] = y1
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    yy = (rows % (h * h)) // h
    xx = rows % h
    acc = jnp.zeros((m, y1.shape[1]), jnp.float32)
    for ky in range(3):
        for kx in range(3):
            off = (ky - 1) * h + (kx - 1)
            sl = pad_ref[pad + off:pad + off + m, :]
            ok = ((yy + (ky - 1) >= 0) & (yy + (ky - 1) < h) &
                  (xx + (kx - 1) >= 0) & (xx + (kx - 1) < h))
            acc += dot(sl, w2_ref[ky * 3 + kx]) * ok.astype(jnp.float32)
    y2 = acc * s2_ref[...] + b2_ref[...]
    y2 = jnp.maximum(y2, 0.0).astype(jnp.bfloat16)
    y3 = dot(y2, w3_ref[...])
    y3 = y3 * s3_ref[...] + b3_ref[...]
    o_ref[...] = jnp.maximum(
        y3 + x.astype(jnp.float32), 0.0).astype(jnp.bfloat16)


def fused_block2d(x, params, g):
    n, h, _, c = x.shape
    f = params["w1"].shape[1]
    m = g * h * h
    x2 = x.reshape(n * h * h, c)
    wspec = lambda shp: pl.BlockSpec(shp, lambda i: (0,) * len(shp))
    out = pl.pallas_call(
        functools.partial(_fused_kernel2d, h, g),
        grid=(n // g,),
        in_specs=[
            pl.BlockSpec((m, c), lambda i: (i, 0)),
            wspec((c, f)), wspec((1, f)), wspec((1, f)),
            wspec((9, f, f)), wspec((1, f)), wspec((1, f)),
            wspec((f, c)), wspec((1, c)), wspec((1, c)),
        ],
        out_specs=pl.BlockSpec((m, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * h * h, c), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((m + 2 * (h + 1), f), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(x2, params["w1"], params["s1"], params["b1"], params["w2"],
      params["s2"], params["b2"], params["w3"], params["s3"], params["b3"])
    return out.reshape(n, h, h, c)


# ---------------------------------------------------------------------------
# XLA reference (identical math)
# ---------------------------------------------------------------------------

def xla_block(x, params):
    f = params["w1"].shape[1]

    def affine(y, s, b):
        return y * s.reshape(1, 1, 1, -1) + b.reshape(1, 1, 1, -1)

    y = jnp.einsum("nhwc,cf->nhwf", x, params["w1"],
                   preferred_element_type=jnp.float32)
    y = jnp.maximum(affine(y, params["s1"], params["b1"]), 0.0) \
        .astype(jnp.bfloat16)
    w2 = params["w2"].reshape(3, 3, f, f)
    y = lax.conv_general_dilated(
        y, w2, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    y = jnp.maximum(affine(y, params["s2"], params["b2"]), 0.0) \
        .astype(jnp.bfloat16)
    y = jnp.einsum("nhwf,fc->nhwc", y, params["w3"],
                   preferred_element_type=jnp.float32)
    y = affine(y, params["s3"], params["b3"])
    return jnp.maximum(y + x.astype(jnp.float32), 0.0).astype(jnp.bfloat16)


def xla_block_conv(x, params):
    """Same math, but 1x1 convs lowered via conv_general_dilated — the
    way a framework emitting conv ops (ours included) hits XLA."""
    f = params["w1"].shape[1]

    def affine(y, s, b):
        return y * s.reshape(1, 1, 1, -1) + b.reshape(1, 1, 1, -1)

    def conv(y, w, kh):
        return lax.conv_general_dilated(
            y, w.reshape(kh, kh, w.shape[-2], w.shape[-1]), (1, 1),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)

    y = conv(x, params["w1"][None, None], 1)
    y = jnp.maximum(affine(y, params["s1"], params["b1"]), 0.0) \
        .astype(jnp.bfloat16)
    y = conv(y, params["w2"].reshape(3, 3, f, f), 3)
    y = jnp.maximum(affine(y, params["s2"], params["b2"]), 0.0) \
        .astype(jnp.bfloat16)
    y = conv(y, params["w3"][None, None], 1)
    y = affine(y, params["s3"], params["b3"])
    return jnp.maximum(y + x.astype(jnp.float32), 0.0).astype(jnp.bfloat16)


def make_params(key, c, f):
    ks = jax.random.split(key, 3)
    sc = lambda k, shp, s: (jax.random.normal(k, shp, jnp.float32) * s
                            ).astype(jnp.bfloat16)
    return {
        "w1": sc(ks[0], (c, f), (2.0 / c) ** 0.5),
        "w2": sc(ks[1], (9, f, f), (2.0 / (9 * f)) ** 0.5),
        "w3": sc(ks[2], (f, c), (2.0 / f) ** 0.5),
        "s1": jnp.full((1, f), 1.0), "b1": jnp.zeros((1, f)),
        "s2": jnp.full((1, f), 0.5), "b2": jnp.zeros((1, f)),
        "s3": jnp.full((1, c), 0.3), "b3": jnp.zeros((1, c)),
    }


def bench(fn, x, params, k, label, flops):
    """Two-point (slope) timing: per-iteration time is the SLOPE
    between chains of k and 5k iterations, so a fixed cost per
    launch+sync cancels (dividing one chain's wall time by k leaves
    that cost, over k, inside millisecond block times)."""
    def chain_t(iters, reps=3):
        @jax.jit
        def chain(x):
            def body(y, _):
                return fn(y, params), None
            y, _ = lax.scan(body, x, None, length=iters)
            return jnp.sum(y.astype(jnp.float32))

        float(chain(x))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(chain(x))
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = chain_t(k)
    t2 = chain_t(5 * k)
    per = (t2 - t1) / (4 * k)
    eff = flops / per / V5E_PEAK_BF16
    print(json.dumps({"path": label, "ms": round(per * 1e3, 3),
                      "frac_of_peak": round(eff, 4)}), flush=True)
    return per


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", default="s2", choices=list(STAGES))
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--g", type=int, default=0, help="imgs/cell (0=sweep)")
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()

    h, c, f = STAGES[args.stage]
    n = args.batch
    flops = n * block_flops(h, c, f)
    print(json.dumps({"stage": args.stage, "h": h, "c": c, "f": f,
                      "batch": n, "gflops_per_call": round(flops / 1e9, 1)}),
          flush=True)
    params = make_params(jax.random.key(0), c, f)
    x = (jax.random.normal(jax.random.key(1), (n, h, h, c), jnp.float32)
         * 0.5).astype(jnp.bfloat16)

    if args.check:
        ref = xla_block(x[:8], params)
        for label, fn in (("4d", fused_block), ("2d", fused_block2d)):
            out = fn(x[:8], params, 4)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - ref.astype(jnp.float32))))
            rel = err / float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
            print(json.dumps({"check": label, "max_abs_err": err,
                              "rel": round(rel, 5)}), flush=True)

    t_xla = bench(lambda y, p: xla_block(y, p), x, params, args.k,
                  "xla_dot", flops)
    bench(lambda y, p: xla_block_conv(y, p), x, params, args.k,
          "xla_conv", flops)
    gs = [args.g] if args.g else [2, 4, 8, 16]
    for label, fn in (("2d", fused_block2d), ("4d", fused_block)):
        for g in gs:
            if n % g:
                continue
            try:
                t = bench(lambda y, p, g=g, fn=fn: fn(y, p, g), x, params,
                          args.k, f"pallas{label}_g{g}", flops)
                print(json.dumps({"variant": label, "g": g,
                                  "speedup_vs_xla": round(t_xla / t, 3)}),
                      flush=True)
            except Exception as e:
                print(json.dumps({"variant": label, "g": g,
                                  "error": str(e)[:160]}), flush=True)


if __name__ == "__main__":
    main()
