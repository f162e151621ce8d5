"""Train-mode (fwd+bwd) bottleneck-block probe with slope timing.

probe_fused_block r4 found the FORWARD XLA block at ~96% of peak once
the fixed per-launch cost is slope-cancelled — so ResNet-50's measured
~16% training MFU is NOT a per-block conv ceiling. This probe bisects
training: fwd-only vs fwd+bwd, affine-BN vs one-pass batch-stats BN,
with/without residual, at each stage shape.

Chaining keeps a serial dependence through BOTH x-grads and param-grads
so nothing is DCE'd or hoisted.
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

V5E_PEAK_BF16 = 197e12
STAGES = {"s0": (56, 256, 64), "s1": (28, 512, 128),
          "s2": (14, 1024, 256), "s3": (7, 2048, 512)}


def make_block(bn_mode, residual=True):
    def affine(y, s, b):
        return y * s.reshape(1, 1, 1, -1) + b.reshape(1, 1, 1, -1)

    def bn(y, s, b):
        if bn_mode == "affine":
            return affine(y.astype(jnp.float32), s, b)
        yf = y.astype(jnp.float32)
        mean = jnp.mean(yf, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(yf), axis=(0, 1, 2)) - jnp.square(mean)
        inv = lax.rsqrt(var + 1e-5) * s
        return yf * inv.reshape(1, 1, 1, -1) + \
            (b - mean * inv).reshape(1, 1, 1, -1)

    def conv(y, w, kh):
        # pure-bf16 conv (probe_resnet's lowering): output bf16, so the
        # autodiff-transposed convs see bf16 cotangents (a f32
        # preferred_element_type output would hand the transpose a f32
        # cotangent conv_general_dilated rejects against bf16 weights)
        return lax.conv_general_dilated(
            y.astype(jnp.bfloat16),
            w.reshape(kh, kh, w.shape[-2], w.shape[-1])
            .astype(jnp.bfloat16), (1, 1),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def block(params, x):
        f = params["w1"].shape[1]
        y = conv(x, params["w1"][None, None], 1)
        y = jnp.maximum(bn(y, params["s1"], params["b1"]), 0.0) \
            .astype(jnp.bfloat16)
        y = conv(y, params["w2"].reshape(3, 3, f, f), 3)
        y = jnp.maximum(bn(y, params["s2"], params["b2"]), 0.0) \
            .astype(jnp.bfloat16)
        y = conv(y, params["w3"][None, None], 1)
        y = bn(y, params["s3"], params["b3"])  # f32
        if residual:
            y = y + x.astype(jnp.float32)
        return jnp.maximum(y, 0.0).astype(jnp.bfloat16)

    return block


def make_params(key, c, f):
    ks = jax.random.split(key, 3)
    sc = lambda k, shp, s: (jax.random.normal(k, shp, jnp.float32) * s
                            ).astype(jnp.bfloat16)
    return {"w1": sc(ks[0], (c, f), (2.0 / c) ** 0.5),
            "w2": sc(ks[1], (9, f, f), (2.0 / (9 * f)) ** 0.5),
            "w3": sc(ks[2], (f, c), (2.0 / f) ** 0.5),
            "s1": jnp.full((f,), 1.0), "b1": jnp.zeros((f,)),
            "s2": jnp.full((f,), 1.0), "b2": jnp.zeros((f,)),
            "s3": jnp.full((c,), 0.3), "b3": jnp.zeros((c,))}


def slope_bench(step, x0, k1, label, flops):
    """Two-span slope timing with auto-scaling: span length grows until
    the long chain runs >=1.5 s so launch-to-launch noise cannot
    swamp the slope; reports both of two independent slope estimates so
    disagreement is visible."""
    def chain_t(iters, reps=4):
        @jax.jit
        def chain(x):
            def body(y, _):
                return step(y), None
            y, _ = lax.scan(body, x, None, length=iters)
            return jnp.sum(y.astype(jnp.float32))

        float(chain(x0))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(chain(x0))
            best = min(best, time.perf_counter() - t0)
        return best

    # rough per-iter estimate to size the spans
    t_probe = chain_t(k1, reps=2)
    per0 = max(t_probe / k1, 1e-5)
    k_long = max(k1, int(1.5 / per0))
    k_short = k_long // 5
    t1 = chain_t(k_short)
    t2 = chain_t(k_long)
    per_a = (t2 - t1) / (k_long - k_short)
    t1b = chain_t(k_short)
    t2b = chain_t(k_long)
    per_b = (t2b - t1b) / (k_long - k_short)
    per = (per_a + per_b) / 2
    print(json.dumps({"path": label, "ms": round(per * 1e3, 3),
                      "ms_b": round(max(per_a, per_b) * 1e3, 3),
                      "frac_of_peak": round(flops / per / V5E_PEAK_BF16,
                                            4)}), flush=True)
    return per


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", default="s2", choices=list(STAGES))
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=30)
    args = ap.parse_args()
    h, c, f = STAGES[args.stage]
    n = args.batch
    fwd_flops = n * 2 * h * h * (c * f + 9 * f * f + f * c)
    params = make_params(jax.random.key(0), c, f)
    x = (jax.random.normal(jax.random.key(1), (n, h, h, c), jnp.float32)
         * 0.5).astype(jnp.bfloat16)
    print(json.dumps({"stage": args.stage, "batch": n,
                      "fwd_gflops": round(fwd_flops / 1e9, 1)}), flush=True)

    for bn_mode in ("affine", "onepass"):
        blk = make_block(bn_mode)
        slope_bench(lambda y: blk(params, y), x, args.k,
                    f"fwd_{bn_mode}", fwd_flops)

        def train_step(y, blk=blk):
            def loss_fn(p, yy):
                return jnp.sum(blk(p, yy).astype(jnp.float32) ** 2) * 1e-6
            l, (gp, gy) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params, y)
            tiny = sum(jnp.sum(t.astype(jnp.float32)) * 1e-30
                       for t in jax.tree_util.tree_leaves(gp))
            return (y - gy * jnp.bfloat16(1e-6)
                    + (tiny * 0 + l * 0).astype(jnp.bfloat16))

        slope_bench(train_step, x, max(args.k // 3, 10),
                    f"train_{bn_mode}", 3 * fwd_flops)


if __name__ == "__main__":
    main()
