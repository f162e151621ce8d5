"""Isolate the conv backward lowering at early-ResNet shapes (lean).

probe_block_train r4: s0/s1 block backward runs at 15-23% of peak while
the forward hits 32-62%. Times dx (transposed conv) and dW (correlation)
separately per shape, vs a dot-based dW reformulation
(conv_general_dilated_patches + one huge-K dot_general).
Fixed two-point chains (k and 5k) slope out the fixed launch cost.
"""
from __future__ import annotations

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


def slope(step_fn, x0, k1, reps=3):
    def chain_t(iters):
        @jax.jit
        def chain(a):
            def body(carry, _):
                return step_fn(carry), None
            c, _ = lax.scan(body, a, None, length=iters)
            return jnp.sum(c[..., :1].astype(jnp.float32))

        float(chain(x0))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(chain(x0))
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = chain_t(k1)
    t2 = chain_t(5 * k1)
    return (t2 - t1) / (4 * k1)


def conv(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def bench_shape(n, h, cin, cout, kh, k1):
    flops = 2 * n * h * h * kh * kh * cin * cout
    x = (jax.random.normal(jax.random.key(0), (n, h, h, cin), jnp.float32)
         * 0.1).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (kh, kh, cin, cout),
                           jnp.float32) * 0.05).astype(jnp.bfloat16)
    dy = (jax.random.normal(jax.random.key(2), (n, h, h, cout),
                            jnp.float32) * 0.1).astype(jnp.bfloat16)
    out = {"n": n, "h": h, "cin": cin, "cout": cout, "k": kh}

    def dx_step(xx):
        _, vjp = jax.vjp(lambda a: conv(a, w), xx)
        (gx,) = vjp(dy + xx[..., :1] * jnp.bfloat16(1e-30))
        return gx * jnp.bfloat16(0.999) if cin == cout else \
            gx * jnp.bfloat16(0.999)
    per = slope(dx_step, x, k1)
    out["dx_ms"] = round(per * 1e3, 3)
    out["dx_eff"] = round(flops / per / V5E_PEAK_BF16, 3)

    def dw_step(xx):
        gw = jax.grad(lambda ww: jnp.sum(
            conv(xx, ww).astype(jnp.float32) * dy.astype(jnp.float32)))(w)
        return xx + (jnp.sum(gw) * 1e-30).astype(jnp.bfloat16)
    per = slope(dw_step, x, k1)
    out["dw_ms"] = round(per * 1e3, 3)
    out["dw_eff"] = round(flops / per / V5E_PEAK_BF16, 3)

    if kh == 3:
        def dw_dot_step(xx):
            p = lax.conv_general_dilated_patches(
                xx, (3, 3), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            gw = lax.dot_general(
                p.reshape(-1, cin * 9), dy.reshape(-1, cout),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return xx + (jnp.sum(gw) * 1e-30).astype(jnp.bfloat16)
        per = slope(dw_dot_step, x, k1)
        out["dw_dot_ms"] = round(per * 1e3, 3)
        out["dw_dot_eff"] = round(flops / per / V5E_PEAK_BF16, 3)

    print(json.dumps(out), flush=True)


bench_shape(256, 56, 64, 64, 3, 60)     # s0 conv2
bench_shape(256, 56, 256, 64, 1, 60)    # s0 conv1
bench_shape(256, 28, 128, 128, 3, 60)   # s1 conv2
