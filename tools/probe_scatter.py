"""TPU scatter-add primitives for the Word2Vec update path (r4).

VERDICT r3 item 2: attack the 374.8k words/s scatter bound with a
different algorithm. This probe measures the primitive space on a
realistic workload (V=100k vocab, D=128, Zipf-ish unigram^0.75 ids,
R update rows per step):

  scatter_rand     - .at[ids].add(upd), random duplicate ids (current)
  scatter_sorted   - same ids sorted, indices_are_sorted=True
  scatter_unique   - R DISTINCT sorted ids: can XLA parallelize when it
                     does not have to serialize duplicate rows?
  sort_machinery   - argsort+gather+cumsum+flags alone (compaction cost)
  hot_matmul       - one-hot [R,H] @ upd MXU accumulation into a dense
                     top-H slab (no scatter at all; H=4096)

Slope-timed (two-span) to cancel the fixed per-launch cost.
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

V, D, R, H = 100_000, 128, 28_672, 4096


def slope(step_fn, x0, k1=100, reps=3):
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


rng = np.random.default_rng(0)
probs = (np.arange(1, V + 1) ** -0.75)
probs /= probs.sum()
ids_np = rng.choice(V, size=R, p=probs).astype(np.int32)
frac_hot = float((ids_np < H).mean())
upd = jnp.asarray(rng.normal(size=(R, D)) * 1e-4, jnp.float32)
table = jnp.zeros((V, D), jnp.float32)
ids = jnp.asarray(ids_np)
ids_sorted = jnp.asarray(np.sort(ids_np))
ids_unique = jnp.asarray(
    np.sort(rng.choice(V, size=R, replace=False)).astype(np.int32))

out = {"V": V, "D": D, "R": R, "H": H, "frac_hot": round(frac_hot, 3)}
print(json.dumps(out), flush=True)


def report(name, per):
    print(json.dumps({
        "variant": name, "ms": round(per * 1e3, 3),
        "rows_per_s_M": round(R / per / 1e6, 1),
        "bytes_gbps": round(R * D * 4 * 3 / per / 1e9, 1)}), flush=True)


report("scatter_rand", slope(
    lambda t: t.at[ids].add(upd), table))
report("scatter_sorted", slope(
    lambda t: t.at[ids_sorted].add(upd, indices_are_sorted=True), table))
report("scatter_unique", slope(
    lambda t: t.at[ids_unique].add(upd, indices_are_sorted=True,
                                   unique_indices=True), table))


def machinery(t):
    order = jnp.argsort(ids)
    ids_s = ids[order]
    upd_s = upd[order]
    csum = jnp.cumsum(upd_s, axis=0)
    last = jnp.concatenate([ids_s[1:] != ids_s[:-1],
                            jnp.ones((1,), bool)])
    return t + (jnp.sum(csum[-1] * last[-1]) * 1e-30)


report("sort_machinery", slope(machinery, table))


def hot_matmul(t):
    onehot = (ids[:, None] == jnp.arange(H)[None, :]).astype(jnp.bfloat16)
    slab = lax.dot_general(onehot, upd.astype(jnp.bfloat16),
                           (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
    return t.at[:H].add(slab)


report("hot_matmul", slope(hot_matmul, table))
