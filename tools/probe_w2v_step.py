"""SGNS training-step decomposition probe (r5, VERDICT item 1).

The r4 bench note: the 10M-word epoch = ~4.4 s device pair-gen +
~8.9 s training scan, updates at ~4.5M pairs/s against a 125M rows/s
sorted-scatter primitive. This probe isolates the step's levers on the
real chip with slope timing:

  A  current step (gathers + analytic grads + 2 sorted dup scatters)
  B  no-sort (raw duplicate scatter — is the argsort paying for itself?)
  C  sort + cumsum segment-sum -> UNIQUE-row scatter (dedup before
     scatter; Zipf batches have heavy duplication)
  D  batch-width sweep of A and C (8k/32k/128k rows per step)
  E  the gather+matmul math alone (no scatter) — the non-scatter floor

Run: python tools/probe_w2v_step.py   (on the chip)
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

V, D, K_NEG = 100_000, 128, 5
LR = 0.025


def slope(make_chain, k1=40, reps=3):
    def chain_t(iters):
        fn = make_chain(iters)
        fn()  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = chain_t(k1)
    t2 = chain_t(5 * k1)
    return (t2 - t1) / (4 * k1)


def make_batches(bsz, rng):
    probs = (np.arange(1, V + 1) ** -1.05)
    probs /= probs.sum()
    neg_probs = (np.arange(1, V + 1) ** -0.75)
    neg_probs /= neg_probs.sum()
    cent = rng.choice(V, size=bsz, p=probs).astype(np.int32)
    ctx = rng.choice(V, size=bsz, p=probs).astype(np.int32)
    negs = rng.choice(V, size=(bsz, K_NEG), p=neg_probs).astype(np.int32)
    w = np.ones(bsz, np.float32)
    return (jnp.asarray(cent), jnp.asarray(ctx), jnp.asarray(negs),
            jnp.asarray(w))


def grads(syn0, syn1, cent, ctx, negs, w):
    c = syn0[cent]
    pos = syn1[ctx]
    neg = syn1[negs]
    pos_s = jnp.sum(c * pos, axis=-1)
    neg_s = jnp.einsum("bd,bkd->bk", c, neg)
    dpos = -(1.0 - jax.nn.sigmoid(pos_s)) * w
    dneg = jax.nn.sigmoid(neg_s) * w[:, None]
    gc = dpos[:, None] * pos + jnp.einsum("bk,bkd->bd", dneg, neg)
    ids1 = jnp.concatenate([ctx, negs.reshape(-1)])
    u1 = jnp.concatenate([
        dpos[:, None] * c,
        (dneg[..., None] * c[:, None, :]).reshape(-1, D)])
    return gc, ids1, u1


def apply_sorted(table, ids, upd):
    o = jnp.argsort(ids)
    return table.at[ids[o]].add(-LR * upd[o], indices_are_sorted=True)


def apply_unsorted(table, ids, upd):
    return table.at[ids].add(-LR * upd)


def apply_unique(table, ids, upd):
    """Sort, segment-sum duplicate rows, scatter UNIQUE sorted rows."""
    o = jnp.argsort(ids)
    sid = ids[o]
    u = upd[o]
    n = sid.shape[0]
    is_first = jnp.concatenate([jnp.ones((1,), bool),
                                sid[1:] != sid[:-1]])
    seg_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1   # sorted
    seg = jax.ops.segment_sum(u, seg_id, num_segments=n,
                              indices_are_sorted=True)
    firsts = jnp.nonzero(is_first, size=n, fill_value=n - 1)[0]
    n_seg = seg_id[-1] + 1
    dest = jnp.where(jnp.arange(n) < n_seg, sid[firsts], V)
    return table.at[dest].add(-LR * seg, mode="drop",
                              unique_indices=True,
                              indices_are_sorted=True)


def step_variant(apply1, apply0):
    def step(syn0, syn1, cent, ctx, negs, w):
        gc, ids1, u1 = grads(syn0, syn1, cent, ctx, negs, w)
        syn0 = apply0(syn0, cent, gc)
        syn1 = apply1(syn1, ids1, u1)
        return syn0, syn1

    return step


def math_only(syn0, syn1, cent, ctx, negs, w):
    gc, ids1, u1 = grads(syn0, syn1, cent, ctx, negs, w)
    return syn0 - 1e-9 * jnp.sum(gc), syn1 - 1e-9 * jnp.sum(u1)


def time_step(step, bsz, rng):
    batch = make_batches(bsz, rng)
    syn0 = jnp.asarray(rng.normal(size=(V, D)) * 0.01, jnp.float32)
    syn1 = jnp.zeros((V, D), jnp.float32)

    def make_chain(iters):
        @jax.jit
        def chain(s0, s1):
            def body(carry, _):
                a, b = carry
                return step(a, b, *batch), None
            (a, b), _ = lax.scan(body, (s0, s1), None, length=iters)
            return jnp.sum(a[0, :1]) + jnp.sum(b[0, :1])

        def run():
            return float(chain(syn0, syn1))

        return run

    return slope(make_chain)


def time_step_proddraw(bsz, rng, table_size=10_000_000,
                       key_impl="rbg", draw_only=False):
    """Replica of the production scan body: negatives drawn ON DEVICE
    per step (fold_in + randint + unigram-table gather), then the A
    step. draw_only=True times just the draw+gather."""
    cent, ctx, _negs, w = make_batches(bsz, rng)
    table = jnp.asarray(
        rng.integers(0, V, table_size).astype(np.int32))
    syn0 = jnp.asarray(rng.normal(size=(V, D)) * 0.01, jnp.float32)
    syn1 = jnp.zeros((V, D), jnp.float32)
    key = jax.random.key(7, impl=key_impl)
    base_step = step_variant(apply_sorted, apply_sorted)

    def make_chain(iters):
        @jax.jit
        def chain(s0, s1):
            def body(carry, _):
                a, b, i = carry
                draws = jax.random.randint(
                    jax.random.fold_in(key, i),
                    (bsz, K_NEG), 0, table_size)
                negs = table[draws]
                if draw_only:
                    a = a + 1e-12 * jnp.sum(negs).astype(jnp.float32)
                else:
                    a, b = base_step(a, b, cent, ctx, negs, w)
                return (a, b, i + 1), None
            (a, b, _), _ = lax.scan(body, (s0, s1, jnp.int32(0)),
                                    None, length=iters)
            return jnp.sum(a[0, :1]) + jnp.sum(b[0, :1])

        def run():
            return float(chain(syn0, syn1))

        return run

    return slope(make_chain)


def main():
    rng = np.random.default_rng(0)
    print(json.dumps({"V": V, "D": D, "k_neg": K_NEG,
                      "device": str(jax.devices()[0])}), flush=True)
    rows_per_pair = 1 + 1 + K_NEG  # cent + ctx + negs

    for bsz in (8192,):
        for name, kw in (
                ("F_prod_replica_rbg", {}),
                ("F_prod_replica_threefry", {"key_impl": "threefry2x32"}),
                ("G_draw_gather_only_rbg", {"draw_only": True}),
                ("H_prod_small_table", {"table_size": 1_000_000}),
        ):
            per = time_step_proddraw(bsz, rng, **kw)
            print(json.dumps({
                "variant": name, "bsz": bsz,
                "ms_per_step": round(per * 1e3, 3),
                "pairs_per_s_M": round(bsz / per / 1e6, 2),
            }), flush=True)

    for bsz in (8192, 32768, 131072):
        batch_dup = make_batches(bsz, rng)
        ids1 = np.concatenate([np.asarray(batch_dup[1]),
                               np.asarray(batch_dup[2]).ravel()])
        uniq = len(np.unique(ids1))
        variants = {
            "A_sorted_dup": step_variant(apply_sorted, apply_sorted),
            "B_unsorted": step_variant(apply_unsorted, apply_unsorted),
            "C_unique_seg": step_variant(apply_unique, apply_unique),
            "E_math_only": math_only,
        }
        for name, st in variants.items():
            per = time_step(st, bsz, rng)
            print(json.dumps({
                "variant": name, "bsz": bsz,
                "uniq_frac_syn1": round(uniq / len(ids1), 3),
                "ms_per_step": round(per * 1e3, 3),
                "pairs_per_s_M": round(bsz / per / 1e6, 2),
                "rows_per_s_M": round(bsz * rows_per_pair / per / 1e6, 1),
            }), flush=True)


if __name__ == "__main__":
    main()
