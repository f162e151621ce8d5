"""Pair-generation decomposition probe (r5, VERDICT item 1).

The r4 bench: ~4.4 s/epoch of device pair-gen for a 10M-word corpus.
Ablations on the real chip:

  full          production gen (windows + validity + cumsum + 2 scatters)
  no_compact    same but returns the uncompacted (cent, ctx, valid)
  searchsorted  scatter-free compaction: destination offsets are the
                cumsum of per-position pair counts (2b each), so output
                slot o maps back to its position by binary search and to
                its context by rank decode — all gathers, no scatter

Run: python tools/probe_w2v_pairgen.py
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

W = 5
P = 8_388_608          # ~8.4M positions (10M words post-subsample)
CAP2_MARGIN = 1.03


def _force(r):
    """Materialize on host: reduce-and-float every output."""
    return sum(float(jnp.sum(jnp.ravel(x).astype(jnp.float32)[:1]))
               for x in jax.tree_util.tree_leaves(r))


def timeit(fn, *args, reps=3):
    r = fn(*args)
    _force(r)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn(*args)
        _force(r)
        best = min(best, time.perf_counter() - t0)
    return best, r


def gen_full(flat, sid, key):
    p = flat.shape[0]
    pos = jnp.arange(p, dtype=jnp.int32)
    b = jax.random.randint(key, (p,), 1, W + 1)
    cents, ctxs, vals = [], [], []
    for d in (*range(-W, 0), *range(1, W + 1)):
        j = jnp.clip(pos + d, 0, p - 1)
        valid = ((sid >= 0) & (sid[j] == sid) & (jnp.abs(d) <= b)
                 & (pos + d >= 0) & (pos + d < p))
        cents.append(flat)
        ctxs.append(flat[j])
        vals.append(valid)
    cent_s = jnp.stack(cents, 1).reshape(-1)
    ctx_s = jnp.stack(ctxs, 1).reshape(-1)
    val_s = jnp.stack(vals, 1).reshape(-1)
    cap = cent_s.shape[0]
    csum = jnp.cumsum(val_s.astype(jnp.int32))
    n_real = csum[-1]
    dest = jnp.where(val_s, csum - 1, cap + jnp.arange(cap))
    out_c = jnp.zeros((cap,), jnp.int32).at[dest].set(
        cent_s, mode="drop", unique_indices=True)
    out_x = jnp.zeros((cap,), jnp.int32).at[dest].set(
        ctx_s, mode="drop", unique_indices=True)
    return out_c, out_x, n_real


def gen_no_compact(flat, sid, key):
    p = flat.shape[0]
    pos = jnp.arange(p, dtype=jnp.int32)
    b = jax.random.randint(key, (p,), 1, W + 1)
    cents, ctxs, vals = [], [], []
    for d in (*range(-W, 0), *range(1, W + 1)):
        j = jnp.clip(pos + d, 0, p - 1)
        valid = ((sid >= 0) & (sid[j] == sid) & (jnp.abs(d) <= b)
                 & (pos + d >= 0) & (pos + d < p))
        cents.append(flat)
        ctxs.append(flat[j])
        vals.append(valid)
    cent_s = jnp.stack(cents, 1).reshape(-1)
    ctx_s = jnp.stack(ctxs, 1).reshape(-1)
    val_s = jnp.stack(vals, 1).reshape(-1)
    return cent_s, ctx_s, val_s.astype(jnp.float32)


def gen_searchsorted(flat, sid, key):
    """Scatter-free: per-position pair count is known analytically
    (only window clipping / sentence edges / corpus edges reduce it),
    so compute counts per position, cumsum, then map output slots back
    with searchsorted + rank decode. All gathers."""
    p = flat.shape[0]
    pos = jnp.arange(p, dtype=jnp.int32)
    b = jax.random.randint(key, (p,), 1, W + 1)
    # count valid contexts per position (vector math, no 2W stack)
    cnt = jnp.zeros((p,), jnp.int32)
    for d in (*range(-W, 0), *range(1, W + 1)):
        j = jnp.clip(pos + d, 0, p - 1)
        valid = ((sid >= 0) & (sid[j] == sid) & (jnp.abs(d) <= b)
                 & (pos + d >= 0) & (pos + d < p))
        cnt = cnt + valid.astype(jnp.int32)
    offs = jnp.cumsum(cnt)              # offs[i] = end of pos i's run
    n_real = offs[-1]
    cap2 = int(P * (W + 1) * CAP2_MARGIN)
    o = jnp.arange(cap2, dtype=jnp.int32)
    src = jnp.searchsorted(offs, o, side="right").astype(jnp.int32)
    src = jnp.minimum(src, p - 1)
    start = offs[src] - cnt[src]
    rank = o - start                    # 0.. cnt[src]-1
    # decode rank -> d: valid d ascending. With per-side truncation:
    # left side has L = min(b, how far left we can go) entries
    sent_ok = sid[src] >= 0
    left_room = jnp.stack(
        [((sid[jnp.clip(src - k, 0, p - 1)] == sid[src])
          & (src - k >= 0) & (k <= b[src])).astype(jnp.int32)
         for k in range(1, W + 1)], 1).sum(1)
    d_off = rank - left_room
    d = jnp.where(d_off < 0, d_off, d_off + 1)
    j = jnp.clip(src + d, 0, p - 1)
    w = ((o < n_real) & sent_ok).astype(jnp.float32)
    return flat[src], flat[j] * (w > 0), w


def gen_direct(flat, sid, key):
    """Position-major slot order identical to gen_full, but cent/ctx/
    valid computed by direct slot-index math (gathers) instead of
    stacking 2W shifted copies — no transposed [P, 2W] interleave
    writes."""
    p = flat.shape[0]
    b = jax.random.randint(key, (p,), 1, W + 1)
    cap = p * 2 * W
    s = jnp.arange(cap, dtype=jnp.int32)
    pos = s // (2 * W)
    di = s % (2 * W)
    d = jnp.where(di < W, di - W, di - W + 1)
    tgt = pos + d
    j = jnp.clip(tgt, 0, p - 1)
    sp = sid[pos]
    valid = ((sp >= 0) & (sid[j] == sp) & (jnp.abs(d) <= b[pos])
             & (tgt >= 0) & (tgt < p))
    cent_s = flat[pos]
    ctx_s = flat[j]
    csum = jnp.cumsum(valid.astype(jnp.int32))
    n_real = csum[-1]
    dest = jnp.where(valid, csum - 1, cap + jnp.arange(cap))
    out_c = jnp.zeros((cap,), jnp.int32).at[dest].set(
        cent_s, mode="drop", unique_indices=True)
    out_x = jnp.zeros((cap,), jnp.int32).at[dest].set(
        ctx_s, mode="drop", unique_indices=True)
    return out_c, out_x, n_real


def gen_direct_no_compact(flat, sid, key):
    p = flat.shape[0]
    b = jax.random.randint(key, (p,), 1, W + 1)
    cap = p * 2 * W
    s = jnp.arange(cap, dtype=jnp.int32)
    pos = s // (2 * W)
    di = s % (2 * W)
    d = jnp.where(di < W, di - W, di - W + 1)
    tgt = pos + d
    j = jnp.clip(tgt, 0, p - 1)
    sp = sid[pos]
    valid = ((sp >= 0) & (sid[j] == sp) & (jnp.abs(d) <= b[pos])
             & (tgt >= 0) & (tgt < p))
    return flat[pos], flat[j], valid.astype(jnp.float32)


def _shift(a, d, fill_edge=True):
    """a shifted by d with edge-clamp semantics (== a[clip(pos+d)]),
    expressed as slice+concat: TPU scalar gathers run at ~0.19 GB/s on
    this chip (measured above), slices at full bandwidth."""
    p = a.shape[0]
    if d == 0:
        return a
    if d > 0:
        edge = jnp.broadcast_to(a[-1:], (d,)) if fill_edge else \
            jnp.zeros((d,), a.dtype)
        return jnp.concatenate([a[d:], edge])
    edge = jnp.broadcast_to(a[:1], (-d,)) if fill_edge else \
        jnp.zeros((-d,), a.dtype)
    return jnp.concatenate([edge, a[:d]])


def gen_slices_rowscatter(flat, sid, key):
    """Slice-based shifts + ONE [cap, 2] row-scatter compaction (cent
    and ctx ride one scatter as a 2-wide row; no x64 needed)."""
    p = flat.shape[0]
    pos = jnp.arange(p, dtype=jnp.int32)
    b = jax.random.randint(key, (p,), 1, W + 1)
    cents, ctxs, vals = [], [], []
    for d in (*range(-W, 0), *range(1, W + 1)):
        valid = ((sid >= 0) & (_shift(sid, d) == sid)
                 & (jnp.abs(d) <= b)
                 & (pos + d >= 0) & (pos + d < p))
        cents.append(flat)
        ctxs.append(_shift(flat, d))
        vals.append(valid)
    cent_s = jnp.stack(cents, 1).reshape(-1)
    ctx_s = jnp.stack(ctxs, 1).reshape(-1)
    val_s = jnp.stack(vals, 1).reshape(-1)
    cap = cent_s.shape[0]
    rows = jnp.stack([cent_s, ctx_s], 1)           # [cap, 2]
    csum = jnp.cumsum(val_s.astype(jnp.int32))
    n_real = csum[-1]
    dest = jnp.where(val_s, csum - 1, cap + jnp.arange(cap))
    out = jnp.zeros((cap, 2), jnp.int32).at[dest].set(
        rows, mode="drop", unique_indices=True)
    return out[:, 0], out[:, 1], n_real


def gen_slices_two_scatter(flat, sid, key):
    """Slice-based shifts, original two int32 scatters."""
    p = flat.shape[0]
    pos = jnp.arange(p, dtype=jnp.int32)
    b = jax.random.randint(key, (p,), 1, W + 1)
    cents, ctxs, vals = [], [], []
    for d in (*range(-W, 0), *range(1, W + 1)):
        valid = ((sid >= 0) & (_shift(sid, d) == sid)
                 & (jnp.abs(d) <= b)
                 & (pos + d >= 0) & (pos + d < p))
        cents.append(flat)
        ctxs.append(_shift(flat, d))
        vals.append(valid)
    cent_s = jnp.stack(cents, 1).reshape(-1)
    ctx_s = jnp.stack(ctxs, 1).reshape(-1)
    val_s = jnp.stack(vals, 1).reshape(-1)
    cap = cent_s.shape[0]
    csum = jnp.cumsum(val_s.astype(jnp.int32))
    n_real = csum[-1]
    dest = jnp.where(val_s, csum - 1, cap + jnp.arange(cap))
    out_c = jnp.zeros((cap,), jnp.int32).at[dest].set(
        cent_s, mode="drop", unique_indices=True)
    out_x = jnp.zeros((cap,), jnp.int32).at[dest].set(
        ctx_s, mode="drop", unique_indices=True)
    return out_c, out_x, n_real


def main():
    rng = np.random.default_rng(0)
    print(json.dumps({"P": P, "W": W,
                      "device": str(jax.devices()[0])}), flush=True)
    sent_len = 25
    flat = rng.integers(0, 100_000, P).astype(np.int32)
    sid = np.repeat(np.arange(P // sent_len + 1, dtype=np.int32),
                    sent_len)[:P]
    flat_d = jax.device_put(flat)
    sid_d = jax.device_put(sid)
    key = jax.random.key(3, impl="rbg")

    for name, fn in (("full", gen_full),
                     ("no_compact", gen_no_compact),
                     ("direct", gen_direct),
                     ("direct_no_compact", gen_direct_no_compact),
                     ("searchsorted", gen_searchsorted)):
        t, r = timeit(jax.jit(fn), flat_d, sid_d, key)
        print(json.dumps({"variant": name, "s": round(t, 3),
                          "words_per_s_M": round(P / t / 1e6, 1)}),
              flush=True)
        if name == "searchsorted":
            # parity vs full: same pair MULTISET per position prefix
            c_f, x_f, n_f = jax.jit(gen_full)(flat_d, sid_d, key)
            c_s, x_s, w_s = r
            n_s = int(np.asarray(w_s, np.int64).sum())
            print(json.dumps({"pairs_full": int(n_f),
                              "pairs_ss": n_s}), flush=True)
            a = np.stack([np.asarray(c_f[:int(n_f)]),
                          np.asarray(x_f[:int(n_f)])], 1)
            mask = np.asarray(w_s) > 0
            bq = np.stack([np.asarray(c_s)[mask],
                           np.asarray(x_s)[mask]], 1)
            same = (a.shape == bq.shape) and bool(
                (np.sort(a.view("i8").ravel())
                 == np.sort(bq.view("i8").ravel())).all())
            print(json.dumps({"pair_multiset_equal": same}), flush=True)


if __name__ == "__main__":
    main()
