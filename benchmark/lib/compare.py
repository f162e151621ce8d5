"""The comparison that decides `correct`: the timed path's readings against
the plain reference's, each number beside a limit of its own (the cell's file
holds the limits; PERF.md section 2 gives the readings they were set from)."""

from __future__ import annotations

import statistics

import numpy as np


def training(prog, ref):
    """prog, ref: {"loss": [per step], "grad": {leaf: norm of the first
    gradient}, "change": {leaf: norm of the parameters' change}, "g1": the
    first gradient itself, as numpy arrays}.

    Norms are compared by the worst leaf: the gap between the two norms (not
    the norm of a difference), against the reference's norm of that leaf or
    of the median leaf, whichever is larger. Leaves whose gradient is nought
    to rounding in the reference (under a thousandth of the median leaf's)
    move under Adam by round-off alone and are left out of the change."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    med_g = statistics.median(ref["grad"].values())
    grad = {k: abs(prog["grad"][k] - g) / max(g, med_g)
            for k, g in ref["grad"].items()}
    moved = [k for k, g in ref["grad"].items() if g >= 1e-3 * med_g]
    med_c = statistics.median(ref["change"][k] for k in moved)
    change = {k: abs(prog["change"][k] - ref["change"][k])
              / max(ref["change"][k], med_c) for k in moved}
    worst_g, worst_c = max(grad, key=grad.get), max(change, key=change.get)
    # The norms above hardly feel a lower precision: rounding leaves a
    # norm where it was. The first gradient's distance from the reference's
    # does (PERF.md section 2), over all leaves at once, so that no small
    # leaf's noise decides it.
    import jax

    pairs = list(zip(jax.tree_util.tree_leaves(prog["g1"]),
                     jax.tree_util.tree_leaves(ref["g1"])))
    dist = sum(float(np.sum(np.square(a - b, dtype=np.float64)))
               for a, b in pairs)
    size = sum(float(np.sum(np.square(b, dtype=np.float64)))
               for _, b in pairs)
    return ({"loss_gap": loss_gap, "grad_norm_gap": grad[worst_g],
             "update_norm_gap": change[worst_c],
             "grad_distance": float(np.sqrt(dist / size))},
            {"grad_worst_leaf": worst_g, "update_worst_leaf": worst_c,
             "leaves_left_out": sorted(set(ref["grad"]) - set(moved))})


def serving(ref_logits, served, first, counts):
    """ref_logits [N,T,V] float32 from the reference over each sampled
    request's prompt and served tokens; served [N,T] the token the timed path
    produced after each position (anything elsewhere); first [N] the position
    that produced the request's first token, counts [N] its served tokens.

    The gap of a served token is how far its reference logit lies below the
    reference's best at that position: 0 where the two agree on the token."""
    import jax.numpy as jnp

    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(served)[..., None],
                              axis=-1)[..., 0]
    pos = np.arange(ref_logits.shape[1])[None, :]
    mask = (pos >= np.asarray(first)[:, None]) \
        & (pos < (np.asarray(first) + np.asarray(counts))[:, None])
    gaps = np.asarray(best - got)[mask]
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}, \
        {"tokens_compared": int(mask.sum()),
         "tokens_off_best": int((gaps > 0).sum())}


def verdict(numbers, limits):
    """[(name, value, limit, ok)] for every number the cell's file limits; a
    limited number that was not produced is not ok."""
    rows = []
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        rows.append((name, value, limit, bool(ok)))
    return rows
