"""The one traffic generator. A cell's file gives the parameters; the seed
gives the ids and the order, and never the amount of work: every seed gets the
same sizes in another order, so two seeds differ as two runs of one seed do."""

from __future__ import annotations

import statistics

import numpy as np


def mlm_batches(traffic, vocab, seed):
    """Endless stream of (tokens [rows, seq] int32, labels [rows, seq] int64
    with -100 where unmasked): uniform ids in 3..vocab-1, `mask_frac` of each
    row's positions masked with id 1, as `models/bert.py:synthetic_mlm_batch`
    makes them, each batch new."""
    rows, seq = traffic["rows"], traffic["seq"]
    n_mask = max(1, int(traffic["mask_frac"] * seq))
    rng = np.random.default_rng([int(seed), 1])
    while True:
        tokens = rng.integers(3, vocab, (rows, seq), dtype=np.int32)
        pos = np.argpartition(rng.random((rows, seq)), n_mask,
                              axis=1)[:, :n_mask]
        labels = np.full((rows, seq), -100, np.int64)
        np.put_along_axis(labels, pos, np.take_along_axis(tokens, pos, 1), 1)
        np.put_along_axis(tokens, pos, 1, 1)
        yield tokens, labels


def _lengths(spec, n):
    """n lengths at the evenly spaced quantiles of a clipped log-normal."""
    inv = statistics.NormalDist().inv_cdf
    raw = [spec["median"] * np.exp(spec["sigma"] * inv((i + 0.5) / n))
           for i in range(n)]
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def requests(traffic, seed):
    """Endless stream of requests as (prompt ids, answer length). Each run of
    `cycle` requests holds the same `cycle` pairs of lengths, paired once for
    all seeds, in an order the seed draws; the ids come from the seed."""
    cycle = traffic["cycle"]
    prompts, answers = _lengths(traffic["prompt"], cycle), \
        _lengths(traffic["answer"], cycle)
    answers = answers[np.random.default_rng(0).permutation(cycle)]
    rng = np.random.default_rng([int(seed), 2])
    lo, hi = traffic["ids"]
    while True:
        for i in rng.permutation(cycle):
            yield (rng.integers(lo, hi + 1, prompts[i]).tolist(),
                   int(answers[i]))
