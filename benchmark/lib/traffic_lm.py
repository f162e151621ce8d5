"""Next-token training batches, beside `traffic.py`'s generators and by their
rule: the cell's file gives the parameters, the seed gives the ids and never
the amount of work."""

from __future__ import annotations

import numpy as np


def lm_batches(traffic, vocab, seed):
    """Endless stream of (tokens [rows, seq] int32, labels [rows, seq] int32):
    each row one document of uniform ids in 3..vocab-1, the label of a
    position the next token and -100 at the last, each batch new."""
    rows, seq = traffic["rows"], traffic["seq"]
    rng = np.random.default_rng([int(seed), 3])
    while True:
        tokens = rng.integers(3, vocab, (rows, seq), dtype=np.int32)
        labels = np.full((rows, seq), -100, np.int32)
        labels[:, :-1] = tokens[:, 1:]
        yield tokens, labels
