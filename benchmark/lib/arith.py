"""The yardstick's arithmetic: operations and bytes from shapes, and the
window statistics. Nothing here touches a device or the program.

`bert_train_flops_per_step` is a copy of `bench.py:bert_train_flops_per_step`
(PERF.md, Open questions: the original is for a later PR to delete)."""

from __future__ import annotations

import math


def bert_train_flops_per_step(hidden, ffn, layers, vocab, batch, seq,
                              n_masked):
    """Model FLOPs of one fwd+bwd step (bwd = 2x fwd, recomputation not
    counted). Per token and layer the matmuls cost 2*h*3h (QKV) + 2*h*h
    (attention out) + 2*2*h*f (FFN pair); attention adds 2*2*T*h per token
    (QK^T and PV). The tied head scores only the n_masked gathered
    positions of each row, as the step does."""
    tokens = batch * seq
    fwd = tokens * layers * (2 * hidden * 3 * hidden + 2 * hidden * hidden
                             + 4 * hidden * ffn)
    fwd += tokens * layers * (4 * seq * hidden)
    fwd += batch * n_masked * 2 * hidden * vocab
    return 3 * fwd


def mlm_max_preds(seq):
    """Masked slots per row, as `models/bert.py:mlm_max_preds` fixes them."""
    return max(1, int(0.15 * seq) + 1)


def decoder_flops_per_position(hidden, ffn, layers, vocab, context):
    """Model FLOPs to advance one sequence by one position with `context`
    positions visible (its own included): 2 x the layer's matmul weights,
    4*context*hidden for QK^T and PV, and the tied head."""
    per_layer = 2 * (3 * hidden * hidden + hidden * hidden
                     + 2 * hidden * ffn) + 4 * context * hidden
    return layers * per_layer + 2 * hidden * vocab


def decoder_weight_bytes(hidden, ffn, layers, vocab, itemsize):
    """Bytes of the weights one token step has to read once: every layer's
    matrices and biases and the tied embedding (read whole by the head)."""
    per_layer = (3 * hidden * hidden + 3 * hidden + hidden * hidden + hidden
                 + 2 * hidden * ffn + ffn + hidden + 4 * hidden)
    return itemsize * (layers * per_layer + vocab * hidden + vocab)


def decoder_step_bytes(hidden, ffn, layers, vocab, contexts, w_itemsize,
                       kv_itemsize):
    """Least HBM traffic of one token step over the live slots: the weights
    once, each live context's K and V once, and one K and one V row written
    per live slot and layer. Whatever implements the step has to move this
    much; copies of the pool are not in it."""
    live = [c for c in contexts if c > 0]
    kv_read = sum(live) * layers * 2 * hidden * kv_itemsize
    kv_write = len(live) * layers * 2 * hidden * kv_itemsize
    return (decoder_weight_bytes(hidden, ffn, layers, vocab, w_itemsize)
            + kv_read + kv_write)


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take, and which of the two bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def percentile(values, q):
    """q in [0, 100], linear interpolation between order statistics (numpy's
    default). None for no values: a reader then reports nothing."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def rate(count, t0, t1):
    """All the work over all the time of the window."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    return count / (t1 - t0)


def request_stats(requests, t0, t1):
    """Window statistics over every request, from the caller's own stamps.

    requests: dicts with `t_submit` and `stamps` (the time each token
    reached the caller). Returns the tokens that arrived inside [t0, t1],
    the time to first token of every request whose first token arrived
    inside it, and every gap between consecutive tokens of one request
    that closed inside it."""
    tokens, ttft, gaps = 0, [], []
    for r in requests:
        st = r["stamps"]
        tokens += sum(1 for t in st if t0 <= t <= t1)
        if st and t0 <= st[0] <= t1:
            ttft.append(st[0] - r["t_submit"])
        gaps.extend(b - a for a, b in zip(st, st[1:]) if t0 <= b <= t1)
    return tokens, ttft, gaps
