"""The yardstick's arithmetic against values worked out by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import arith  # noqa: E402


@pytest.mark.parametrize("sizes, expected", [
    # BERT-base 16x512: 8192 tokens x 12 layers x (3.54M + 1.18M + 9.44M)
    # + attention 8192 x 12 x 4x512x768 + head 16 x 77 x 2x768x30522, x3
    ((768, 3072, 12, 30522, 16, 512, 77), 4811839782912),
    # BERT-large 16x512
    ((1024, 4096, 24, 30522, 16, 512, 77), 16311391027200),
])
def test_train_flops(sizes, expected):
    h, f, L, v, b, t, m = sizes
    by_hand = 3 * (b * t * L * (6 * h * h + 2 * h * h + 4 * h * f)
                   + b * t * L * 4 * t * h + b * m * 2 * h * v)
    assert by_hand == expected
    assert arith.bert_train_flops_per_step(*sizes) == expected
    assert arith.mlm_max_preds(t) == m


def test_decoder_flops_and_bytes_bert_base():
    h, f, L, v = 768, 3072, 12, 30522
    # per layer 2 x (2.36M + 4.72M) matmul weights = 14,155,776 FLOPs, plus
    # 4 x 100 x 768 of attention; the head 2 x 768 x 30522
    assert arith.decoder_flops_per_position(h, f, L, v, 100) == \
        12 * (14155776 + 307200) + 46881792
    per_layer = 3 * h * h + 3 * h + h * h + h + 2 * h * f + f + h + 4 * h
    assert per_layer == 7087872
    w = 4 * (12 * per_layer + v * h + v)
    assert arith.decoder_weight_bytes(h, f, L, v, 4) == w == 434103528
    # two live slots at 100 and 50 positions and an empty one
    kv = (150 + 2) * 12 * 2 * 768 * 4
    assert arith.decoder_step_bytes(h, f, L, v, [100, 50, 0], 4, 4) == w + kv
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = arith.roofline_seconds(1e9, w + kv, peak)
    assert bound == "memory" and t == pytest.approx((w + kv) / 819e9)
    assert arith.roofline_seconds(197e12, 819e6, peak) == (1.0, "compute")


def test_percentile():
    assert arith.percentile([], 95) is None
    assert arith.percentile([3.0], 95) == 3.0
    assert arith.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert arith.percentile(list(range(101)), 95) == 95.0
    assert arith.percentile([0, 10], 95) == pytest.approx(9.5)


def window(stall):
    """Four requests of ten tokens, one every 0.1 s; with `stall`, every
    request waits one second between its fifth and sixth token."""
    reqs = []
    for r in range(4):
        t, stamps = 0.05 * r + 1.0, []
        for k in range(10):
            t += 0.1 + (stall if k == 5 else 0.0)
            stamps.append(t)
        reqs.append({"t_submit": 0.05 * r, "stamps": stamps})
    return reqs


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    t0, t1 = 0.0, 3.5
    tokens, ttft, gaps = arith.request_stats(window(0.0), t0, t1)
    assert tokens == 40 and len(ttft) == 4 and len(gaps) == 36
    assert arith.percentile(ttft, 95) == pytest.approx(1.1)
    s_tokens, s_ttft, s_gaps = arith.request_stats(window(1.0), t0, t1)
    assert len(s_gaps) == 36 and s_ttft == ttft
    assert arith.rate(s_tokens, t0, t1) == arith.rate(40, t0, t1)
    # a shorter window cuts the stalled requests' last tokens off
    assert arith.rate(arith.request_stats(window(1.0), 0.0, 2.5)[0], 0, 2.5) \
        < arith.rate(arith.request_stats(window(0.0), 0.0, 2.5)[0], 0, 2.5)
    assert arith.percentile(s_gaps, 95) > 5 * arith.percentile(gaps, 95)
    assert arith.percentile(s_gaps, 50) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        arith.rate(1, 2.0, 2.0)
