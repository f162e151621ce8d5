"""The residual path of several streams in a token step against its roofline:
a sublayer's maps' leaves once and the live slots' streams read once and
written once, every sublayer (`lib/arith_hc.py:mix_bytes`: bandwidth-bound, a
FLOP to four bytes), over the HBM bandwidth, against the device time a launch
spends in the operations that compute the maps and mix the streams, whatever
implements them.

The operations are found by the names the compiler gave them, because a name,
a start and a duration are what `lib/trace.py:load` keeps of an event. The
profiler's file does carry the program's scope (`hc.maps`, `hc.mix`) with
every operation, as the `tf_op` of its metadata, and `trace.load` drops it: a
reader that selected by scope would need that file's edit (PERF.md, Open
questions). What the names are, a sublayer, in the order the launch runs them
(my chip run, PR 39, seed 3900040013: one launch operation by operation, each
with its operands from the HLO text; slots 128, n 4, hidden 3584):

- the write-back: `slice_bitcast_fusion f32[slots]` (a column of the maps),
  two `add_bitcast_fusion bf16[slots,1,hidden]` (the mix `H_res X + H_post
  y`, two streams each, 2.4 us), then **`multiply_reduce_fusion f32[slots]`
  with the result `(f32[slots], bf16[slots,n,hidden])`: the four mixed
  streams put together, fused with the mean square that the next sublayer's
  maps start from** (2.7 us), and `copy bf16[slots,n,hidden]` (the same laid
  out as the next sublayer reads them, 3.8 us);
- the maps: `add_rsqrt_fusion f32[slots]`, `fusion f32[2n+n*n,slots]` (the
  product with `phi`, scaled and biased, 2.0 us) and the kernel `stream_maps`
  (`kernels/stream_maps.py`: sigmoids, clamp, `exp` and the Sinkhorn
  iterations in one call, 1.1 us);
- the read-out: `slice_bitcast_fusion f32[slots]` and `convert_element_type
  f32[slots,n,hidden]` (the streams widened to float32, 2-4 us). The sum `sum_i
  H_pre[i] X_i` itself runs in a `fusion f32[slots]` with the sublayer's norm
  statistic (1.2 us, 0.094 ms a launch) and again inside the sublayer's first
  product: **not counted**, the name is any reduction to a number a slot.

**Two of the names are another layer's too, and the share errs low by it.**
`multiply_reduce_fusion f32[slots]` is 160 operations and 0.551 ms a launch:
80 are this path's (0.206 ms), 40 are the latent projection's product with
`wq_a` fused with its norm's statistic (operands `bf16[hidden,768]`,
`bf16[slots,hidden]`: 0.321 ms) and 40 the latent row's statistic (0.025 ms).
`add_rsqrt_fusion f32[slots]` is 241 operations and 0.005 ms, 80 of them this
path's. So of the 1.727 ms the names add up to, 0.345 ms is the latent
projections' and the path's own operations take 1.38 ms, 1.48 ms with the
uncounted read-out: the share read 49.5% where the path's own time gives 58%.
Names alone cannot tell the two apart, and a re-fusion renames any of them:
the metric then shifts or falls silent, as `serve.latent_attention_roofline`
and `serve.expert_matmul_roofline` did. A kernel that takes more of the path
over keeps reporting here under `stream_maps`, alone under its name."""
from benchmark.lib import arith_hc, readers_lm


def ops(config):
    pub, slots = config["published"], config["engine"]["max_slots"]
    n, hidden = pub["hc_mult"], pub["hidden_size"]
    return ("stream_maps",
            "convert_element_type f32[%d,%d,%d]" % (slots, n, hidden),
            "multiply_reduce_fusion f32[%d]" % slots,
            "add_rsqrt_fusion f32[%d]" % slots,
            "fusion f32[%d,%d]" % (arith_hc.map_columns(pub), slots),
            "slice_bitcast_fusion f32[%d]" % slots,
            "add_bitcast_fusion bf16[%d,1,%d]" % (slots, hidden),
            "copy bf16[%d,%d,%d]" % (slots, n, hidden))


def read(r):
    c = r["counters"]
    dev_s = readers_lm.kernel_seconds_per_step(r, ops(r["config"]))
    if dev_s is None or not c.get("live_slots"):
        return None
    nbytes = arith_hc.mix_bytes(
        r["config"]["published"], r["config"]["model"],
        int(round(c["live_slots"])), c["kv_itemsize"])
    return 100.0 * nbytes / r["peak"]["hbm_bytes_per_s"] / dev_s
