"""The loop over the live latent pages against its roofline: the least time
(every live row of the pool once a layer, `kv_rank + rope_dim` numbers, against
the rows' score and value FLOPs for all heads) over the device time a launch
spends in the loop. The loop is told by name: `live_page_attention` is the
step's only `lax.fori_loop`, one a layer, and the trace has an event named
`while...` around each loop's body (`lib/trace.py`); nothing else in the step
lowers to a `while`. A page is read whole, so rows past a slot's position count
against the loop, not for it."""
from benchmark.lib import arith, arith_mla, readers_lm


def read(r):
    c = r["counters"]
    dev_s = readers_lm.kernel_seconds_per_step(r, ("while",))
    if dev_s is None or not c.get("live_slots"):
        return None
    pub = r["config"]["published"]
    rows = c["live_slots"] * (c["mean_context"] + 1) \
        * len(r["config"]["model"]["layer_kinds"])
    least, _ = arith.roofline_seconds(
        rows * arith_mla.attend_flops_per_row(pub),
        rows * arith_mla.latent_row_bytes(pub, c["kv_itemsize"]), r["peak"])
    return 100.0 * least / dev_s
