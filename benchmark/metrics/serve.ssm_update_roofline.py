"""The Mamba-2 state updates of a token step against their roofline: the least
time (every live slot's float32 state read once and written once a Mamba-2
layer, against 5 FLOPs a number of the state: the bytes bound it) over the
device time a launch spends in the update's operations.

How the trace names them (found on a traced run, PR 34): the compiler makes the
update of one layer ONE multi-output fusion that reads the state, the decay,
`dt x`, B and C, writes the new state and yields `y = S C` beside it, and the
trace names a fusion by its first output: `fusion f32[slots,heads,head_dim,
state]`, at the cell's sizes `fusion f32[128,128,64,128]`, five a launch. No
other operation of the step yields that shape (a state is an array a layer). A
program that keeps the state in one stacked array, or that splits the update,
would go by another name, and the reader would return nothing."""
from benchmark.lib import arith_hybrid, readers_lm


def ops(config):
    p = config["published"]
    return ("fusion f32[%d,%d,%d,%d]" % (
        config["engine"]["max_slots"], p["mamba_num_heads"],
        p["mamba_head_dim"], p["ssm_state_size"]),)


def read(r):
    c = r["counters"]
    dev_s = readers_lm.kernel_seconds_per_step(r, ops(r["config"]))
    if dev_s is None or not c.get("live_slots"):
        return None
    return 100.0 * arith_hybrid.update_least_seconds(
        r["config"]["published"], r["config"]["model"],
        int(round(c["live_slots"])), r["peak"]) / dev_s
