"""What the causal-LM cells' kernel readers share: the device time, a step, of
the operations whose short name (`lib/trace.py:short_name`: the instruction's
name without its number, and the shape it yields) starts with a prefix.

The rule by which a reader tells a kernel's launches: XLA names a Pallas or
Mosaic custom call after the kernel. The splash attention kernels are
`splash_mqa_fwd_*`, `splash_mqa_dkv_*`, `splash_mqa_dq_*`; the grouped
products `jax.lax.ragged_dot` lowers to are `ragged-dot-*` (with a small
`ragged-dot-metadata` before each). A short name does not say which layer
launched a kernel, nor which of an expert's three products one is: a full and
a sliding layer's attention, and gate, up and down, are not told apart."""

from benchmark.lib import trace


def kernel_seconds_per_step(r, prefixes):
    """Device seconds a step spends in the operations named by `prefixes`,
    over the launches of the step executable that lie wholly inside the
    traced part of the first device; None where there is no launch or no
    such operation (a program without the kernel)."""
    t = r["trace"]
    dev = t["devices"][t["used"][0]]
    runs = trace.launches(dev["modules"], r["counters"]["step_executable"],
                          t["t0"], t["t1"])
    if not runs:
        return None
    total, found = 0.0, False
    for _, s, d in runs:
        for name, os_, od in dev["ops"]:
            if os_ >= s and os_ + od <= s + d and name.startswith(prefixes):
                total, found = total + od, True
    return total / len(runs) if found else None
