"""Readings for the limits of `correct` in a serving cell whose driver brings
`setup`, `measure`, `sample`, `free` and `reference_gaps(..., control=mode)`
(the cell's file names the driver; `serve_closed_hc` by default's cell): the
program, the control, the reference's witness and a planted fault, each
against the plain reference, over several seeds in one process. Nothing here is run by the
benchmark's own runs.

    python benchmark/tools/calibrate_hc.py --seeds 1,2,3 \
        [--workload xing4-29b.closed-128] [--what program,control,bf16,slot] \
        [--seconds 25] [--out f.jsonl]

`program` is the timed path. `control` is the reference in the nearest
precision below the configuration's: float8_e4m3 matmul operands (one scale a
tensor) under bfloat16 activations: it has to come out not correct. `bf16` is
the reference in bfloat16, a witness: what the configuration states. `slot`
is the program's own tokens with one of each sampled request's replaced by
another request's (twelve positions of some 3,000, which the mean gap cannot
see): it has to come out not correct by the widest gap. Each
reading goes through `compare.verdict` under the cell's limits."""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MODES = {"program": None, "control": "fp8", "bf16": "bf16", "slot": "slot"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="xing4-29b.closed-128")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out")
    args = ap.parse_args()

    from benchmark import run
    from benchmark.lib import compare

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    peaks = run.load_json(run.HERE, "peaks.json")
    _, cell, config = run.load_cell(ROOT, manifest, args.workload)
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])
    devices = run.devices_or_refuse(cell, peaks)
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(cell, config, seed, args.seconds, 0, devices)
        st = driver.setup(ctx)
        win = driver.measure(ctx, st)
        sizes, requests = st.sizes, st.requests
        chosen = driver.sample(ctx, st)
        driver.free(st)
        out = {"workload": args.workload, "seed": seed,
               "end_to_end": win["end_to_end"], "finished": len(chosen)}
        for w in args.what.split(","):
            numbers, notes = driver.reference_gaps(
                ctx, sizes, requests, chosen, control=MODES[w])
            rows = compare.verdict(numbers, {
                k: v for k, v in cell["limits"].items() if k in numbers})
            out[w] = dict(numbers, **notes,
                          correct=all(ok for *_, ok in rows))
        line = json.dumps(out)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
