"""Readings for the limits of `correct` in a `train_lm` cell, as
`tools/calibrate.py` takes them for the BERT cells (which names their two
drivers): the program, the witness, the control and the planted faults, each
against the plain reference, over several seeds in one process. Nothing here
is run by the benchmark's own runs.

    python benchmark/tools/calibrate_lm.py --workload <cell> --seeds 1,2,3 \
        --what program,bf16,control[,no_window,drop_expert,keep_rows] \
        [--out file.jsonl]

`program` is the timed path's first three steps. `bf16` is the reference in
bfloat16, what the configuration states: a witness. `control` is the
reference with float8_e4m3 matmul operands under bfloat16 activations, the
nearest precision below: it has to fail at least one limit. `no_window`,
`drop_expert` and `keep_rows` are the reference with a fault planted (a
sliding layer attends as a full one; the last held expert of every sparse
layer is left out; only half of every batch's rows is trained on). Each
reading goes through the harness's own comparison under the cell's limits:
`correct` says what a run that read those numbers would have been called,
`failed` which limits said no."""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

READINGS = {"bf16": {"mode": "bf16"}, "control": {"mode": "fp8"},
            "no_window": {"fault": "no_window"},
            "drop_expert": {"fault": "drop_expert"},
            "keep_rows": {"fault": "keep_rows"}}


def readings(ctx, driver, what):
    from benchmark.lib import compare

    st = driver.setup(ctx)
    program = st.program
    driver.free(st)
    t0 = time.perf_counter()
    ref = driver.reference_readings(ctx, program)
    out = {"reference_loss": ref["loss"],
           "reference_seconds": time.perf_counter() - t0}
    for w in what:
        got = program if w == "program" else driver.reference_readings(
            ctx, program, **READINGS[w])
        numbers, notes = driver.compare_with(got, ref)
        # what the program's own counters and the window add, at their best
        rows = compare.verdict(dict(numbers, moe_dropped=0,
                                    window_compiles=0), ctx.cell["limits"])
        out[w] = dict(numbers, loss=got["loss"],
                      correct=all(ok for *_, ok in rows),
                      failed=[name for name, *_, ok in rows if not ok],
                      **notes)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,bf16,control")
    ap.add_argument("--out")
    args = ap.parse_args()

    from benchmark import run

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    peaks = run.load_json(run.HERE, "peaks.json")
    _, cell, config = run.load_cell(ROOT, manifest, args.workload)
    devices = run.devices_or_refuse(cell, peaks)
    run.enable_compile_cache()
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(cell, config, seed, 0.0, 0, devices)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           **readings(ctx, driver, args.what.split(","))})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
