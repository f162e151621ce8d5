"""Readings for the limits of `correct`: the program, the control and the
planted faults, each against the plain reference, over several seeds in one
process. Nothing here is run by the benchmark's own runs.

    python benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        --what program,control[,rows:8] [--seconds 25] [--out file.jsonl]

`program` is the timed path. `control` is the reference in the nearest
precision below the configuration's: float8_e4m3 matmul operands (one scale a
tensor) under bfloat16 activations. `bf16` is the reference in bfloat16, a
second witness: what the training configuration states, and as near to what
the TPU's default float32 matmul does in the serving one as a plain
reference gets.
`rows:N` (training) is the reference with only the first N rows of each batch
kept: half the batch left out, or on four chips the exchange left out."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def train(ctx, driver, what):
    from benchmark.lib import compare

    out = {}
    if "program" in what:
        st = driver.setup(ctx)
        program, sizes = st.program, st.sizes
        driver.free(st)
    else:
        sizes = driver._sizes(ctx.config)
    ref = driver.reference_readings(ctx, sizes)
    for w in what:
        if w == "program":
            got = program
        elif w == "control":
            got = driver.reference_readings(ctx, sizes, mode="fp8")
        elif w == "bf16":
            got = driver.reference_readings(ctx, sizes, mode="bf16")
        elif w.startswith("rows:"):
            got = driver.reference_readings(ctx, sizes,
                                            keep_rows=int(w[5:]))
        else:
            raise SystemExit(f"unknown reading {w!r}")
        numbers, notes = compare.training(got, ref)
        out[w] = dict(numbers, loss=got["loss"], **notes)
        got = None
    out["reference_loss"] = ref["loss"]
    return out


def serve(ctx, driver, what):
    st = driver.setup(ctx)
    win = driver.measure(ctx, st)
    sizes, requests = st.sizes, st.requests
    chosen = driver.sample(ctx, st)
    driver.free(st)
    out = {"end_to_end": win["end_to_end"], "finished": len(chosen)}
    modes = {"program": None, "control": "fp8", "bf16": "bf16_all"}
    for w in what:
        numbers, notes = driver.reference_gaps(ctx, sizes, requests, chosen,
                                               control=modes[w])
        out[w] = dict(numbers, **notes)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out")
    args = ap.parse_args()

    import importlib

    from benchmark import run

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    peaks = run.load_json(run.HERE, "peaks.json")
    _, cell, config = run.load_cell(ROOT, manifest, args.workload)
    # the control and the faults are the reference alone: one chip does
    needs = cell if "program" in args.what.split(",") else dict(cell, chips=1)
    devices = run.devices_or_refuse(needs, peaks)
    run.enable_compile_cache()
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])
    reader = train if cell["driver"] == "train" else serve
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(cell, config, seed, args.seconds, 0, devices)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           **reader(ctx, driver, args.what.split(","))})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
