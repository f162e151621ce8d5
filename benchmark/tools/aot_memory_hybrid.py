"""What the hybrid state-space serving cell's token step needs of a v5e chip, by
an ahead-of-time compile for a described chip (nothing runs, no chip needed):
arguments, outputs, what is aliased (the donated pages and slot state) and
temporaries, the three parts of what the cell holds at rest (weights, state by
slot, pages), and whether the Mamba-2 state keeps one layout through the step
(a copy of the whole state in another layout is what PERF.md's PR 27 and PR 32
entries are about).

    python benchmark/tools/aot_memory_hybrid.py [--workload nemotron3-super.closed-128] [--hlo file]
"""

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="nemotron3-super.closed-128")
    ap.add_argument("--hlo", help="write the compiled step's HLO text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import run
    from benchmark.drivers import serve_closed_hybrid as driver
    from benchmark.reference import nemotron_h_plain as plain
    from deeplearning4j_tpu.serving import HybridDecodeModel
    from deeplearning4j_tpu.telemetry import memledger

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    _, _, config = run.load_cell(ROOT, manifest, args.workload)
    eng = config["engine"]
    weights = jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(config)))
    model = HybridDecodeModel(
        driver.to_program(weights), driver.program_config(config),
        max_slots=eng["max_slots"], page=eng["page"],
        max_pages_per_slot=eng["max_pages_per_slot"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    slots = jax.ShapeDtypeStruct((eng["max_slots"],), jnp.int32, sharding=one)
    table = jax.ShapeDtypeStruct(
        (eng["max_slots"], eng["max_pages_per_slot"]), jnp.int32,
        sharding=one)
    state = jax.eval_shape(model.init_state)
    compiled = jax.jit(model._fn, donate_argnums=model.state_donation).lower(
        on_chip(model.params), on_chip(state), slots, slots,
        table).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    ssm = "f32[" + ",".join(str(n) for n in state["ssm"][0].shape) + "]"
    print(json.dumps({
        "workload": args.workload,
        "weight_bytes": memledger.tree_bytes(model.params),
        "slot_state_bytes": model.slot_state_bytes(),
        "page_bytes": memledger.tree_bytes(state) - model.slot_state_bytes(),
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "total_bytes": ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes,
        "ssm_state": ssm,
        "ssm_state_layouts_minor_to_major": sorted(set(re.findall(
            re.escape(ssm) + r"\{([0-9,]*)", text)))}))


if __name__ == "__main__":
    main()
