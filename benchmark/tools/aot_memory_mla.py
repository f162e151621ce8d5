"""What the latent-attention serving cell's token step needs of a v5e chip, by
an ahead-of-time compile for a described chip (nothing runs, no chip needed):
arguments, outputs, what is aliased (the donated pool) and temporaries, and
whether the pool keeps one layout through the step (a copy of the whole pool
in another layout is what PERF.md's PR 27 and PR 32 entries are about).

    python benchmark/tools/aot_memory_mla.py [--workload deepseek-v3.closed-128]
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
    ap.add_argument("--workload", default="deepseek-v3.closed-128")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import run
    from benchmark.drivers import serve_closed_lm as driver
    from benchmark.reference import deepseek_v3_plain as plain
    from deeplearning4j_tpu.serving import LatentDecodeModel

    manifest = run.load_json(ROOT, "BENCHMARK.json")
    _, _, config = run.load_cell(ROOT, manifest, args.workload)
    eng = config["engine"]
    weights = jax.eval_shape(
        lambda: plain.draw_params(0, driver.reference_sizes(config)))
    model = LatentDecodeModel(
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
    compiled = jax.jit(model._fn, donate_argnums=model.state_donation).lower(
        on_chip(model.params), on_chip(jax.eval_shape(model.init_state)),
        slots, slots, table).compile()
    ma = compiled.memory_analysis()
    pool = "bf16[" + ",".join(str(n) for n in model._pool_shape()) + "]"
    layouts = sorted(set(re.findall(
        re.escape(pool) + r"\{([0-9,]*)", compiled.as_text())))
    print(json.dumps({
        "workload": args.workload,
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "total_bytes": ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes,
        "pool": pool, "pool_layouts_minor_to_major": layouts}))


if __name__ == "__main__":
    main()
