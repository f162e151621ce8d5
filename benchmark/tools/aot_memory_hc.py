"""What the token step of the latent-attention cell with a residual path of
several streams needs of a v5e chip, by an ahead-of-time compile for a
described chip (nothing runs, no chip needed): arguments, outputs, what is
aliased (the donated pool) and temporaries, whether the pool keeps one layout
through the step, and how many device operations the compiled step holds,
all of them and those of the residual path (by the `hc.maps` and `hc.mix`
scopes in their metadata).

    python benchmark/tools/aot_memory_hc.py [--workload xing4-29b.closed-128]
        [--layers N] [--loop] [--hlo FILE]

`--layers N` compiles the first N layers only (a look at one layer's
operations takes seconds; the whole depth minutes). The step is compiled as
the chip traces it, through the paged-attention kernel (this host's backend is
the CPU, so the tool steers `kernels._on_tpu`, as a scratch wrapper had to
for `aot_memory_mla.py`); `--loop` compiles the loop route instead."""

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# an instruction of the compiled module's entry computation that runs on the
# device as an operation of its own
OPERATION = re.compile(r"^  (?:ROOT )?%?[\w.\-]+ = .*? (fusion|custom-call|"
                       r"convolution|copy|while|sort|dynamic-update-slice|"
                       r"dynamic-slice|gather|scatter|reduce|transpose|"
                       r"convert)\(")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="xing4-29b.closed-128")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--loop", action="store_true")
    ap.add_argument("--hlo")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import run
    from benchmark.drivers import serve_closed_hc as driver
    from benchmark.reference import xing4_plain as plain
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.serving import LatentDecodeModel

    if not args.loop:
        kernels._on_tpu = lambda: True
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    _, _, config = run.load_cell(ROOT, manifest, args.workload)
    if args.layers:
        m = config["model"]
        config["model"] = dict(
            m, layer_ids=m["layer_ids"][:args.layers],
            layer_kinds=m["layer_kinds"][:args.layers])
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
    ma, text = compiled.memory_analysis(), compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    entry = text[text.index("\nENTRY "):]
    ops = [line for line in entry.splitlines() if OPERATION.match(line)]
    scoped = lambda scope: sum(  # noqa: E731
        1 for o in ops if f"/{scope}/" in o or f"/{scope}\"" in o)
    pool = "bf16[" + ",".join(str(n) for n in model._pool_shape()) + "]"
    layouts = sorted(set(re.findall(
        re.escape(pool) + r"\{([0-9,]*)", text)))
    print(json.dumps({
        "workload": args.workload, "layers": len(model.cfg.layers),
        "route": "loop" if args.loop else "kernel",
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "total_bytes": ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes,
        "pool": pool, "pool_layouts_minor_to_major": layouts,
        "device_operations": len(ops), "hc_maps_operations": scoped("hc.maps"),
        "hc_mix_operations": scoped("hc.mix")}))


if __name__ == "__main__":
    main()
