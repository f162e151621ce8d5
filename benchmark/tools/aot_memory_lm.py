"""Rehearsal without the chip, for a `train_lm` cell: compile the whole train
step at the cell's real sizes for a described v5e and print what the compiler
says it needs (as `tools/aot_memory.py` does for the BERT cells, whose
trainers it names). Nothing runs; not a measurement.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_memory_lm.py <cell> [hlo-out]

The program asks `jax.default_backend()` whether to take its TPU branch and
here that says `cpu`, so this script steers it (`causal_lm._on_tpu`), as
`on-chip-measurement` section 2 has a rehearsal do."""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def train_step(cell, config, devices):
    """`CausalLMTrainer`'s step as its engine jits it, lowered on shapes
    (the trainer's constructor places weights, and a described chip takes
    none)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.drivers import train_lm
    from deeplearning4j_tpu.models import causal_lm as lm
    from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, MeshConfig,
                                                  spec_for)

    lm._on_tpu = lambda: True
    cfg, tr = train_lm.build_config(config), cell["traffic"]
    mesh = MeshConfig(data=cell["chips"],
                      devices=devices[:cell["chips"]]).build()
    t = object.__new__(lm.CausalLMTrainer)    # no device_put: no device
    m = config["model"]
    t.cfg, t.mesh, t.lr, t.warmup_steps = cfg, mesh, m["lr"], m["warmup_steps"]
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        shapes)
    p_sh = jax.tree_util.tree_map(lambda a: repl, shapes)
    opt = {"m": params, "v": params}
    batch = jax.ShapeDtypeStruct((tr["rows"], tr["seq"]), jnp.int32,
                                 sharding=rows)
    fn = jax.jit(t._step_math, donate_argnums=(0, 1),
                 out_shardings=(repl, p_sh, {"m": p_sh, "v": p_sh},
                                (repl, repl)))
    return fn.lower(params, opt, batch, batch, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=repl)).compile()


def main():
    import time

    from jax.experimental import topologies

    from benchmark.tools import aot_memory

    cell, config = aot_memory.load(sys.argv[1])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    t0 = time.perf_counter()
    compiled = train_step(cell, config, list(topo.devices))
    out = aot_memory.analysis(compiled)
    out["total_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                          - out["alias_bytes"] + out["temp_bytes"])
    out["compile_seconds"] = round(time.perf_counter() - t0, 1)
    text = compiled.as_text()
    out["kernels"] = sorted({w for w in ("splash_mqa_fwd", "splash_mqa_dkv",
                                         "splash_mqa_dq", "ragged-dot")
                             if w in text})
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(json.dumps({sys.argv[1]: out}))


if __name__ == "__main__":
    main()
