"""Rehearsal without the chip: compile a cell's step executable at its real
sizes for a described v5e (`on-chip-measurement` section 2.3) and print what
the compiler says it needs on each device. Nothing runs; not a measurement.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_memory.py <cell>

The decode step is also compiled by tests/benchmark/test_benchmark_aot.py;
the BERT-large train step takes about a minute here, so it is only here."""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def load(cell_name):
    from benchmark import run

    _, cell, config = run.load_cell(
        ROOT, run.load_json(ROOT, "BENCHMARK.json"), cell_name)
    return cell, config


def analysis(compiled):
    m = compiled.memory_analysis()
    return {"argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes}


def train_step(cell, config, devices):
    """`BertTrainer`'s step as `_build()` jits it, lowered on shapes. The
    trainer's constructor puts its weights on the mesh's devices and a
    described chip takes none, so this reaches past it to `_step_math`
    (PERF.md, Open questions); nothing but this rehearsal depends on that."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.models.bert import (BertConfig, BertTrainer,
                                                init_params, mlm_max_preds,
                                                param_specs)
    from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, MeshConfig,
                                                  spec_for)

    m, tr = config["model"], cell["traffic"]
    cfg = BertConfig(vocab_size=m["vocab_size"], hidden=m["hidden"],
                     num_layers=m["num_layers"], num_heads=m["num_heads"],
                     ffn=m["ffn"], max_len=m["max_len"], dropout=m["dropout"])
    mesh = MeshConfig(data=cell["chips"],
                      devices=devices[:cell["chips"]]).build()
    t = object.__new__(BertTrainer)     # no device_put: there is no device
    t.cfg, t.mesh, t.lr = cfg, mesh, m["lr"]
    repl = NamedSharding(mesh, P())
    p_sh = jax.tree_util.tree_map(lambda s: repl, param_specs(cfg),
                                  is_leaf=lambda x: isinstance(x, P))
    rows = NamedSharding(mesh, spec_for(mesh, DATA_AXIS))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    with_sh = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sh)
    params = with_sh(shapes, p_sh)
    opt = {"m": params, "v": params}
    b, s, mp = tr["rows"], tr["seq"], mlm_max_preds(tr["seq"])
    arg = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    key = jax.eval_shape(lambda: jax.random.key(1, impl="rbg"))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl)
    fn = jax.jit(t._step_math, donate_argnums=(0, 1),
                 out_shardings=(repl, p_sh, {"m": p_sh, "v": p_sh}))
    return fn.lower(params, opt, arg((b, s), jnp.int32, rows),
                    arg((b, mp), jnp.int32, rows),
                    arg((b, mp), jnp.int32, rows),
                    arg((b, mp), jnp.float32, rows), key,
                    arg((), jnp.int32, repl)).compile()


def decode_step(config, device):
    """The token step the engine drives, `TransformerDecodeModel.step`, lowered
    on shapes: the model comes from its own constructor, as the driver builds
    it, over weights that are shapes alone (a zero broadcast to each leaf's
    shape holds no memory), and `step` is traced with the weights as
    arguments, which is what it passes to its executable."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from deeplearning4j_tpu.models.bert import BertConfig, init_params
    from deeplearning4j_tpu.serving import TransformerDecodeModel

    m, e = config["model"], config["engine"]
    cfg = BertConfig(vocab_size=m["vocab_size"], hidden=m["hidden"],
                     num_layers=m["num_layers"], num_heads=m["num_heads"],
                     ffn=m["ffn"], max_len=m["max_len"])
    one = SingleDeviceSharding(device)
    on = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    model = TransformerDecodeModel(
        jax.tree_util.tree_map(
            lambda a: np.broadcast_to(np.zeros((), a.dtype), a.shape), shapes),
        n_heads=m["num_heads"], max_slots=e["max_slots"], page=e["page"],
        max_pages_per_slot=e["max_pages_per_slot"], eps=m["layer_norm_eps"])

    def step(params, state, tokens, pos, table):
        model.params = params
        return model.step(state, tokens, pos, table)

    s = e["max_slots"]
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    return jax.jit(step).lower(
        on(shapes), on(jax.eval_shape(model.init_state)), ints(s), ints(s),
        ints(s, e["max_pages_per_slot"])).compile()


def main():
    import jax
    from jax.experimental import topologies

    cell, config = load(sys.argv[1])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell["driver"] == "train":
        compiled = train_step(cell, config, list(topo.devices))
    else:
        compiled = decode_step(config, topo.devices[0])
    out = analysis(compiled)
    out["collectives"] = sorted(
        {w for w in ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")
         if w in compiled.as_text()})
    print(json.dumps({sys.argv[1]: out}))
    del jax


if __name__ == "__main__":
    main()
