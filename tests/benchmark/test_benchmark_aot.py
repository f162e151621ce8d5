"""The serving cell's token step at its real sizes, compiled for a described
v5e chip (nothing runs, nothing is measured): what the compiler says the step
needs has to fit the chip and be no toy. The three figures are printed, not
pinned: a change that donates the pool or drops its copies makes them smaller
and is welcome to. The topology is described inside the fixture, as
`on-chip-measurement` section 2 sets out, and only this file does so."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_decode_step_fits_one_chip(topo, no_cache):
    from benchmark.tools import aot_memory

    _, config = aot_memory.load("bert-base-decoder.closed-64")
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        hbm = json.load(f)["TPU v5 lite"]["hbm_bytes"]
    m = aot_memory.analysis(aot_memory.decode_step(config, topo.devices[0]))
    print(json.dumps(m))
    total = (m["argument_bytes"] + m["output_bytes"] - m["alias_bytes"]
             + m["temp_bytes"])
    # the driver's floor for a cell whose chip is busy: an eighth of the chip
    assert 0.125 * hbm < total < hbm, m
