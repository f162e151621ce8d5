"""Test configuration: force an 8-virtual-device CPU platform so mesh /
sharding tests run without TPU hardware (SURVEY.md §4 "distributed without a
cluster": the reference simulates multi-node in-process over Aeron loopback;
our equivalent is XLA's forced host platform device count).

jax settings go through jax.config.update, which holds whether or not jax
was imported before this file. XLA_FLAGS is read lazily at first backend
init, so setting it here works as long as no test touched a device yet.

The persistent compilation cache is deliberately NOT enabled here
(runtime.RuntimeConfig.enable_compile_cache is for entry points): tier-1
tests count backend compiles.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Run the suite on the virtual CPU mesh, never on a chip.
jax.config.update("jax_platforms", "cpu")
# This jax build's default matmul precision truncates operands to bfloat16
# (fine for the MXU perf path; fatal for numeric gradient checks) — force
# full fp32 matmuls in tests (SURVEY.md §7 "Numerics").
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def lock_witness(request):
    """Runtime half of the dl4jlint lock-order rule (ISSUE 7): under
    the slow multi-thread tests (serving soak, resilience, parallel
    ETL), package-created threading.Lock/RLock are replaced with
    instrumented wrappers that record ACTUAL acquisition orders; the
    test fails on any witnessed inversion — the deadlock orders the
    static rule's call-graph resolution cannot see. Quick-mode tests
    are untouched (no monkeypatching on the tier-1 path)."""
    if request.node.get_closest_marker("slow") is None:
        yield None
        return
    from deeplearning4j_tpu.analysis import witness

    w = witness.install()
    try:
        yield w
    finally:
        witness.uninstall()
    assert not w.inversions, (
        "lock-order inversion witnessed at runtime:\n"
        + w.format_inversions())


@pytest.fixture
def assert_rows_close():
    """Two programs of different shape or placement (another bucket, a
    replica's clone, a mesh) that compute the same rows: compared at a
    float32 tolerance, not bit for bit (ROADMAP D4).

    XLA picks a matmul's tiling and a reduction's order per shape and per
    layout, so the two sides may round differently in the last place or
    two; observed here at most 2.4e-7 absolute and 1.9e-6 relative (1-8
    float32 ulps). The bound is 1e-5 relative (some 5x the widest seen,
    and 84 ulps: a wrong row, weight or bucket misses by orders more) with
    an absolute floor of 1e-6 for entries near zero. Where both sides run
    the SAME executable (kill-and-resume, capture replay, a slot reused)
    tests keep `assert_array_equal`."""
    import numpy as np

    def check(actual, expected):
        np.testing.assert_allclose(np.asarray(actual), np.asarray(expected),
                                   rtol=1e-5, atol=1e-6)

    return check
