"""The decode engine's dispatch intervals over its whole life (ISSUE 36): the
time from one token-step dispatch to the next where the engine did not idle in
between, the longest one split by what the engine's thread did in it, and what
of an interval lay under none of the five phase spans. A toy engine whose step
sleeps once (`test_decode_phases.py`'s model)."""

import os
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.serving.decode import (DecodeEngine,
                                               TransformerDecodeModel)
from deeplearning4j_tpu.telemetry import registry as registry_mod
from deeplearning4j_tpu.telemetry.registry import (DECODE_PHASES,
                                                   LONGEST_PARTS,
                                                   DispatchAccount,
                                                   MetricsRegistry,
                                                   ServingInstruments)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib.program_spans import sample_sum  # noqa: E402

STALL = 0.5         # the planted sleep, seconds
IDLE = 1.0          # the engine idles this long between the two bursts
BURSTS = [(7, 9), (5, 11)]      # one request a burst: prompt length, max_new


def _model(seed=0):
    return TransformerDecodeModel.init(
        vocab=32, hidden=16, n_layers=1, n_heads=2, max_len=64, seed=seed,
        max_slots=2, page=8, max_pages_per_slot=4)


def _sample(snap, name, **labels):
    return sample_sum(snap, name, **labels) or 0.0


class Recorded:
    """A histogram child that also keeps what it was given."""

    def __init__(self, inner):
        self.inner, self.values = inner, []

    def observe(self, value, exemplar=None):
        self.values.append(value)
        self.inner.observe(value)


@pytest.fixture(scope="module", params=["step", "chunk"])
def stalled(request):
    """Two bursts of one request each with an idle second between them; the
    model's step sleeps once, in the middle of the second burst."""
    reg = MetricsRegistry()
    prev = telemetry.set_registry(reg)
    telemetry.enable()
    frozen = registry_mod._startup["frozen"]
    registry_mod._startup["frozen"] = False
    model = _model()
    plant = {"at": None, "calls": 0}
    inner = model.step

    def step(state, tokens, pos, table, site=None):
        plant["calls"] += 1
        if plant["calls"] == plant["at"]:
            time.sleep(STALL)
        return inner(state, tokens, pos, table, site=site)

    model.step = step
    inst = telemetry.serving_instruments("stalled")
    inst.between = between = Recorded(inst.between)
    options = {"step": {}, "chunk": {"chunk": 4}}[request.param]
    eng = DecodeEngine(model, name="stalled", instruments=inst,
                       **options).warmup()
    rng = np.random.default_rng(5)
    try:
        for i, (n, m) in enumerate(BURSTS):
            if i:
                time.sleep(IDLE)
                plant["at"] = plant["calls"] + 6
            eng.submit([int(t) for t in rng.integers(1, 32, size=n)],
                       m).result(timeout=120.0)
    finally:
        eng.close()
        telemetry.set_registry(prev)
        registry_mod._startup["frozen"] = frozen
    return {"mode": request.param, "snap": reg.snapshot(),
            "between": between.values}


def test_the_planted_stall_is_the_longest_interval(stalled):
    snap = stalled["snap"]
    longest = _sample(snap, "dl4j_decode_interval_max_seconds",
                      model="stalled")
    # the idle second between the bursts is no interval, the sleep is
    assert STALL <= longest <= 1.1 * STALL
    parts = {p: _sample(snap, "dl4j_decode_longest_interval_seconds",
                        model="stalled", part=p) for p in LONGEST_PARTS}
    assert sum(parts.values()) == pytest.approx(longest, rel=0.01)
    assert all(v >= 0 for v in parts.values()), parts
    # the model's step slept inside the dispatch span
    assert max(parts, key=parts.get) == "dispatch"
    assert parts["dispatch"] >= STALL


def test_an_interval_a_dispatch_but_none_across_an_idle_poll_or_a_block(
        stalled):
    snap = stalled["snap"]
    count = _sample(snap, "dl4j_decode_interval_seconds_count",
                    model="stalled")
    steps = _sample(snap, "dl4j_decode_boundaries_total", model="stalled",
                    executable="step")
    blocks = _sample(snap, "dl4j_decode_boundaries_total", model="stalled",
                     executable="prefill")
    if stalled["mode"] == "step":
        # a burst of one request dispatches a token step a position, and
        # its first dispatch follows an idle poll
        assert steps == sum(n + m - 1 for n, m in BURSTS)
        assert count == steps - len(BURSTS)
    else:
        # every iteration that launched a block forgets the dispatch before
        # it, and each burst's first iteration launches one
        assert blocks > len(BURSTS)
        assert count == steps - blocks
    total = _sample(snap, "dl4j_decode_interval_seconds_sum",
                    model="stalled")
    assert STALL < total < STALL + IDLE
    assert _sample(snap, "dl4j_decode_between_phases_seconds_count",
                   model="stalled") == count


def test_between_is_never_negative_and_under_the_interval(stalled):
    assert len(stalled["between"]) > 0
    assert min(stalled["between"]) >= 0.0
    snap = stalled["snap"]
    between = _sample(snap, "dl4j_decode_between_phases_seconds_sum",
                      model="stalled")
    assert 0 < between < _sample(snap, "dl4j_decode_interval_seconds_sum",
                                 model="stalled")


def test_the_first_delivered_boundary_froze_the_start_up_account(stalled):
    snap = stalled["snap"]
    total = sample_sum(snap, "dl4j_startup_seconds", part="total")
    assert total is not None and total > 0
    assert sample_sum(snap, "dl4j_startup_executables",
                      outcome="compiled") >= 0


def test_a_collection_inside_a_phase_is_taken_out_of_that_part():
    """The seven parts of the longest interval add up to it whether a
    collection fell inside a phase span or between two: 0.25 s inside `emit`
    and 0.03 s between spans, written into the account by hand."""
    reg = MetricsRegistry()
    inst, account = ServingInstruments(reg, "m"), DispatchAccount()
    mark = registry_mod._gc_seconds[0]
    inst.dispatched(account, 100.0)
    assert inst.interval.count == 0         # a first dispatch closes nothing
    account.spent.update(admit=0.01, build=0.02, dispatch=0.03,
                         readback=0.04, emit=0.30)
    account.paused["emit"] = 0.25
    registry_mod._gc_seconds[0] = mark + 0.28   # the cell only ever rises
    inst.dispatched(account, 100.5)
    snap = reg.snapshot()
    part = lambda p: snap[  # noqa: E731
        f'dl4j_decode_longest_interval_seconds{{model="m",part="{p}"}}']
    assert snap['dl4j_decode_interval_max_seconds{model="m"}'] == 0.5
    assert part("emit") == pytest.approx(0.05)
    assert part("gc") == pytest.approx(0.28)
    assert part("between") == pytest.approx(0.07)
    assert sum(part(p) for p in LONGEST_PARTS) == pytest.approx(0.5)
    assert snap['dl4j_decode_between_phases_seconds_sum{model="m"}'] \
        == pytest.approx(0.10)
    assert snap['dl4j_decode_interval_gc_seconds_total{model="m"}'] \
        == pytest.approx(0.28)
    # the account starts again from this dispatch; a shorter interval is
    # observed and leaves the longest one's split as it was
    assert all(account.spent[p] == account.paused[p] == 0.0
               for p in DECODE_PHASES)
    inst.dispatched(account, 100.6)
    assert inst.interval.count == 2
    assert reg.snapshot()[
        'dl4j_decode_longest_interval_seconds{model="m",part="emit"}'] \
        == pytest.approx(0.05)
    account.clear()
    inst.dispatched(account, 200.0)         # after an idle poll: no interval
    assert inst.interval.count == 2
