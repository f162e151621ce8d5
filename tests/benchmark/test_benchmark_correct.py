"""What decides `correct` has to be able to fail. The control (the reference
in the nearest precision below the configuration's, put in the program's
place) and each fault a cell can have, planted under a run that skips only
the look for a chip, come out as not correct at a toy size on the CPU. The
readings at the cells' own sizes, taken on the chip, are in PERF.md."""

import argparse
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
TOY = os.path.join(ROOT, "tests", "benchmark", "data", "toy")

from benchmark import run  # noqa: E402
from benchmark.drivers import serve_closed, train  # noqa: E402
from benchmark.lib import compare  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cell(cell, seed=2 ** 31 + 9):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=0)
    return run.run(args, root=TOY, devices=jax.devices(), peak=PEAK)


def context(cell_name, seed):
    _, cell, config = run.load_cell(
        TOY, run.load_json(TOY, "BENCHMARK.json"), cell_name)
    return run.Context(cell, config, seed, 0.5, 0, jax.devices())


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 5])
def test_training_control_in_float8_is_not_correct(seed):
    ctx = context("toy-mlm.train-1", seed)
    sizes = train._sizes(ctx.config)
    ref = train.reference_readings(ctx, sizes)
    stated, _ = compare.training(
        train.reference_readings(ctx, sizes, mode="bf16"), ref)
    control, _ = compare.training(
        train.reference_readings(ctx, sizes, mode="fp8"), ref)
    limits = ctx.cell["limits"]
    assert all(ok for *_, ok in compare.verdict(
        dict(stated, window_compiles=0), limits)), stated
    assert not all(ok for *_, ok in compare.verdict(
        dict(control, window_compiles=0), limits)), control
    assert control["grad_distance"] > 3 * stated["grad_distance"]


def test_serving_control_in_float8_is_not_correct():
    ctx = context("toy-decoder.closed-4", 11)
    sizes = serve_closed._sizes(ctx.config)
    rng = np.random.default_rng(0)
    requests = [(rng.integers(3, 64, 20).tolist(), 44) for _ in range(4)]
    chosen = [{"i": i, "tokens": rng.integers(3, 64, 44).tolist()}
              for i in range(4)]
    control, _ = serve_closed.reference_gaps(ctx, sizes, requests, chosen,
                                             control="fp8")
    assert not all(ok for *_, ok in compare.verdict(
        dict(control, window_compiles=0), ctx.cell["limits"])), control


def unchanged_state(monkeypatch):
    from deeplearning4j_tpu.models.bert import BertTrainer

    real = BertTrainer.train_step

    def step(self, tokens, labels):
        keep = jax.tree_util.tree_map(jax.numpy.copy, (self.params, self.opt))
        loss = real(self, tokens, labels)
        self.params, self.opt = keep
        return loss

    monkeypatch.setattr(BertTrainer, "train_step", step)


def part_of_the_batch(monkeypatch, share):
    """Only the first `share` of the rows counts, the mean taken over them:
    they are fed again in place of the rest, so the shapes stay. With a
    quarter on four devices this is what the first chip computes when the
    exchange between the chips is left out."""
    from deeplearning4j_tpu.models.bert import BertTrainer

    real = BertTrainer.train_step

    def step(self, tokens, labels):
        n = int(len(tokens) * share)
        reps = len(tokens) // n
        return real(self, np.tile(tokens[:n], (reps, 1)),
                    np.tile(labels[:n], (reps, 1)))

    monkeypatch.setattr(BertTrainer, "train_step", step)


def altered_token(monkeypatch):
    from deeplearning4j_tpu.serving.decode import TransformerDecodeModel

    real = TransformerDecodeModel.step

    def step(self, *a, **kw):
        nxt, state = real(self, *a, **kw)
        return (np.asarray(nxt) + 1) % self.vocab, state

    monkeypatch.setattr(TransformerDecodeModel, "step", step)


@pytest.mark.parametrize("cell, fault", [
    ("toy-mlm.train-1", "unchanged_state"),
    ("toy-mlm.train-1", "half_of_the_batch"),
    ("toy-mlm.train-dp4", "unchanged_state"),
    ("toy-mlm.train-dp4", "half_of_the_batch"),
    ("toy-mlm.train-dp4", "no_exchange"),
    ("toy-decoder.closed-4", "altered_token"),
])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, cell, fault):
    {"unchanged_state": lambda: unchanged_state(monkeypatch),
     "half_of_the_batch": lambda: part_of_the_batch(monkeypatch, 0.5),
     "no_exchange": lambda: part_of_the_batch(monkeypatch, 0.25),
     "altered_token": lambda: altered_token(monkeypatch)}[fault]()
    result, rows = run_cell(cell)
    assert result["correct"] is False, rows
    assert result["attempted"] > 0
    failed = [name for name, _, _, ok in rows if not ok]
    assert failed and "window_compiles" not in failed, rows
