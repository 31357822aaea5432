"""The training cell's whole run, at a size a CPU holds: the harness drives
the flow of segments over a ``TrainingFabric`` and compares its first
steps with the plain reference.  With the step broken underneath (a state
returned unchanged, half of the batch left out) ``correct`` comes out
false; so it does with the fp8 control of the reference in the program's
place, which also reads far above the program."""

from __future__ import annotations

import copy
import time

import jax
import pytest

from benchmarks.chip import faults, harness, training
from benchmarks.chip.references import dense_lm

SEED = 2**31 + 303


def tiny_cell():
    cell = harness.find_cell("phi3-train-segments")
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(n_layers=2, d_model=64, n_heads=4,
                                n_kv_heads=4, d_ff=128, vocab_size=256)
    cell.traffic = dict(cell.traffic, batch=4, seq_len=32, steps_per_segment=2)
    # a 64-wide model in bf16 on the CPU departs from float32 by about
    # 2e-4 (loss) and 1e-3 (norms), more than the chip's cell at its
    # published widths; these limits sit ten times above that
    cell.limits = {"loss": 2e-3, "first_grad": 1e-2, "change": 1e-2}
    return cell


@pytest.fixture(scope="module")
def sound_run():
    cell = tiny_cell()
    return cell, harness.run_cell(cell, SEED, 1.0, False, time.time())


def test_a_sound_run_is_correct_and_counts_whole_segments(sound_run):
    cell, run = sound_run
    assert run.correct, run.checks
    assert run.attempted == sum(s["steps"] for s in run.data["segments"])
    assert run.attempted % cell.traffic["steps_per_segment"] == 0
    assert run.window_s >= 1.0
    assert run.data["segments"][-1]["end"] == run.t_end
    line = harness.result_line(cell, run, False, jax.devices())
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert set(line["compared"]) == {"loss", "first_grad", "change"}


def test_the_control_reads_far_above_the_program(sound_run):
    cell, run = sound_run
    control = dense_lm.train_readings(
        cell.model, cell.traffic["optimizer"], SEED,
        training.SeededTokens(SEED, cell.model["vocab_size"], 4, 32),
        3, 2, control=True)
    program = run.checks
    readings = training.compare(control, run.data["want"])
    assert any(readings[k] > 3 * program[k][0] for k in readings)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "control"])
def test_a_broken_step_makes_the_run_incorrect(monkeypatch, fault):
    faults.plant(fault, monkeypatch)
    cell = tiny_cell()
    run = harness.run_cell(cell, SEED, 0.5, False, time.time())
    assert not run.correct
    over = [k for k, (v, lim) in run.checks.items() if v > lim]
    assert over, run.checks


def test_compare_reads_the_worst_leaf_against_the_median():
    want = {"losses": [2.0, 1.9], "first_grad": [1.0, 2.0, 3.0, 1e-6],
            "change": [0.1, 0.2, 0.3, 0.5]}
    got = {"losses": [2.02, 1.9], "first_grad": [1.0, 2.0, 3.0, 0.3],
           "change": [0.1, 0.2, 0.6, 5.0]}
    r = training.compare(got, want)
    assert r["loss"] == pytest.approx(0.01)
    # the near-zero leaf's gap is measured against the median leaf
    assert r["first_grad"] == pytest.approx((0.3 - 1e-6) / 1.5)
    # the leaf the reference barely moves is left out of the change
    assert r["change"] == pytest.approx(0.3 / 0.3)
    assert training.compare(dict(got, losses=[2.0]), want)["loss"] == \
        float("inf")
