"""The serving cells' whole run, at a size a CPU holds: the harness drives
FlowsService -> ComputeProvider -> ServeEngine, and the comparison with the
plain reference decides ``correct``.  With the timed path broken (a served
token altered where it is produced) ``correct`` comes out false; so it does
with the fp8 control serving in the program's place, under the cell's own
limit, and the control reads far above the program."""

from __future__ import annotations

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import faults, harness, serving
from benchmarks.chip.references import dense_lm

TINY = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
            vocab_size=512)
SEED = 2**31 + 101


def tiny_cell(name: str):
    """The committed cell with its model and prompts cut to a CPU's size;
    its mix, limits and metrics are the cell's own."""
    cell = harness.find_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY)
    mix = dict(cell.traffic, prompt_buckets={"16": 0.5, "32": 0.3, "64": 0.2},
               max_len=64 + cell.traffic["max_new_tokens"])
    mix["max_new_tokens"] = min(mix["max_new_tokens"], 8)
    if "rate_per_s" in mix:
        mix["rate_per_s"] = 10.0
    cell.traffic = mix
    return cell


@pytest.fixture(scope="module")
def decode_run():
    cell = tiny_cell("internlm2-serve-decode")
    return cell, harness.run_cell(cell, SEED, 1.0, False, time.time())


def test_a_sound_run_is_correct_and_reports_its_metrics(decode_run):
    cell, run = decode_run
    assert run.correct, run.checks
    assert run.failed == 0 and run.attempted >= 4
    gap, limit = run.checks["logit_gap"]
    assert 0 <= gap <= limit
    device = jax.devices()[0]
    line = harness.result_line(cell, run, False, [device])
    assert set(line["metrics"]) == {"setup_s", "output_tokens_per_s"}
    assert line["metrics"]["output_tokens_per_s"]["value"] > 0
    assert run.t_end >= run.t0 + 1.0
    # the window ends on a completion and holds whole flows only
    assert any(abs(f["completed"] - run.t_end) < 1e-9
               for f in run.data["flows"] if f["ok"])


def test_a_traced_run_ties_the_trace_to_the_window():
    """The CPU trace has no device plane, so the device metrics read
    nothing; the window still lies on the trace's clock."""
    cell = tiny_cell("internlm2-serve-score")
    run = harness.run_cell(cell, SEED, 1.0, True, time.time())
    assert run.correct
    lo, hi = run.trace_window()
    assert hi - lo == pytest.approx(run.window_s)
    names = [s[2] for s in run.trace.spans]
    assert "bench:anchor" in names and "bench:submit" in names
    assert any(n.startswith("bench:serve S=") for n in names)
    line = harness.result_line(cell, run, True, jax.devices())
    assert set(line["metrics"]) == {"control_ms.score",
                                    "endpoint_wait_ms.score"}
    assert line["device"]["busy_s"] == 0.0
    assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_the_control_reads_far_above_the_program(decode_run):
    cell, run = decode_run
    program = run.checks["logit_gap"][0]
    control = serving.control_gap(cell, SEED, run)
    assert control > 3 * max(program, 1e-3)


@pytest.mark.parametrize("name", ["internlm2-serve-decode",
                                  "internlm2-serve-score"])
def test_an_altered_token_makes_the_run_incorrect(monkeypatch, name):
    faults.plant("altered_token", monkeypatch)
    cell = tiny_cell(name)
    run = harness.run_cell(cell, SEED, 1.0, False, time.time())
    assert run.failed == 0
    assert not run.correct
    gap, limit = run.checks["logit_gap"]
    assert gap > limit


@pytest.mark.parametrize("name", ["internlm2-serve-decode",
                                  "internlm2-serve-score"])
def test_the_control_in_the_programs_place_makes_the_run_incorrect(
        monkeypatch, name):
    faults.plant("control", monkeypatch)
    cell = tiny_cell(name)
    run = harness.run_cell(cell, SEED, 1.0, False, time.time())
    assert run.failed == 0
    gap, limit = run.checks["logit_gap"]
    assert limit == harness.find_cell(name).limits["logit_gap"]
    assert gap > limit
    assert not run.correct


def test_the_reference_agrees_with_the_program_in_float32():
    """At float32 compute the program's forward and the reference's agree
    to rounding, on the weights the benchmark makes: the layout, RoPE,
    grouped heads and norms are read alike."""
    from repro.configs.base import ModelConfig
    from repro.models.model import Model, param_shapes

    cfg = dict(harness.find_cell("internlm2-serve-decode").model, **TINY)
    params = dense_lm.init_params(cfg, SEED)
    program_shapes = jax.tree_util.tree_map(
        lambda s: tuple(s), param_shapes(ModelConfig(**cfg)),
        is_leaf=lambda x: isinstance(x, tuple))
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == program_shapes
    model = Model(ModelConfig(**dict(cfg, compute_dtype="float32")))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": tokens})
    want = dense_lm.logits(cfg, params, tokens, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
