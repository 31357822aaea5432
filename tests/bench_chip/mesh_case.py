"""One tiny training run of the chip benchmark on four CPU devices, for
``test_bench_chip_mesh.py``, which runs it in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench_chip/mesh_case.py <case>

``<case>`` is ``mesh`` (the 2x2 cell), ``mesh:<fault>`` (the same with a
fault of ``faults.py`` planted), ``one`` (the one-chip cell) or
``reference`` (the reference's readings with and without the mesh).  The
last line of standard output is one JSON object of what it read.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SEED = 2**31 + 303
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab_size=256)
#: a 64-wide model in bf16 on the CPU departs from float32 by about 4e-4
#: (loss) and 2e-3 (norms); these limits sit five times above that
LIMITS = {"loss": 2e-3, "first_grad": 1e-2, "change": 1e-2}


def tiny_cell(name: str):
    from benchmarks.chip import harness

    cell = harness.find_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY)
    cell.traffic = dict(cell.traffic, batch=4, seq_len=32,
                        steps_per_segment=2)
    cell.limits = dict(LIMITS)
    return cell


def spread_of_state(seen: dict):
    """Record, at the first segment, how the fabric's parameters lie."""
    import jax

    from repro.train.fabric import TrainingFabric

    steps = TrainingFabric.train_steps

    def watched(self, *args, **kw):
        if not seen:
            leaves = jax.tree_util.tree_leaves(self.state.params)
            seen["devices"] = sorted({len(x.sharding.device_set)
                                      for x in leaves})
            seen["whole_on_one_device"] = sum(
                x.sharding.shard_shape(x.shape) == x.shape for x in leaves)
            seen["leaves"] = len(leaves)
        return steps(self, *args, **kw)

    TrainingFabric.train_steps = watched


def run_cell(name: str, fault: str | None) -> dict:
    from benchmarks.chip import faults, harness

    cell = tiny_cell(name)
    seen: dict = {}
    spread_of_state(seen)
    if fault:
        faults.plant(fault, faults.Patch())
    run = harness.run_cell(cell, SEED, 0.5, False, time.time())
    return {"correct": run.correct,
            "checks": run.checks,
            "chips": run.data["chips"], "got": run.data["got"],
            "want": run.data["want"], "state": seen}


def reference() -> dict:
    from benchmarks.chip import training
    from benchmarks.chip.references import dense_lm

    cell = tiny_cell("internlm2-train-2x2")
    mix = cell.traffic
    data = training.SeededTokens(SEED, cell.model["vocab_size"],
                                 mix["batch"], mix["seq_len"])
    read = {}
    for key, mesh in (("one", None), ("mesh", training.mesh_of(cell))):
        read[key] = dense_lm.train_readings(
            cell.model, mix["optimizer"], SEED, data, 3, 2, mesh=mesh)
    return read


def main(case: str) -> dict:
    if case == "reference":
        return reference()
    if case == "one":
        return run_cell("phi3-train-segments", None)
    _, _, fault = case.partition(":")
    return run_cell("internlm2-train-2x2", fault or None)


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])), flush=True)
