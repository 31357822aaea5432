"""Training cells on a mesh, at a size a CPU holds, on four CPU devices in a
process of their own (``mesh_case.py``): the 2x2 cell's whole run reads
``correct`` with its state spread over the chips, and with its step broken
underneath (half of the batch, an unchanged state, no gradient exchanged
between the data-parallel groups) reads not correct; the reference spread over the mesh reads as
it does on one device; and the one-chip cell, which names no mesh, reads
what it read before meshes could be named (``one_chip_readings.json``)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


def case(name: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(HERE / "mesh_case.py"), name],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def close(a: list, b: list, rel: float) -> bool:
    return len(a) == len(b) and all(abs(x - y) <= rel * abs(y)
                                    for x, y in zip(a, b))


def test_a_mesh_run_is_correct_with_its_state_spread_over_the_chips():
    read = case("mesh")
    assert read["correct"], read["checks"]
    assert all(v <= limit for v, limit in read["checks"].values())
    assert read["chips"] == 4
    # every parameter lies on all four devices, and none whole on one
    assert read["state"]["devices"] == [4]
    assert read["state"]["whole_on_one_device"] == 0


@pytest.mark.parametrize("fault", ["half_batch", "unchanged", "no_exchange"])
def test_a_broken_step_on_the_mesh_makes_the_run_incorrect(fault):
    read = case(f"mesh:{fault}")
    assert not read["correct"]
    over = [k for k, (v, limit) in read["checks"].items() if v > limit]
    assert over, read["checks"]


def test_the_reference_over_a_mesh_reads_as_on_one_device():
    read = case("reference")
    one, mesh = read["one"], read["mesh"]
    assert close(mesh["losses"], one["losses"], 1e-6)
    # per-leaf norms sum in another order over the mesh
    assert close(mesh["first_grad"], one["first_grad"], 1e-5)
    assert close(mesh["change"], one["change"], 1e-5)


def test_without_a_mesh_the_one_chip_run_reads_as_before():
    read = case("one")
    before = json.loads((HERE / "one_chip_readings.json").read_text())
    assert read["chips"] == 1
    assert read["state"]["devices"] == [1]
    for side in ("got", "want"):
        for key in ("losses", "first_grad", "change"):
            assert close(read[side][key], before[side][key], 1e-6), \
                (side, key)
