"""The chip benchmark's harness finds every part of a cell by name, and
refuses to print a result off the chip."""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[2]

KIND = '''
from benchmarks.chip.harness import RunResult


def run(cell, seed, seconds, trace, t_process):
    ref = cell.reference
    return RunResult(setup_s=1.5, t0=10.0, t_end=10.0 + seconds,
                     seconds=seconds, attempted=3, failed=0, correct=True,
                     checks={"answer": (ref.answer(seed), 99)},
                     memory_peak_bytes=123,
                     data={"served": cell.traffic["per_flow"] * 3})
'''
METRIC = '''
def read(run, cell):
    return run.data["served"] / run.window_s
'''
SILENT = '''
def read(run, cell):
    return None
'''


def extra_root(tmp_path: Path) -> Path:
    """A checkout whose BENCHMARK.json gains one cell, configuration, mix,
    kind, reference and two metrics, each only as a new file or entry."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "https://example.org",
                            "file": "benchmarks/chip/configs/toy.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy-cell", "config": "toy",
                              "traffic": "toy-mix", "chips": 1, "why": "t"})
    spec["end_to_end"].append({"name": "toy_rate", "unit": "flows/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["toy-cell"]})
    spec["per_layer"].append({"name": "toy_silent", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "toy", "moves": "toy_rate",
                              "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (chip / "configs" / "toy.json").write_text(json.dumps(
        {"reference": "toy_ref", "model": {"width": 3}}))
    (chip / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"kind": "toy_kind", "per_flow": 4}))
    (chip / "limits" / "toy-cell.json").write_text(json.dumps(
        {"limits": {"answer": 99}}))
    (chip / "kinds" / "toy_kind.py").write_text(KIND)
    (chip / "references" / "toy_ref.py").write_text(
        "def answer(seed):\n    return seed % 7\n")
    (chip / "metrics" / "toy_rate.py").write_text(METRIC)
    (chip / "metrics" / "toy_silent.py").write_text(SILENT)
    return root


def test_an_added_cell_is_found_by_name_with_no_edit(tmp_path):
    root = extra_root(tmp_path)
    cell = harness.find_cell("toy-cell", root=root)
    assert cell.chips == 1 and cell.model == {"width": 3}
    assert cell.traffic["kind"] == "toy_kind"
    assert cell.limits == {"answer": 99}
    assert "toy_rate" in cell.end_to_end and "setup_s" in cell.end_to_end
    # metrics limited to other cells are not this cell's
    assert "output_tokens_per_s" not in cell.end_to_end
    assert cell.per_layer == ["toy_silent"]
    run = harness.run_cell(cell, 12, 2.0, False, 0.0)
    device = NS(platform="tpu", device_kind="TPU v5 lite")
    line = harness.result_line(cell, run, False, [device])
    assert line["metrics"] == {"setup_s": {"value": 1.5, "unit": "s"},
                               "toy_rate": {"value": 6.0, "unit": "flows/s"}}
    assert list(line)[-1] == "compared"
    assert line["compared"] == {"answer": {"value": 5, "limit": 99}}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123}
    # a reader with nothing to read leaves its metric out of the line
    traced = harness.result_line(cell, run, True, [device])
    assert traced["metrics"] == {}
    # the committed cells are still found as they were
    for name in ("internlm2-serve-decode", "internlm2-serve-score"):
        assert harness.find_cell(name, root=root).config["name"] == \
            "internlm2-1.8b"
    with pytest.raises(KeyError):
        harness.find_cell("no-such-cell", root=root)


def test_every_committed_cell_resolves_to_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert (cell.chip_dir / "kinds" / f"{cell.traffic['kind']}.py").exists()
        assert (cell.chip_dir / "references"
                / f"{cell.config['reference']}.py").exists()
        assert cell.limits, w["name"]
        for name in cell.end_to_end + cell.per_layer:
            assert (cell.chip_dir / "metrics" / f"{name}.py").exists(), name
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        # a mix that names a mesh covers the cell's chips with named axes
        mesh = cell.traffic.get("mesh")
        if mesh is not None:
            assert math.prod(mesh["shape"]) == cell.chips, w["name"]
            assert len(mesh["axes"]) == len(mesh["shape"]), w["name"]


def _run_py(cwd: Path, *args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=240)


ARGS = ("--workload", "internlm2-serve-decode", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0")


def test_off_the_chip_run_exits_nonzero_and_prints_no_result():
    out = _run_py(ROOT, *ARGS)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no accelerator" in out.stderr


def test_the_benchmark_files_alone_print_no_result(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path, *ARGS)
    assert out.returncode != 0
    assert out.stdout == ""
