"""Smoke-run the example scripts (each asserts its own invariants)."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout


def test_quickstart():
    out = _run("quickstart.py")
    assert "SUCCEEDED" in out and "catalog entry" in out


def test_ssx_pipeline():
    out = _run("ssx_pipeline.py", "--images", "8", "--hits-needed", "3")
    assert "SSX pipeline complete" in out


def test_publication_flow():
    out = _run("publication_flow.py")
    assert "DOI: 10.18126/repro.000001" in out
    assert "Publication flow complete" in out


def test_elastic_training():
    """Sharded 2x2 training on four virtual devices, killed, resharded."""
    out = _run("elastic_training.py")
    assert "resharded: (2, 2) -> (1, 2), restored step 5" in out
    assert "Elastic training complete" in out
