"""The harness: find a cell by name, run it once, print one result line.

Everything a cell is made of is found by name, so a later change adds a
cell by adding files and ``BENCHMARK.json`` entries, and edits nothing:

* the cell (``workloads``) names a configuration and a traffic mix;
* the configuration's file (``configs[].file``) holds the model's sizes
  (``model``) and names its plain reference, ``references/<name>.py``;
* the traffic mix is ``traffic/<name>.json``; its ``kind`` names the
  driver, ``kinds/<kind>.py``, which exposes ``run(cell, seed, seconds,
  trace, t_process) -> RunResult``;
* the limits of the comparison that decides ``correct`` are
  ``limits/<cell>.json``;
* each metric is ``metrics/<name>.py``, exposing ``read(run, cell)`` that
  returns a number, or ``None`` where the run has nothing to read.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]

#: the checkout's compile-cache directory, unless JAX_COMPILATION_CACHE_DIR
#: names one; a fixed path, since the path is part of every entry's key
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ finding

def load_module(path: Path):
    """The module in file ``path``, loaded once per process."""
    key = f"bench_chip:{Path(path).resolve()}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file
    traffic: dict         # the traffic mix's file
    limits: dict          # limits/<cell>.json
    end_to_end: list      # names of the cell's end-to-end metrics
    per_layer: list       # names of the cell's per-layer metrics
    chip_dir: Path
    root: Path

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def reference(self):
        return load_module(self.chip_dir / "references"
                           / f"{self.config['reference']}.py")

    def kind(self):
        return load_module(self.chip_dir / "kinds"
                           / f"{self.traffic['kind']}.py")

    def metric(self, name: str):
        return load_module(self.chip_dir / "metrics" / f"{name}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT, chip_dir: Path | None = None
              ) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files read."""
    chip_dir = chip_dir or root / "benchmarks" / "chip"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((chip_dir / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits_path = chip_dir / "limits" / f"{name}.json"
    limits = json.loads(limits_path.read_text()) if limits_path.exists() else {}
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        limits=limits.get("limits", {}),
        end_to_end=[m["name"] for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m["name"] for m in spec["per_layer"] if _applies(m, name)],
        chip_dir=chip_dir, root=root,
    )


# ------------------------------------------------------------------ running

@dataclass
class RunResult:
    setup_s: float
    t0: float                 # window start, host clock (time.time())
    t_end: float              # window end, host clock
    seconds: float            # --seconds
    attempted: int
    failed: int
    correct: bool
    checks: dict              # name -> (value, limit)
    memory_peak_bytes: int
    device_kind: str = ""
    trace: object = None      # xplane.TraceSummary of the window, or None
    data: dict = field(default_factory=dict)   # what the cell's kind saw

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def trace_window(self) -> tuple[float, float] | None:
        """The measured window on the trace's clock."""
        if self.trace is None:
            return None
        return self.trace.to_trace(self.t0), self.trace.to_trace(self.t_end)


class Tracer:
    """A profiler trace of the measured window, when ``on``.  The host
    clock is tied to the trace's by a ``bench:anchor`` span opened at a
    known ``time.time()``."""

    def __init__(self, cell: Cell, on: bool):
        self.on = on
        self.dir = cell.root / TRACE_DIR.name / cell.name
        self.anchor = None

    @contextlib.contextmanager
    def window(self):
        """Trace what runs inside the ``with`` block, when on."""
        if not self.on:
            yield
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench:anchor"):
                self.anchor = time.time()
            yield
        finally:
            t = time.time()
            jax.profiler.stop_trace()
            log(f"trace: stopped in {time.time() - t:.3f} s")

    def finish(self):
        """The reduced trace, its clock tied to the host's; None when off."""
        if not self.on:
            return None
        from benchmarks.chip import xplane

        t = time.time()
        summary = xplane.load(str(self.dir))
        log(f"trace: read in {time.time() - t:.3f} s, "
            f"{sum(len(d.ops) for d in summary.devices)} device operations")
        starts = [s for s, _, n in summary.spans if n == "bench:anchor"]
        if not starts:
            raise RuntimeError("the trace holds no bench:anchor span")
        summary.offset = starts[0] - self.anchor
        return summary


def memory_peak() -> int:
    """Peak bytes in use on the fullest device since the process started."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def free(tree) -> None:
    """Release device buffers now, whoever still holds a reference."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def keep_runtime_logs() -> None:
    """Send the TPU runtime's own logs into the checkout; call before JAX
    starts.  The runtime writes them only where the directory exists."""
    path = ROOT / ".bench_work" / "tpu_logs"
    path.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(path))


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int) -> list:
    """The TPU devices, or exit non-zero with no result: off the TPU, with
    fewer chips than ``n``, or on a chip whose peaks the table lacks."""
    import jax

    from benchmarks.chip.peaks import PEAKS

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no accelerator: JAX's first device is {devices[0].platform}; "
            f"this benchmark runs only on a TPU")
        raise SystemExit(3)
    if len(devices) < n:
        log(f"the cell needs {n} chips; JAX sees {len(devices)}")
        raise SystemExit(3)
    if devices[0].device_kind not in PEAKS:
        log(f"no published peaks for {devices[0].device_kind!r}; "
            f"known: {sorted(PEAKS)}")
        raise SystemExit(3)
    return devices[:n]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float) -> RunResult:
    return cell.kind().run(cell, seed, seconds, trace, t_process)


def result_line(cell: Cell, run: RunResult, trace: bool, devices) -> dict:
    """The last line of standard output: the contract's keys, then the
    numbers compared, each beside its limit."""
    from benchmarks.chip import xplane

    metrics = {}
    names = cell.per_layer if trace else cell.end_to_end
    units = _units(cell.root)
    for name in names:
        value = cell.metric(name).read(run, cell)
        if value is not None:
            metrics[name] = {"value": value, "unit": units.get(name, "")}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": bool(run.correct), "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    window = run.trace_window()
    if trace and window is not None:
        lo, hi = window
        device["busy_s"] = xplane.device_busy(run.trace, lo, hi)
        device["window_s"] = hi - lo
        line["breakdown"] = xplane.breakdown(run.trace, lo, hi)
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    return line


def _units(root: Path) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}


def main(argv: list[str] | None, t_process: float) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = find_cell(args.workload)
    devices = require_chips(cell.chips)
    log(f"compile cache: {enable_compile_cache()}")
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_process)
    line = result_line(cell, run, bool(args.trace), devices)
    log(f"correct: {line['correct']}")
    for k, (v, lim) in run.checks.items():
        log(f"compared {k}: {v!r} limit {lim!r}")
    print(json.dumps(line), flush=True)
    return 0
