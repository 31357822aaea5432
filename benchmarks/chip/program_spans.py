"""The program's own spans in a traced run, set against the device.

The program marks its layers with profiler spans (``repro.obs``):
``flows.*`` (the flow engine, each with its ``run``), ``journal.append``,
``compute.run`` (with the action's ``request`` and the endpoint's
``queued_ms``), ``serve.*`` and ``train.*``.  They lie on the host plane
of the run's ``.xplane.pb``, on the clock of the device's operations, each
on the line of its thread and with its metadata as event stats.

The readers here keep the spans that lie wholly inside the measured
window and read, for a span, the device-idle time inside it and its self
time (its duration less what its child spans on the same line cover).
Where the trace has no device plane to set them against, or the program
marks no such spans, they read ``None``.
"""

from __future__ import annotations

import functools
import glob
import os
import statistics
from collections import defaultdict
from dataclasses import dataclass

from benchmarks.chip.harness import TRACE_DIR, log
from benchmarks.chip.readers import median_ms

PREFIXES = ("flows.", "journal.", "compute.", "serve.", "train.")


@dataclass
class Span:
    start: float          # seconds, the trace's clock
    end: float
    name: str
    line: tuple           # (plane, line): one thread
    meta: dict            # the span's stats
    self_s: float = 0.0   # duration less its children's on the same line


def from_planes(planes) -> list:
    """The program's spans of objects shaped like ``ProfileData.planes``
    (events with ``name``, ``start_ns``, ``duration_ns``, ``stats``), by
    start."""
    spans = []
    for p, plane in enumerate(planes):
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            spans += [Span(e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name,
                           (p, k), dict(e.stats))
                      for e in line.events if e.name.startswith(PREFIXES)]
    # a thread's spans nest: each one's parent is the innermost open span
    spans.sort(key=lambda s: (s.line, s.start, -s.end))
    open_spans: list = []
    for s in spans:
        s.self_s = s.end - s.start
        while open_spans and (open_spans[-1].line != s.line
                              or open_spans[-1].end <= s.start):
            open_spans.pop()
        if open_spans:
            open_spans[-1].self_s -= s.end - s.start
        open_spans.append(s)
    spans.sort(key=lambda s: s.start)
    return spans


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> tuple:
    import jax

    return tuple(from_planes(jax.profiler.ProfileData.from_file(path).planes))


def load(trace_dir) -> list:
    """The program's spans in the newest ``.xplane.pb`` under
    ``trace_dir``, read once per file."""
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    return list(_read(files[-1], os.stat(files[-1]).st_mtime_ns))


def in_window(run, cell) -> list | None:
    """The program's spans wholly inside the run's window; ``None``
    without a trace that has a device plane."""
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    lo, hi = window
    return [s for s in load(cell.root / TRACE_DIR.name / cell.name)
            if lo <= s.start and s.end <= hi]


def idle_inside(run, span: Span) -> float:
    """Seconds inside ``span`` in which no operation ran, mean over the
    traced devices."""
    devices = run.trace.devices
    return sum(span.end - span.start
               - d.busy_index().busy(span.start, span.end)
               for d in devices) / len(devices)


def idle_ms(run, cell, name: str) -> float | None:
    """Median device-idle time inside the window's ``name`` spans, ms."""
    spans = in_window(run, cell)
    if spans is None:
        return None
    return median_ms(idle_inside(run, s) for s in spans if s.name == name)


def idle_per_step_ms(run, cell, names: tuple) -> float | None:
    """Median, over steps, of the device-idle time inside the window's
    spans called one of ``names`` that carry that ``step``, ms."""
    spans = in_window(run, cell)
    if spans is None:
        return None
    steps: dict = defaultdict(float)
    for s in spans:
        if s.name in names:
            steps[s.line, s.meta.get("step")] += idle_inside(run, s)
    return median_ms(steps.values())


def transition_self_ms(run, cell) -> float | None:
    """Median, over the flows that start and complete in the window, of
    the summed self time of their ``flows.*`` and ``journal.append``
    spans, ms."""
    spans = in_window(run, cell)
    if spans is None:
        return None
    started = {s.meta.get("run") for s in spans if s.name == "flows.start"}
    whole = started & {s.meta.get("run") for s in spans
                       if s.name == "flows.complete"}
    per_run: dict = defaultdict(float)
    for s in spans:
        run_id = s.meta.get("run")
        if run_id in whole and (s.name.startswith("flows.")
                                or s.name == "journal.append"):
            per_run[run_id] += s.self_s
    return median_ms(per_run.values())


def endpoint_queue_ms(run, cell) -> float | None:
    """Median ``queued_ms`` of the window's ``compute.run`` spans: the
    time an action waited for the endpoint's worker."""
    spans = in_window(run, cell)
    if spans is None:
        return None
    queued = [float(s.meta["queued_ms"]) for s in spans
              if s.name == "compute.run" and "queued_ms" in s.meta]
    return statistics.median(queued) if queued else None


def window_compiles(run, cell=None) -> int | None:
    """Programs the process lowered inside the window, by the program's
    compile log; read beside the traced run's device plane, as the spans
    are, and ``None`` where the program keeps no compile log."""
    if run.trace is None or not run.trace.devices:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    names = obs.compiles_between(run.t0, run.t_end)
    if names:
        log(f"compiled inside the window: {names}")
    return len(names)
