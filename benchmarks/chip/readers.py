"""Shared arithmetic of the metric readers in ``metrics/``.

Each reader takes a finished run (``harness.RunResult``) and its cell, and
returns a number, or ``None`` where the run holds nothing to read (no
trace, no program of that kind in the window).
"""

from __future__ import annotations

import re
import statistics

from benchmarks.chip import flops, xplane
from benchmarks.chip.peaks import peaks

#: program names as the trace shows them (``XLA Modules``).  The serving
#: prefill is jitted from a lambda today; a stable name that says prefill,
#: decode or train step is read as well.
PREFILL = r"^jit__lambda$|prefill"
DECODE = r"decode"
TRAIN_STEP = r"^jit_(train_step|wrapped)$|train_step"

_SERVE_SPAN = re.compile(r"^bench:serve S=(\d+) B=(\d+)")


def median_ms(values) -> float | None:
    values = list(values)
    return statistics.median(values) * 1e3 if values else None


def p95(values) -> float | None:
    values = sorted(values)
    if not values:
        return None
    return float(statistics.quantiles(values, n=20, method="inclusive")[-1]
                 if len(values) > 1 else values[0])


def idle_share(run, cell=None) -> float | None:
    """Percent of the window in which no operation ran, mean over chips;
    the reader of every ``device_idle_share.<cell kind>`` metric."""
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    lo, hi = window
    return 100.0 * (1.0 - xplane.device_busy(run.trace, lo, hi) / (hi - lo))


def ok_flows(run) -> list:
    return [f for f in run.data.get("flows", []) if f.get("ok")]


def flows_in_window(run) -> list:
    """Flows completed inside the window."""
    return [f for f in ok_flows(run) if f["completed"] <= run.t_end]


def serve_shapes_of(run, programs) -> list:
    """``(program, seq, batch)`` of each program run that lies inside a
    ``bench:serve`` span (whose name carries the prompt length)."""
    spans = [(s, e, _SERVE_SPAN.match(n)) for s, e, n in run.trace.spans]
    spans = [(s, e, int(m[1]), int(m[2])) for s, e, m in spans if m]
    out = []
    for p in programs:
        mid = (p[0] + p[1]) / 2
        for s, e, seq, b in spans:
            if s <= mid <= e:
                out.append((p, seq, b))
                break
    return out


def prefill_mfu(run, cell) -> float | None:
    """Model FLOPs of the window's prefill programs over their device time
    at the chip's peak, percent."""
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    dev = run.trace.devices[0]
    runs = serve_shapes_of(run, xplane.programs_matching(dev, PREFILL,
                                                         *window))
    if not runs:
        return None
    work = sum(flops.prefill_flops(cell.model, b, s) for _, s, b in runs)
    busy = sum(p[1] - p[0] for p, _, _ in runs)
    return 100.0 * work / (busy * peaks(run.device_kind).bf16_flops)
