"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The trace holds, per device plane (``/device:TPU:<n>``), a line of XLA
programs (``XLA Modules``: one event per execution of a jitted program)
and a line of the operations inside them (``XLA Ops``); and, on the host
plane, the benchmark's own ``jax.profiler.TraceAnnotation`` spans, whose
names start with ``bench:``.  Host and device events share one clock, in
nanoseconds from the start of the trace.

Everything here works on plain ``(start_s, end_s, name)`` tuples, so the
reduction can be checked on a trace synthesized in a test.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from dataclasses import dataclass, field

SPAN_PREFIX = "bench:"
_PROGRAM_HASH = re.compile(r"\(\d+\)$")


@dataclass
class DeviceTrace:
    name: str
    ops: list = field(default_factory=list)       # (start_s, end_s, name)
    programs: list = field(default_factory=list)  # (start_s, end_s, name)
    _index: object = field(default=None, repr=False)

    def busy_index(self) -> "BusyIndex":
        if self._index is None:
            self._index = BusyIndex(self)
        return self._index


@dataclass
class TraceSummary:
    devices: list                                  # [DeviceTrace]
    spans: list                                    # (start_s, end_s, name)
    offset: float = 0.0     # trace clock minus host clock (time.time())

    def to_trace(self, t_host: float) -> float:
        return t_host + self.offset


def program_name(event_name: str) -> str:
    """``jit_decode_step(1713...)`` -> ``jit_decode_step``."""
    return _PROGRAM_HASH.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.80 = s32[...] fusion(...)`` -> ``fusion.80``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def from_planes(planes) -> TraceSummary:
    """Build a summary from objects shaped like ``ProfileData.planes``:
    each plane has ``name`` and ``lines``; each line ``name`` and
    ``events``; each event ``name``, ``start_ns`` and ``duration_ns``."""
    devices, spans = [], []
    for plane in planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _intervals(line.events, op_name)
                elif line.name == "XLA Modules":
                    dev.programs = _intervals(line.events, program_name)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(iv for iv in _intervals(line.events, str)
                             if iv[2].startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d.name)
    spans.sort()
    return TraceSummary(devices=devices, spans=spans)


def _intervals(events, rename) -> list:
    out = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
            rename(e.name)) for e in events]
    out.sort()
    return out


def load(trace_dir: str) -> TraceSummary:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(jax.profiler.ProfileData.from_file(files[-1]).planes)


# ---------------------------------------------------------------- intervals

def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` pieces of ``intervals`` clipped to [lo, hi]."""
    merged = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch in [lo, hi] with no interval."""
    gaps, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def programs_matching(device: DeviceTrace, pattern: str, lo: float,
                      hi: float) -> list:
    """Program executions whose name matches ``pattern`` (a regex searched
    in the name) and that lie wholly inside [lo, hi]."""
    rx = re.compile(pattern)
    return [p for p in device.programs
            if rx.search(p[2]) and p[0] >= lo and p[1] <= hi]


class BusyIndex:
    """Busy time of a device over any stretch, by bisection over the union
    of its operations (or programs, where the trace has no operations)."""

    def __init__(self, device: DeviceTrace):
        pieces = union(device.ops or device.programs, float("-inf"),
                       float("inf"))
        self.starts = [s for s, _ in pieces]
        self.ends = [e for _, e in pieces]
        self.before = [0.0]
        for s, e in pieces:
            self.before.append(self.before[-1] + e - s)

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(self.ends[i - 1], t) - self.starts[i - 1]

    def busy(self, lo: float, hi: float) -> float:
        return max(self._upto(hi) - self._upto(lo), 0.0)


def idle_between(device: DeviceTrace, runs: list) -> list:
    """Device-idle seconds between consecutive program runs in ``runs``
    (sorted), counting only the time no operation of any program ran."""
    index = device.busy_index()
    return [start - end - index.busy(end, start)
            for (_, end, _), (start, _, _) in zip(runs, runs[1:])
            if start > end]


def span_at(spans, t: float) -> str:
    """The innermost (shortest) benchmark span covering time ``t``, without
    its prefix and parameters; ``"none"`` where no span covers it."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    if best is None:
        return "none"
    return best[2][len(SPAN_PREFIX):].split(" ", 1)[0]


def median(values) -> float | None:
    values = list(values)
    return statistics.median(values) if values else None


# ---------------------------------------------------------------- breakdown

def device_busy(summary: TraceSummary, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the traced devices."""
    if not summary.devices:
        return 0.0
    return sum(busy_seconds(d.ops or d.programs, lo, hi)
               for d in summary.devices) / len(summary.devices)


def breakdown(summary: TraceSummary, lo: float, hi: float) -> dict:
    """The device operations that took most time (per device, averaged,
    named ``program/op``) and the device-idle time by what the host was
    doing (the innermost benchmark span), each the ten largest."""
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    n = max(len(summary.devices), 1)
    for dev in summary.devices:
        progs = [p for p in dev.programs if p[1] > lo and p[0] < hi]
        k = 0
        for s, e, name in dev.ops:
            if e <= lo or s >= hi:
                continue
            while k < len(progs) and progs[k][1] < s:
                k += 1
            prog = progs[k][2] if k < len(progs) and progs[k][0] <= s else "?"
            key = f"{prog}/{name}"
            ops[key] = ops.get(key, 0.0) + (min(e, hi) - max(s, lo)) / n
        inside = union(dev.programs, lo, hi)
        starts = [s for s, _ in inside]
        for s, e in idle_gaps(dev.ops or dev.programs, lo, hi):
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and inside[i][1] >= mid:
                label = "in-program"   # between operations of one program
            else:
                label = span_at(summary.spans, mid)
            gaps[label] = gaps.get(label, 0.0) + (e - s) / n

    def biggest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]

    return {"device_ops": biggest(ops), "idle_gaps": biggest(gaps)}
