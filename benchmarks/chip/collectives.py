"""Collective operations of the train-step program, read from the trace.

On each device plane of the run's ``.xplane.pb``, the ``XLA Ops`` line
holds every operation the device ran, named by its HLO instruction
(``%all-gather-start.3 = bf16[...] all-gather-start(...), ...``).  An
operation is a collective where its instruction name, or the computation
a fusion calls (``calls=%all-reduce-scatter.6``), contains one of

    all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all,
    async-collective

which covers their ``-start`` / ``-done`` forms (``all-gather-start.3``,
``collective-permute-done``), their fusions (``all-gather-fusion.2``, a
fusion calling ``all-reduce-scatter.6``) and the TPU's asynchronous
collective fusions (``async-collective-start.1`` ... ``-done.1``).  A
compute fusion that runs beside a collective in flight
(``calls=%async_collective_fusion.510``, with underscores) and any other
name (``fusion.12``, ``convolution.3``, ``copy-start.20``) is not one.
Every chip runs the same program, so the names are read on the first
device plane and hold for all.

A ``-start`` and the next ``-done`` of the same name (``.N`` suffix and
all) make one collective in flight from the start's beginning to the
done's end; any other collective is in flight while it runs.  The other
operations, not counting those that hold others (a ``while`` around a
layer's operations), are the compute.  Per run of the train-step program,
the collective time is the union of the collectives in flight, and the
exposed time the part of that union in which no compute runs.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from collections import defaultdict, deque

import numpy as np

from benchmarks.chip import xplane
from benchmarks.chip.harness import TRACE_DIR
from benchmarks.chip.readers import TRAIN_STEP

KINDS = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|"
                   r"all-to-all|async-collective")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_ASYNC = re.compile(r"^(.*)-(start|done)(\.\d+)?$")


def is_collective(event_name: str) -> bool:
    """Whether the operation ``event_name`` (its HLO text, or just its
    name) is a collective, by its own name or the computation it calls."""
    if KINDS.search(xplane.op_name(event_name)):
        return True
    called = _CALLS.search(event_name)
    return bool(called and KINDS.search(called.group(1)))


def collective_names(planes) -> set:
    """Names of the collective operations on the first device plane's
    ``XLA Ops`` line of objects shaped like ``ProfileData.planes``."""
    for plane in sorted(planes, key=lambda p: p.name):
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return {xplane.op_name(n) for n in
                            {e.name for e in line.events} if is_collective(n)}
            return set()
    return set()


@functools.lru_cache(maxsize=1)
def _names(path: str, mtime_ns: int) -> frozenset:
    import jax

    return frozenset(collective_names(
        jax.profiler.ProfileData.from_file(path).planes))


def load_names(trace_dir) -> set:
    """:func:`collective_names` of the newest ``.xplane.pb`` under
    ``trace_dir``, read once per file."""
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return set()
    return set(_names(files[-1], os.stat(files[-1]).st_mtime_ns))


def in_flight(ops: list, names: set) -> list:
    """``(start, end)`` of each collective in flight among one device's
    operations ``(start_s, end_s, name)``, sorted by start."""
    out = []
    open_starts: dict = defaultdict(deque)
    for s, e, name in sorted(o for o in ops if o[2] in names):
        pair = _ASYNC.match(name)
        key = pair and (pair.group(1), pair.group(3))
        if pair is None:
            out.append((s, e))
        elif pair.group(2) == "start":
            open_starts[key].append(s)
        else:
            out.append((open_starts[key].popleft() if open_starts[key]
                        else s, e))
    return sorted(out)


def _merged(starts, ends) -> tuple:
    """The union of intervals sorted by start, as arrays of the merged
    pieces' starts and ends."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    reach = np.maximum.accumulate(ends)
    new = np.ones(len(starts), bool)
    new[1:] = starts[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], len(starts)) - 1
    return starts[first], reach[last]


def _covered(starts, ends):
    """A function giving, for each time of an array, the seconds of the
    merged pieces ``starts``/``ends`` before it."""
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def upto(t):
        i = np.searchsorted(starts, t, side="right") - 1
        j = np.maximum(i, 0)
        inside = np.clip(t - starts[j], 0, ends[j] - starts[j]) \
            if len(starts) else np.zeros_like(t)
        return np.where(i >= 0, before[j] + inside, 0.0)

    return upto


def per_step(device: xplane.DeviceTrace, names: set, lo: float,
             hi: float) -> list:
    """``(collective_s, exposed_s)`` of each train-step program run of
    ``device`` wholly inside [lo, hi]."""
    steps = [p for p in device.programs
             if re.search(TRAIN_STEP, p[2]) and p[0] >= lo and p[1] <= hi]
    if not steps:
        return []
    ops = device.ops
    s = np.array([o[0] for o in ops])
    e = np.array([o[1] for o in ops])
    coll = np.array([o[2] in names for o in ops], bool)
    # an operation that holds the next one (a loop around its body) is no
    # compute of its own; on a chip's core operations only nest
    order = np.lexsort((-e, s))
    s, e, coll = s[order], e[order], coll[order]
    holds = np.zeros(len(s), bool)
    holds[:-1] = (s[1:] < e[:-1]) & (e[1:] > s[1:])
    work = ~coll & ~holds & (e > s)
    compute = _covered(*_merged(s[work], e[work]))
    pieces = np.array(in_flight(ops, names), float).reshape(-1, 2)
    f_lo, f_hi = _merged(pieces[:, 0], pieces[:, 1])
    out = []
    for a, b, _ in steps:
        i, j = np.searchsorted(f_hi, a), np.searchsorted(f_lo, b)
        lo_f, hi_f = np.clip(f_lo[i:j], a, b), np.clip(f_hi[i:j], a, b)
        held = float(np.sum(hi_f - lo_f))
        under = float(np.sum(compute(hi_f) - compute(lo_f)))
        out.append((held, held - under))
    return out


def summary_ms(devices: list, names: set, lo: float, hi: float,
               which: int) -> float | None:
    """The median over the train steps in [lo, hi] of a device's collective
    time (``which`` 0) or exposed collective time (1), mean over the
    devices, ms; ``None`` where no step holds a collective."""
    medians = []
    for device in devices:
        rows = per_step(device, names, lo, hi)
        if any(held for held, _ in rows):
            medians.append(xplane.median(row[which] for row in rows))
    if not medians:
        return None
    return 1e3 * sum(medians) / len(medians)


def step_ms(run, cell, which: int) -> float | None:
    """:func:`summary_ms` of the run's window; ``None`` without a trace
    that has a device plane."""
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    names = load_names(cell.root / TRACE_DIR.name / cell.name)
    return summary_ms(run.trace.devices, names, *window, which)
