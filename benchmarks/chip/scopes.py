"""Device time of a program's operations by the named scope
(``jax.named_scope``) they were traced under.

The trace's ``XLA Ops`` events name an HLO instruction (``fusion.12``) and
carry no scope.  The scope is in the instruction's metadata (``op_name``,
e.g. ``jit(decode_step)/while/body/mla/dot_general``), and the profiler
stores each program's HLO (an ``HloProto``) on the trace's
``/host:metadata`` plane, under the program's name as the ``XLA Modules``
line shows it.  This module reads those protos straight from the
``.xplane.pb`` file's wire format (only the few fields it needs), maps
each instruction to its scope, and sums the device time of the leaf
operations of each program run by scope.

Every function returns an empty result where the trace holds nothing to
read, so a metric built on it reads ``None``.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os

from benchmarks.chip import xplane

# field numbers: tensorflow/tsl/profiler/protobuf/xplane.proto and
# xla/service/hlo.proto, xla/xla_data.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_ID, _STAT_META_NAME = 1, 2
_STAT_META_REF, _STAT_BYTES = 1, 6
_HLO_PROTO_MODULE = 1
_MODULE_COMPUTATIONS = 3
_COMPUTATION_INSTRUCTIONS, _COMPUTATION_ID = 2, 5
_INSTR_NAME, _INSTR_METADATA, _INSTR_CALLED = 1, 7, 38
_OP_NAME = 2

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


# ------------------------------------------------------------- wire format

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a ``memoryview`` for a length-delimited field;
    fixed-width fields are skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")


def _packed(value) -> list[int]:
    """A repeated varint field, packed (bytes) or one element (an int)."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


# -------------------------------------------------------------- HLO protos

@functools.lru_cache(maxsize=2)
def hlo_protos(path: str) -> dict[str, bytes]:
    """``{program name: HloProto bytes}`` from the trace's metadata plane,
    the name as the ``XLA Modules`` line shows it (``jit_f(5)``); empty
    where the file holds none or cannot be read as a trace."""
    try:
        return _hlo_protos(path)
    except (OSError, ValueError, IndexError):
        return {}


def _hlo_protos(path: str) -> dict[str, bytes]:
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for number, plane in fields(space):
        if number != _SPACE_PLANES:
            continue
        name, metas, stat_names = None, [], {}
        for k, v in fields(plane):
            if k == _PLANE_NAME:
                name = _text(v)
                if name != METADATA_PLANE:
                    break
            elif k == _PLANE_EVENT_META:
                metas.append(v)
            elif k == _PLANE_STAT_META:
                sm = dict(fields(dict(fields(v)).get(2, b"")))
                stat_names[sm.get(_STAT_META_ID)] = _text(
                    sm.get(_STAT_META_NAME, b""))
        if name != METADATA_PLANE:
            continue
        for entry in metas:
            meta = dict(fields(entry)).get(2)
            if meta is None:
                continue
            program, proto = None, None
            for k, v in fields(meta):
                if k == _EVENT_META_NAME:
                    program = _text(v)
                elif k == _EVENT_META_STATS:
                    stat = dict(fields(v))
                    if (stat_names.get(stat.get(_STAT_META_REF))
                            == HLO_PROTO_STAT and _STAT_BYTES in stat):
                        proto = bytes(stat[_STAT_BYTES])
            if program and proto:
                out[program] = proto
    return out


def instruction_scopes(hlo_proto: bytes, scopes: dict) -> dict[str, str]:
    """``{instruction name: scope}`` of one program.  ``scopes`` maps a
    label to a test of one component of an ``op_name`` path; an
    instruction takes the label of the first matching component of its
    own ``op_name``, or, where it has none (a fusion), the label most of
    the instructions it calls take."""
    module = dict(fields(hlo_proto)).get(_HLO_PROTO_MODULE)
    if module is None:
        return {}
    own, called, members = {}, {}, {}
    for k, comp in fields(module):
        if k != _MODULE_COMPUTATIONS:
            continue
        comp_id, names = None, []
        for ck, cv in fields(comp):
            if ck == _COMPUTATION_ID:
                comp_id = cv
            if ck != _COMPUTATION_INSTRUCTIONS:
                continue
            name, op_name, calls = None, "", []
            for ik, iv in fields(cv):
                if ik == _INSTR_NAME:
                    name = _text(iv)
                elif ik == _INSTR_METADATA:
                    op_name = _text(dict(fields(iv)).get(_OP_NAME, b""))
                elif ik == _INSTR_CALLED:
                    calls += _packed(iv)
            if name is None:
                continue
            names.append(name)
            own[name] = _label(op_name, scopes)
            called[name] = calls
        members[comp_id] = names
    out = {}
    for name, label in own.items():
        if label is None:
            votes = [own[m] for c in called[name] for m in members.get(c, ())
                     if own.get(m)]
            label = max(set(votes), key=votes.count) if votes else None
        if label is not None:
            out[name] = label
    return out


def _label(op_name: str, scopes: dict) -> str | None:
    for part in op_name.split("/"):
        for label, test in scopes.items():
            if test(part):
                return label
    return None


# ------------------------------------------------------------------- times

def leaf_ops(ops: list) -> list:
    """The operations that hold no other (a loop's event spans its body's
    operations): ``(start, end, name)`` sorted by start."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[0] >= op[1]:
            out.append(op)
    return out


def trace_file(cell) -> str | None:
    """The cell's traced window's ``.xplane.pb``, the file
    ``xplane.load`` reads."""
    root = os.path.join(str(cell.root), ".bench_traces", cell.name)
    files = sorted(glob.glob(os.path.join(root, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return files[-1] if files else None


def scope_seconds(run, cell, pattern: str, scopes: dict) -> list[dict]:
    """For each run of a program matching ``pattern`` in the window, on
    the first chip: ``{label: device seconds of its leaf operations}``."""
    window = run.trace_window()
    path = trace_file(cell)
    if window is None or not run.trace.devices or path is None:
        return []
    dev = run.trace.devices[0]
    runs = xplane.programs_matching(dev, pattern, *window)
    if not runs:
        return []
    # the trace's program runs carry the name without the module's
    # fingerprint, so a name that several modules share is not read
    modules: dict = {}
    for program, proto in hlo_protos(path).items():
        modules.setdefault(xplane.program_name(program), []).append(proto)
    maps = {}
    for name, protos in modules.items():
        if len(protos) == 1 and any(name == r[2] for r in runs):
            try:
                maps[name] = instruction_scopes(protos[0], scopes)
            except (ValueError, IndexError):
                continue
    if not maps:
        return []
    leaves = leaf_ops([o for o in dev.ops if window[0] <= o[0] < window[1]])
    starts = [o[0] for o in leaves]
    out = []
    for lo, hi, name in runs:
        by_op = maps.get(name, {})
        times: dict = {}
        for s, e, op in leaves[bisect.bisect_left(starts, lo):
                               bisect.bisect_left(starts, hi)]:
            label = by_op.get(op)
            if label is not None:
                times[label] = times.get(label, 0.0) + (min(e, hi) - s)
        out.append(times)
    return out
