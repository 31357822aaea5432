"""Post-optimization HLO text analysis: collective bytes for the roofline.

``cost_analysis()`` does not expose collective traffic, so we parse the
compiled module text and sum operand/result sizes of every collective op
(per-partition shapes — i.e. per-device bytes).  Wire-byte estimates use the
standard ring-algorithm factors: all-reduce moves ~2x its operand bytes,
gathers/scatters ~1x.

Two-pass parse: (1) map every instruction name to its result bytes; (2) for
each collective, resolve operand names through that map (post-opt HLO prints
operands as bare ``%name`` references).
"""

from __future__ import annotations

import re
from collections import defaultdict


DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all", "collective-broadcast",
)

_SHAPE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?.*?)\s+([a-z][a-z0-9\-]*)\("
)
_OPERAND = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = DTYPE_BYTES.get(dtype)
    if n is None:
        return 0
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _types_bytes(text: str) -> int:
    return sum(_shape_bytes(d, s) for d, s in _SHAPE.findall(text))


def analyze_collectives(hlo_text: str, top_n: int = 12) -> dict:
    """Per-collective stats from post-SPMD HLO text (per-device bytes)."""
    # pass 1: every instruction's result bytes
    result_bytes: dict[str, int] = {}
    instrs: list[tuple[str, str, str, str]] = []  # (name, type_str, op, line)
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, type_str, opcode = m.group(1), m.group(2), m.group(3)
        result_bytes[name] = _types_bytes(type_str)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in COLLECTIVES:
            instrs.append((name, type_str, base, line[m.end() - 1:]))

    stats: dict[str, dict] = defaultdict(
        lambda: {"count": 0, "operand_bytes": 0, "result_bytes": 0}
    )
    ops: list[dict] = []
    for name, type_str, base, args in instrs:
        res = result_bytes.get(name, 0)
        arg_str = args.split("),", 1)[0]
        inline = _types_bytes(arg_str)
        if inline:
            operand = inline
        else:
            operand = sum(
                result_bytes.get(op_name, 0)
                for op_name in _OPERAND.findall(arg_str)
            )
        rec = stats[base]
        rec["count"] += 1
        rec["operand_bytes"] += operand
        rec["result_bytes"] += res
        wire = 2 * operand if base == "all-reduce" else max(operand, res)
        ops.append({"op": base, "name": name, "operand_bytes": operand,
                    "result_bytes": res, "wire_bytes": wire})

    wire_total = sum(o["wire_bytes"] for o in ops)
    out = dict(stats)
    out["_total"] = {
        "count": sum(r["count"] for r in stats.values()),
        "wire_bytes_per_device": wire_total,
    }
    ops.sort(key=lambda o: -o["wire_bytes"])
    out["_top_ops"] = ops[:top_n]
    return out


def count_instructions(hlo_text: str, opcodes: tuple[str, ...]) -> dict:
    counts = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = m.group(3)
            if op in opcodes:
                counts[op] += 1
    return dict(counts)
