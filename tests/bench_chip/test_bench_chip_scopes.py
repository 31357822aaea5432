"""Device time by named scope (``benchmarks/chip/scopes.py``): the HLO of
each program read from a real profiler trace's metadata plane, each
instruction mapped to its scope, and leaf operations summed per program
run on a synthesized device line."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, scopes, xplane

SCOPES = {"mla": lambda part: part == "mla",
          "moe": lambda part: part.startswith("moe.")}


@jax.jit
def two_scopes(x, w):
    with jax.named_scope("mla"):
        y = jnp.tanh(x @ w)
    with jax.named_scope("moe.experts"):
        return jnp.sum(jnp.exp(y) * 2.0 + 1.0)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A cell directory holding the trace of a few calls of
    ``two_scopes``, where the harness keeps a traced window's file."""
    root = tmp_path_factory.mktemp("checkout")
    cell = types.SimpleNamespace(root=root, name="cell")
    x = jnp.ones((64, 64))
    two_scopes(x, x).block_until_ready()
    jax.profiler.start_trace(str(root / ".bench_traces" / "cell"))
    for _ in range(2):
        two_scopes(x, x).block_until_ready()
    jax.profiler.stop_trace()
    return cell


def _program(protos: dict) -> tuple[str, bytes]:
    [(name, proto)] = [(k, v) for k, v in protos.items()
                       if xplane.program_name(k) == "jit_two_scopes"]
    return name, proto


def test_the_trace_holds_each_program_s_hlo(traced):
    path = scopes.trace_file(traced)
    assert path is not None
    name, proto = _program(scopes.hlo_protos(path))
    assert name.startswith("jit_two_scopes(")
    by_op = scopes.instruction_scopes(proto, SCOPES)
    assert set(by_op.values()) == {"mla", "moe"}
    assert any(op.startswith("dot") and label == "mla"
               for op, label in by_op.items())
    assert any(op.startswith("exp") and label == "moe"
               for op, label in by_op.items())


def _varint(n):
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def field(number, payload):
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def instr(name, op_name=b"", calls=None):
    """One HLO instruction: its name, ``op_name`` and called computation."""
    body = field(1, name)
    if op_name:
        body += field(7, field(2, op_name))
    if calls is not None:
        body += field(38, bytes([calls]))
    return field(2, body)


def test_a_fusion_without_its_own_scope_takes_its_callees_scope():
    """A fusion instruction with no metadata takes the scope most of the
    instructions of the computation it calls carry."""
    fused = field(3, instr(b"mul.1", b"jit(f)/moe.shared/mul")
                  + instr(b"add.1", b"jit(f)/moe.shared/add")
                  + instr(b"neg.1", b"jit(f)/mla/neg") + b"\x28\x07")
    entry = field(3, instr(b"fusion.3", calls=7)
                  + instr(b"copy.2", b"jit(f)/while/body/copy") + b"\x28\x01")
    proto = field(1, fused + entry)
    assert scopes.instruction_scopes(proto, SCOPES) == {
        "mul.1": "moe", "add.1": "moe", "neg.1": "mla", "fusion.3": "moe"}


def test_the_cell_s_scopes_hold_the_expert_kernels_not_the_scan_s_copies():
    """The moonlight cell's scopes: the ragged matmul kernels, whose
    ``op_name`` is the kernel's own, are the expert layer's; the layer
    scan's slices of the stacked weights and cache are neither's."""
    from benchmarks.chip.readers_mla_moe import SCOPES as cell_scopes

    body = (instr(b"fusion.7", b"jit(decode_step)/while/body/closed_call/"
                  b"mla/dot_general")
            + instr(b"ragged-dot-none.1", b"ragged-dot-none")
            + instr(b"fusion.9", b"jit(decode_step)/while/body/closed_call/"
                    b"moe.shared/mul")
            + instr(b"dynamic-slice_bitcast_fusion.12",
                    b"jit(decode_step)/while/body/squeeze"))
    proto = field(1, field(3, body + b"\x28\x01"))
    assert scopes.instruction_scopes(proto, cell_scopes) == {
        "fusion.7": "mla", "ragged-dot-none.1": "moe", "fusion.9": "moe"}


def test_leaf_operations_leave_out_the_loops_that_hold_them():
    ops = [(0.0, 10.0, "while.4"), (1.0, 2.0, "fusion.1"),
           (2.0, 4.0, "fusion.2"), (4.0, 9.0, "while.5"),
           (5.0, 6.0, "fusion.3"), (11.0, 12.0, "copy.1")]
    assert [o[2] for o in scopes.leaf_ops(ops)] == [
        "fusion.1", "fusion.2", "fusion.3", "copy.1"]


def test_leaf_time_is_summed_by_scope_for_each_program_run(traced):
    name, proto = _program(scopes.hlo_protos(scopes.trace_file(traced)))
    by_op = scopes.instruction_scopes(proto, SCOPES)
    mla = next(op for op, label in by_op.items() if label == "mla")
    moe = next(op for op, label in by_op.items() if label == "moe")
    program = xplane.program_name(name)
    dev = xplane.DeviceTrace(
        "/device:TPU:0",
        ops=[(1.0, 5.0, "while.1"), (1.0, 2.0, mla), (2.0, 2.5, moe),
             (3.0, 4.0, moe), (6.0, 6.5, mla), (6.5, 8.0, "copy.9"),
             (20.0, 21.0, mla)],
        programs=[(1.0, 5.0, program), (6.0, 8.0, program),
                  (20.0, 21.0, "jit_other")])
    run = harness.RunResult(
        setup_s=0.0, t0=0.0, t_end=30.0, seconds=30.0, attempted=1,
        failed=0, correct=True, checks={}, memory_peak_bytes=0,
        trace=xplane.TraceSummary(devices=[dev], spans=[]))
    times = scopes.scope_seconds(run, traced, "two_scopes", SCOPES)
    assert times == [{"mla": 1.0, "moe": 1.5}, {"mla": 0.5}]


def test_nothing_to_read_reads_nothing(traced, tmp_path):
    run = harness.RunResult(
        setup_s=0.0, t0=0.0, t_end=1.0, seconds=1.0, attempted=1, failed=0,
        correct=True, checks={}, memory_peak_bytes=0)
    assert scopes.scope_seconds(run, traced, "two_scopes", SCOPES) == []
    bare = types.SimpleNamespace(root=tmp_path, name="cell")
    assert scopes.trace_file(bare) is None


def test_a_name_that_several_modules_share_is_not_read(traced, monkeypatch):
    """The trace's program runs carry no fingerprint, so two modules of
    one name (a program compiled for two shapes) cannot be told apart."""
    name, proto = _program(scopes.hlo_protos(scopes.trace_file(traced)))
    program = xplane.program_name(name)
    monkeypatch.setattr(scopes, "hlo_protos", lambda path: {
        f"{program}(1)": proto, f"{program}(2)": proto})
    dev = xplane.DeviceTrace("/device:TPU:0", ops=[(1.0, 2.0, "dot.1")],
                             programs=[(1.0, 2.0, program)])
    run = harness.RunResult(
        setup_s=0.0, t0=0.0, t_end=3.0, seconds=3.0, attempted=1, failed=0,
        correct=True, checks={}, memory_peak_bytes=0,
        trace=xplane.TraceSummary(devices=[dev], spans=[]))
    assert scopes.scope_seconds(run, traced, "two_scopes", SCOPES) == []
