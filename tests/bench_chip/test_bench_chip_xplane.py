"""The chip benchmark's trace reduction, on synthesized and recorded traces."""

from __future__ import annotations

import glob
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import xplane

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def synthesized():
    """One device: prefill 0-10 ms, decodes 12-16, 20-24, 30-34 ms with an
    argmax op between them; host spans for the window and one served batch."""
    ops = [ev("%fusion.1 = f32[8] fusion(x)", 0, 10),
           ev("%fusion.2 = bf16[8] fusion(y)", 12, 4),
           ev("%argmax.3 = s32[8] reduce(z)", 17, 1),
           ev("%fusion.2 = bf16[8] fusion(y)", 20, 4),
           ev("%fusion.2 = bf16[8] fusion(y)", 30, 4)]
    programs = [ev("jit__lambda(123)", 0, 10), ev("jit_decode_step(9)", 12, 4),
                ev("jit__argmax(7)", 17, 1), ev("jit_decode_step(9)", 20, 4),
                ev("jit_decode_step(9)", 30, 4)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=programs), NS(name="XLA Ops", events=ops),
        NS(name="Async XLA Ops", events=[ev("%copy-start", 0, 40)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench:window", 0, 40), ev("bench:serve S=128 B=8", 0, 35),
        ev("PjitFunction(f)", 1, 1)])])
    other = NS(name="/device:CUSTOM:Megascale Trace", lines=[
        NS(name="XLA Ops", events=[ev("x", 0, 40)])])
    return xplane.from_planes([device, host, other])


def test_planes_are_split_into_devices_and_benchmark_spans():
    t = synthesized()
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert [p[2] for p in t.devices[0].programs] == [
        "jit__lambda", "jit_decode_step", "jit__argmax", "jit_decode_step",
        "jit_decode_step"]
    assert t.devices[0].ops[0][2] == "fusion.1"
    assert t.spans == [(0.0, 0.035, "bench:serve S=128 B=8"),
                       (0.0, 0.040, "bench:window")]


def test_busy_union_and_idle_gaps():
    dev = synthesized().devices[0]
    assert xplane.busy_seconds(dev.ops, 0, 0.040) == pytest.approx(0.023)
    assert xplane.busy_seconds(dev.ops, 0.005, 0.021) == pytest.approx(0.011)
    gaps = [t for gap in xplane.idle_gaps(dev.ops, 0, 0.040) for t in gap]
    assert gaps == pytest.approx([0.010, 0.012, 0.016, 0.017, 0.018, 0.020,
                                  0.024, 0.030, 0.034, 0.040])
    overlapping = [(0, 2, "a"), (1, 3, "b"), (5, 6, "c")]
    assert xplane.union(overlapping, 0, 10) == [(0, 3), (5, 6)]
    index = xplane.BusyIndex(dev)
    for lo, hi in [(0, 0.04), (0.011, 0.0175), (0.013, 0.031), (0.05, 0.06)]:
        assert index.busy(lo, hi) == pytest.approx(
            xplane.busy_seconds(dev.ops, lo, hi))


def test_per_program_runs_gaps_and_medians():
    dev = synthesized().devices[0]
    decodes = xplane.programs_matching(dev, "decode", 0, 0.040)
    assert len(decodes) == 3
    # 16-20 ms holds 1 ms of argmax, so 3 ms idle; 24-30 ms is all idle
    assert xplane.idle_between(dev, decodes) == pytest.approx([0.003, 0.006])
    assert xplane.median(p[1] - p[0] for p in decodes) == pytest.approx(0.004)
    assert xplane.median([]) is None
    # a run only partly inside the window is left out
    assert len(xplane.programs_matching(dev, "decode", 0, 0.032)) == 2


def test_breakdown_names_ops_by_program_and_gaps_by_host_span():
    t = synthesized()
    b = xplane.breakdown(t, 0, 0.040)
    ops = dict(b["device_ops"])
    assert ops["jit_decode_step/fusion.2"] == pytest.approx(0.012)
    assert ops["jit__lambda/fusion.1"] == pytest.approx(0.010)
    assert ops["jit__argmax/argmax.3"] == pytest.approx(0.001)
    gaps = dict(b["idle_gaps"])
    # gaps whose middle lies before 35 ms are in the serve span; 34-40 ms
    # has its middle in the window only
    assert gaps["serve"] == pytest.approx(0.011)
    assert gaps["window"] == pytest.approx(0.006)
    assert sum(gaps.values()) == pytest.approx(0.040 - 0.023)
    assert xplane.device_busy(t, 0, 0.040) == pytest.approx(0.023)


def test_span_at_picks_the_innermost_span():
    spans = [(0, 10, "bench:window"), (2, 4, "bench:serve S=1 B=1")]
    assert xplane.span_at(spans, 3) == "serve"
    assert xplane.span_at(spans, 5) == "window"
    assert xplane.span_at(spans, 11) == "none"


def test_a_recorded_trace_loads_with_its_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:anchor"):
        pass
    with jax.profiler.TraceAnnotation("bench:window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    t = xplane.load(str(tmp_path))
    names = [s[2] for s in t.spans]
    assert "bench:anchor" in names and "bench:window" in names
    lo, hi = next((s, e) for s, e, n in t.spans if n == "bench:window")
    assert 0 <= lo < hi
    # the CPU backend has no device plane: nothing to call busy or idle
    assert t.devices == []
    assert xplane.device_busy(t, lo, hi) == 0.0
