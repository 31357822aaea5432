"""The readers of the program's own spans, on synthesized planes and on a
recorded trace: device-idle time inside a span kind, self time less
children, the median of ``queued_ms``, the window's edges, and ``None``
where there is nothing to read."""

from __future__ import annotations

import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import harness, program_spans, xplane

MS = 1_000_000  # ns


def ev(name, start_ms, end_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS,
              duration_ns=(end_ms - start_ms) * MS, stats=list(stats.items()))


def device(*busy):
    ops = [ev(f"%fusion.{i} = f32[8] fusion(x)", s, e)
           for i, (s, e) in enumerate(busy)]
    return NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])


def host(*lines):
    return NS(name="/host:CPU", lines=[NS(name="python", events=list(line))
                                      for line in lines])


def run_of(planes, t0_ms, t_end_ms, monkeypatch):
    """A finished run whose trace holds ``planes``, on the host's clock."""
    monkeypatch.setattr(program_spans, "load",
                        lambda _: program_spans.from_planes(planes))
    return harness.RunResult(
        setup_s=1.0, t0=t0_ms / 1e3, t_end=t_end_ms / 1e3, seconds=1.0,
        attempted=1, failed=0, correct=True, checks={}, memory_peak_bytes=0,
        trace=xplane.from_planes(planes))


CELL = NS(root=Path("."), name="cell")  # its trace is read by ``load``
# ms: decode steps run 0-10, 14-24 and 27-37 with a 1 ms argmax after
# each of the first two, so the device idles 11-14, 25-27 and 37-40
DECODE = [device((0, 10), (10, 11), (14, 24), (24, 25), (27, 37)),
          host([ev("compute.run", 0, 45, request="r1:Serve:0", queued_ms=2.5),
                ev("bench:serve S=8 B=1", 0.5, 44),
                ev("serve.pull", 1, 12), ev("serve.decode", 12.5, 14.5),
                ev("serve.pull", 15, 26.5), ev("serve.decode", 26.5, 27.5),
                ev("serve.pull", 28, 39.5), ev("serve.decode", 39.5, 41)])]


def test_idle_inside_a_span_kind_is_its_median_per_step(monkeypatch):
    run = run_of(DECODE, 0, 45, monkeypatch)
    # pulls hold 1, 1.5 and 2.5 ms of idle; decodes 1.5, 0.5 and 1.5
    assert program_spans.idle_ms(run, CELL, "serve.pull") == pytest.approx(1.5)
    assert program_spans.idle_ms(run, CELL, "serve.decode") == \
        pytest.approx(1.5)
    assert program_spans.idle_ms(run, CELL, "train.sync") is None


def test_spans_across_the_window_edge_are_left_out(monkeypatch):
    run = run_of(DECODE, 0, 40, monkeypatch)
    names = [s.name for s in program_spans.in_window(run, CELL)]
    assert names == ["serve.pull", "serve.decode", "serve.pull",
                     "serve.decode", "serve.pull"]
    assert program_spans.idle_ms(run, CELL, "serve.decode") == \
        pytest.approx(1.0)
    late = run_of(DECODE, 13, 45, monkeypatch)
    assert program_spans.idle_ms(late, CELL, "serve.pull") == \
        pytest.approx(2.0)


def test_feed_idle_sums_the_batch_and_dispatch_of_one_step(monkeypatch):
    planes = [device((0, 10), (13, 23), (27, 37)),
              host([ev("train.sync", 9, 10.5),
                    ev("train.batch", 10.5, 12, step=4),
                    ev("train.dispatch", 12, 12.5, step=4),
                    ev("train.sync", 12.5, 23.5),
                    ev("train.batch", 23.5, 26, step=5),
                    ev("train.dispatch", 26, 26.5, step=5),
                    ev("train.sync", 26.5, 37.2),
                    ev("train.batch", 37.2, 38, step=6),
                    ev("train.dispatch", 38, 38.2, step=6)])]
    run = run_of(planes, 0, 40, monkeypatch)
    # steps 4, 5 and 6 idle 2, 3 and 1 ms between batch and dispatch
    assert program_spans.idle_per_step_ms(
        run, CELL, ("train.batch", "train.dispatch")) == pytest.approx(2.0)
    # the syncs idle 0.5, 1.0 and 0.7 ms before their step starts and
    # after the one they wait for ends
    assert program_spans.idle_ms(run, CELL, "train.sync") == \
        pytest.approx(0.7)


# ms: the engine's thread runs r1 and r2 whole inside the window, r3 only
# starts there; r2's action runs inline, r1's on a worker thread
ENGINE = [
    ev("flows.start", 0, 2, run="r1"), ev("journal.append", 0.5, 1.5, run="r1"),
    ev("flows.enter", 3, 6, run="r1"), ev("journal.append", 3.2, 3.7, run="r1"),
    ev("flows.dispatch", 4, 5, run="r1", request="r1:S:0"),
    ev("flows.start", 7, 8, run="r2"), ev("flows.enter", 8.5, 9.5, run="r2"),
    ev("flows.dispatch", 8.6, 9.4, run="r2", request="r2:S:0"),
    ev("compute.run", 8.7, 9.3, request="r2:S:0", queued_ms=0.0),
    ev("flows.finish", 20, 24, run="r1"), ev("journal.append", 20.5, 21, run="r1"),
    ev("flows.complete", 22, 23.5, run="r1"),
    ev("journal.append", 22.5, 23, run="r1"),
    ev("flows.finish", 30, 31, run="r2"),
    ev("flows.complete", 30.2, 30.8, run="r2"),
    ev("flows.start", 33, 34, run="r3")]
WORKER = [ev("compute.run", 10, 19, request="r1:S:0", queued_ms=2.0),
          ev("serve.prefill", 10.5, 18.5, rows=8, length=128),
          ev("compute.run", 35, 45, request="r3:S:0", queued_ms=7.0)]
SCORE = [device((10, 18)), host(ENGINE, WORKER)]


def test_self_time_is_the_duration_less_the_children_on_its_line():
    spans = program_spans.from_planes(SCORE)
    by = {(s.name, round(s.start * 1e3, 6)): s.self_s * 1e3 for s in spans}
    assert by["flows.enter", 3] == pytest.approx(1.5)       # 3 - 0.5 - 1
    assert by["flows.dispatch", 8.6] == pytest.approx(0.2)  # inline compute
    assert by["flows.finish", 20] == pytest.approx(2.0)     # 4 - 0.5 - 1.5
    assert by["flows.complete", 22] == pytest.approx(1.0)
    assert by["compute.run", 10] == pytest.approx(1.0)      # another line
    assert by["journal.append", 0.5] == pytest.approx(1.0)


def test_transition_self_time_is_per_flow_that_lies_in_the_window(
        monkeypatch):
    run = run_of(SCORE, 0, 40, monkeypatch)
    # r1: flows and journal cover 2 + 3 + 4 ms; r2: 1 + 1 + 1 less its
    # inline function's 0.6; r3 does not complete in the window
    assert program_spans.transition_self_ms(run, CELL) == \
        pytest.approx((9.0 + 2.4) / 2)


def test_endpoint_queue_is_the_median_queued_ms_in_the_window(monkeypatch):
    run = run_of(SCORE, 0, 40, monkeypatch)
    assert program_spans.endpoint_queue_ms(run, CELL) == pytest.approx(1.0)
    run = run_of(SCORE, 0, 50, monkeypatch)
    assert program_spans.endpoint_queue_ms(run, CELL) == pytest.approx(2.0)


def test_nothing_to_read_without_a_device_trace_or_spans(monkeypatch):
    run = run_of(SCORE, 0, 40, monkeypatch)
    untraced = harness.RunResult(
        setup_s=1.0, t0=0.0, t_end=0.04, seconds=1.0, attempted=1, failed=0,
        correct=True, checks={}, memory_peak_bytes=0)
    no_device = run_of([host(ENGINE, WORKER)], 0, 40, monkeypatch)
    for r in (untraced, no_device):
        assert program_spans.in_window(r, CELL) is None
        assert program_spans.transition_self_ms(r, CELL) is None
        assert program_spans.endpoint_queue_ms(r, CELL) is None
        assert program_spans.idle_ms(r, CELL, "serve.pull") is None
        assert program_spans.window_compiles(r) is None
    # a program older than its spans leaves the readers nothing
    older = run_of([device((10, 18)), host([ev("bench:submit", 1, 2)])], 0,
                   40, monkeypatch)
    assert program_spans.in_window(older, CELL) == []
    assert program_spans.transition_self_ms(older, CELL) is None
    assert program_spans.endpoint_queue_ms(older, CELL) is None
    assert program_spans.idle_per_step_ms(older, CELL, ("train.batch",)) \
        is None
    assert run.trace.devices


def test_window_compiles_counts_the_programs_lowered_in_the_window(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro import obs

    with obs.span("train.dispatch"):  # the log starts once JAX is loaded
        pass

    def compiled_in_window(x):
        return x * 5 - 2

    f = jax.jit(compiled_in_window)
    x = jnp.arange(3.0).block_until_ready()
    t0 = time.time()
    f(x).block_until_ready()
    f(x).block_until_ready()
    run = run_of(SCORE, t0 * 1e3, time.time() * 1e3, monkeypatch)
    assert program_spans.window_compiles(run) == 1
    run = run_of(SCORE, time.time() * 1e3, time.time() * 1e3 + 1, monkeypatch)
    f(x).block_until_ready()
    assert program_spans.window_compiles(run) == 0


def test_a_recorded_trace_loads_with_the_programs_spans(tmp_path):
    import jax

    from repro import obs

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with obs.span("flows.enter", run="run-1"):
        with obs.span("journal.append", run="run-1"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench:serve S=8 B=1"):
            with obs.span("compute.run", request="run-1:S:0", queued_ms=1.5):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    spans = program_spans.load(tmp_path)
    assert [s.name for s in spans] == ["flows.enter", "journal.append",
                                       "compute.run"]
    enter, append, compute = spans
    assert enter.meta == {"run": "run-1"}
    assert compute.meta == {"request": "run-1:S:0", "queued_ms": 1.5}
    assert enter.line == append.line == compute.line
    # the benchmark's own span between them is not a child of the program's
    assert enter.self_s == pytest.approx(
        enter.end - enter.start - (append.end - append.start)
        - (compute.end - compute.start))
    assert program_spans.load(tmp_path / "nothing") == []
