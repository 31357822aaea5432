"""The collective reader on a synthesized four-device trace: a collective
wholly under compute is all hidden, one half outside compute is exposed by
that half, and operations that are not collectives (``fusion.12``, a loop
around the step's operations, a copy) are never counted."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import collectives, xplane

MS = 1_000_000  # ns


def ev(text, start_ms, dur_ms):
    return NS(name=text, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def op(name, start_ms, dur_ms, calls=None):
    """An ``XLA Ops`` event named as the TPU's trace names them."""
    tail = f", kind=kCustom, calls=%{calls}" if calls else ""
    return ev(f"%{name} = bf16[8]{{0}} {name.split('.')[0]}(%p){tail}",
              start_ms, dur_ms)


def step(t0, exposed_ms):
    """One 10 ms train step from ``t0``, its operations in a loop: an
    asynchronous all-gather in flight from 1 to 5 ms, with a compute
    fusion beside it for all but the last ``exposed_ms`` of that, which
    its done op waits out; then ops that are no collectives."""
    hidden = 4 - exposed_ms
    return [op("while.3", t0, 9.5),
            op("fusion.1", t0, 1),
            op("async-collective-start.1", t0 + 1, 0,
               "fused_computation.363"),
            op("fusion.510", t0 + 1, hidden, "async_collective_fusion.510"),
            op("async-collective-done.1", t0 + 1 + hidden, exposed_ms,
               "fused_computation.364"),
            op("fusion.12", t0 + 5, 3),
            op("copy-start.20", t0 + 8, 1)]


def device(k, exposed):
    ops, programs = [], []
    for i, x in enumerate(exposed):
        t0 = 20.0 * i
        ops += step(t0, x)
        programs.append(ev("jit_train_step(42)", t0, 10))
    return NS(name=f"/device:TPU:{k}", lines=[
        NS(name="XLA Modules", events=programs),
        NS(name="XLA Ops", events=ops)])


def four_devices(per_device):
    """The devices' traces and the collectives' names, as the reader gets
    them from a run."""
    planes = [device(k, x) for k, x in enumerate(per_device)] + [
        NS(name="/host:CPU", lines=[])]
    return xplane.from_planes(planes).devices, \
        collectives.collective_names(planes)


def test_names_that_are_collectives_and_names_that_are_not():
    yes = ["%all-gather-start.3 = bf16[8] all-gather-start(%p)",
           "all-reduce.5", "reduce-scatter-fusion.1", "collective-permute-done",
           "%all-to-all.2 = f32[4] all-to-all(%x)",
           "%async-collective-done = bf16[8] fusion(%x), calls=%fc.364",
           "%fusion.472 = bf16[8] fusion(%p), calls=%all-reduce-scatter.6"]
    no = ["fusion.12", "%fusion.12 = bf16[8] fusion(%p), calls=%fused.12",
          "%fusion.510 = bf16[8] fusion(%p), calls=%async_collective_fusion.5",
          "convolution.3", "copy-start.20", "while.3"]
    assert all(collectives.is_collective(n) for n in yes)
    assert not any(collectives.is_collective(n) for n in no)
    _, names = four_devices([[0.0]])
    assert names == {"async-collective-start.1", "async-collective-done.1"}


def test_a_collective_under_compute_is_hidden_and_counted_whole():
    devices, names = four_devices([[0.0]] * 4)
    assert len(devices) == 4
    for dev in devices:
        [(held, exposed)] = collectives.per_step(dev, names, -1.0, 1.0)
        assert held == pytest.approx(0.004)
        assert exposed == pytest.approx(0.0, abs=1e-12)


def test_a_collective_half_outside_compute_reads_that_half():
    devices, names = four_devices([[2.0]] * 4)
    [(held, exposed)] = collectives.per_step(devices[0], names, -1.0, 1.0)
    assert held == pytest.approx(0.004)
    assert exposed == pytest.approx(0.002)


def test_a_fusion_that_calls_a_reduce_scatter_is_a_collective():
    devices, names = four_devices([[0.0]])
    dev = devices[0]
    dev.ops.append((0.0095, 0.0099, "fusion.472"))
    names |= collectives.collective_names([NS(
        name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            op("fusion.472", 9.5, 0.4, "all-reduce-scatter.6.clone")])])])
    [(held, exposed)] = collectives.per_step(dev, names, -1.0, 1.0)
    # 0.4 ms past the loop and the copy, with nothing beside it
    assert held == pytest.approx(0.0044)
    assert exposed == pytest.approx(0.0004)


def test_median_over_steps_then_mean_over_devices():
    devices, names = four_devices([[0.0, 1.0, 2.0], [0.5, 0.5, 0.5],
                                   [2.0, 2.0, 0.0], [1.0, 0.0, 1.0]])
    # medians 1.0, 0.5, 2.0, 1.0 ms exposed; 4 ms in flight on each
    assert collectives.summary_ms(devices, names, -1.0, 1.0, 1) == \
        pytest.approx((1.0 + 0.5 + 2.0 + 1.0) / 4)
    assert collectives.summary_ms(devices, names, -1.0, 1.0, 0) == \
        pytest.approx(4.0)
    # a step only partly inside the window is left out
    assert collectives.summary_ms(devices, names, 0.015, 1.0, 1) == \
        pytest.approx((1.5 + 0.5 + 1.0 + 0.5) / 4)


def test_steps_with_no_collective_read_nothing():
    quiet = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_train_step(1)", 0, 10)]),
        NS(name="XLA Ops", events=[op("fusion.12", 0, 4),
                                   op("convolution.3", 5, 4)])])
    devices = xplane.from_planes([quiet]).devices
    names = collectives.collective_names([quiet])
    assert names == set()
    assert collectives.summary_ms(devices, names, -1.0, 1.0, 0) is None
