"""Median device-idle time between consecutive train-step programs of one
segment: the step's host sync (``device_get`` of the step counter) and the
host's batch build in ``TrainingFabric.train_steps``."""

from benchmarks.chip import xplane
from benchmarks.chip.readers import TRAIN_STEP, median_ms


def read(run, cell):
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    dev = run.trace.devices[0]
    steps = xplane.programs_matching(dev, TRAIN_STEP, *window)
    segments = [(s, e) for s, e, n in run.trace.spans
                if n.startswith("bench:train_steps ")]
    gaps = []
    for a, b in zip(steps, steps[1:]):
        if any(s <= a[0] and b[1] <= e for s, e in segments):
            gaps += xplane.idle_between(dev, [a, b])
    return median_ms(gaps)
