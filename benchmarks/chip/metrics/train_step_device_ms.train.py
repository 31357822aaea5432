"""Median device time of the train-step program."""

from benchmarks.chip import xplane
from benchmarks.chip.readers import TRAIN_STEP, median_ms


def read(run, cell):
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    steps = xplane.programs_matching(run.trace.devices[0], TRAIN_STEP, *window)
    return median_ms(p[1] - p[0] for p in steps)
