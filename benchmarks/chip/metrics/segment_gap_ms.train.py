"""Median time from one ``train_steps`` return to the next call's start:
the flows service and the compute endpoint between two segments."""

from benchmarks.chip.readers import median_ms
from benchmarks.chip.training import gaps_between_segments


def read(run, cell):
    return median_ms(gaps_between_segments(run))
