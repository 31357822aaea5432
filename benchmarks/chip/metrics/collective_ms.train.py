"""Median per train step of the time a collective is in flight on a chip,
hidden under compute or not, mean over the chips (``collectives.py``)."""

from benchmarks.chip.collectives import step_ms


def read(run, cell):
    return step_ms(run, cell, 0)
