"""Median per train step of the time a collective is in flight on a chip
and no other operation runs there, mean over the chips
(``collectives.py``)."""

from benchmarks.chip.collectives import step_ms


def read(run, cell):
    return step_ms(run, cell, 1)
