"""Median per step of the device-idle time inside ``train.sync``: the
host's read of the step counter, which waits for the previous step, one
part of ``step_gap_ms.train``."""

from benchmarks.chip.program_spans import idle_ms


def read(run, cell):
    return idle_ms(run, cell, "train.sync")
