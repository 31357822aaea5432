"""Median per step of the device-idle time inside ``train.batch`` and
``train.dispatch``: the host's build and upload of the batch and the
dispatch of the step, one part of ``step_gap_ms.train``."""

from benchmarks.chip.program_spans import idle_per_step_ms


def read(run, cell):
    return idle_per_step_ms(run, cell, ("train.batch", "train.dispatch"))
