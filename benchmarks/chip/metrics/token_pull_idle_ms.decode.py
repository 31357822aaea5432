"""Median per decode step of the device-idle time inside ``serve.pull``:
the sampling of the step's token and its copy to the host, one part of
``decode_gap_ms.decode``."""

from benchmarks.chip.program_spans import idle_ms


def read(run, cell):
    return idle_ms(run, cell, "serve.pull")
