"""Median per decode step of the device-idle time inside ``serve.decode``:
the upload of the token and position and the dispatch of the step, one
part of ``decode_gap_ms.decode``."""

from benchmarks.chip.program_spans import idle_ms


def read(run, cell):
    return idle_ms(run, cell, "serve.decode")
