"""Tokens of every step of the window's whole segments, over the time from
the first segment's start to the end of the last, the first segment that
ends after ``--seconds``."""

from benchmarks.chip.training import step_tokens_per_s


def read(run, cell):
    return step_tokens_per_s(run)
