"""Closed loop: ``clients`` callers, each sending its next serving flow
when its last one completes."""

from benchmarks.chip import serving


def run(cell, seed, seconds, trace, t_process):
    return serving.run(cell, seed, seconds, trace, t_process, loop="closed")
