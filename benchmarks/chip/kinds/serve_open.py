"""Open loop: serving flows sent at seeded times at ``rate_per_s``,
whether or not earlier ones have completed."""

from benchmarks.chip import serving


def run(cell, seed, seconds, trace, t_process):
    return serving.run(cell, seed, seconds, trace, t_process, loop="open")
