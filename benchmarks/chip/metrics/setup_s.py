"""Set-up: process start to the window's start (import, device, weights
from the seed, compiles from the cache, warm-up through the whole path)."""


def read(run, cell):
    return run.setup_s
