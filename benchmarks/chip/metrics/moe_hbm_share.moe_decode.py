"""Least bytes of a decode step's expert layers (routers and shared
experts once, the held experts at the share the engine's counter saw hit,
in bf16) over the median device time of the decode program's operations
under the ``moe.*`` named scopes at the chip's HBM bandwidth, percent."""

from benchmarks.chip.readers_mla_moe import scope_hbm_share


def read(run, cell):
    return scope_hbm_share(run, cell, "moe")
