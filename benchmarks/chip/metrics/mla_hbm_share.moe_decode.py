"""Least bytes of a decode step's latent attention (every layer's
attention weights once and the latent cache up to the mix's mean position,
in bf16) over the median device time of the decode program's operations
under the ``mla`` named scope at the chip's HBM bandwidth, percent."""

from benchmarks.chip.readers_mla_moe import scope_hbm_share


def read(run, cell):
    return scope_hbm_share(run, cell, "mla")
