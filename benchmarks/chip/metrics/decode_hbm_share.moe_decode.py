"""Least bytes of a decode step (every matmul weight once in bf16, the
held experts as far as the engine's counter saw them hit, and the latent
cache up to the mix's mean position) over the median decode program's
device time at the chip's HBM bandwidth, percent."""

from benchmarks.chip.readers_mla_moe import decode_hbm_share as read  # noqa: F401
