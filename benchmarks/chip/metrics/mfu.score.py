"""Model FLOPs of the window's prefill programs over their device time at
the chip's bf16 peak, percent."""

from benchmarks.chip.readers import prefill_mfu


def read(run, cell):
    return prefill_mfu(run, cell)
