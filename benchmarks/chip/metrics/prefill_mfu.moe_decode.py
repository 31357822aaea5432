"""Model FLOPs of the window's prefill programs (expanded latent
attention, the held share of the routed experts) over their device time
at the chip's bf16 peak, percent."""

from benchmarks.chip.readers_mla_moe import prefill_mfu as read  # noqa: F401
