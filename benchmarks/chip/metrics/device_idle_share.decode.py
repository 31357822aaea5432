"""Percent of the traced window in which no operation ran on the device
(the mean over the cell's chips)."""

from benchmarks.chip.readers import idle_share as read  # noqa: F401
