"""Programs lowered inside the measured window (the program's compile
log): a shape the warm-up missed would compile there."""

from benchmarks.chip.program_spans import window_compiles as read  # noqa: F401
