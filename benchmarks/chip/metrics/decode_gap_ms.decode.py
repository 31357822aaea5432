"""Median device-idle time between consecutive decode programs of one
served batch: the per-token host round trip of ``ServeEngine.generate``."""

from benchmarks.chip import xplane
from benchmarks.chip.readers import DECODE, median_ms, serve_shapes_of


def read(run, cell):
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    dev = run.trace.devices[0]
    runs = serve_shapes_of(run, xplane.programs_matching(dev, DECODE, *window))
    gaps = []
    for (a, _, _), (b, _, _) in zip(runs, runs[1:]):
        same_batch = [s for s in run.trace.spans
                      if s[0] <= a[0] and b[1] <= s[1]
                      and s[2].startswith("bench:serve ")]
        if same_batch:
            gaps += xplane.idle_between(dev, [a, b])
    return median_ms(gaps)
