"""95th percentile over every flow of the window of the time from its
scheduled send to ``FlowCompleted``.  A flow that failed or never
completed counts as the longest wait the run allows, over every limit."""

from benchmarks.chip.readers import p95
from benchmarks.chip.serving import DRAIN_S


def read(run, cell):
    flows = run.data.get("flows")
    if not flows:
        return None
    worst = run.seconds + DRAIN_S
    return p95(f["completed"] - f["scheduled"] if f.get("ok") else worst
               for f in flows)
