"""Median per flow of the control plane's own time: latency from
``run_flow`` to ``FlowCompleted``, less the endpoint wait and the
registered function's time (``Run.events`` and the benchmark's wrapper)."""

from benchmarks.chip.readers import median_ms, ok_flows


def read(run, cell):
    return median_ms((f["entered"] - f["submitted"])
                     + (f["completed"] - f["fn_end"]) for f in ok_flows(run))
