"""Median per flow from the Action state's ``StateEntered`` to the start of
the registered function on the thread endpoint."""

from benchmarks.chip.readers import median_ms, ok_flows


def read(run, cell):
    return median_ms(f["fn_start"] - f["entered"] for f in ok_flows(run))
