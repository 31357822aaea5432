"""Least bytes of a decode step (every matmul weight once in bf16, the
compute dtype, and the bf16 K/V cache up to the mix's mean position) over
the median decode program's device time at the chip's HBM bandwidth,
percent."""

from benchmarks.chip import flops, xplane
from benchmarks.chip.peaks import peaks
from benchmarks.chip.readers import DECODE


def read(run, cell):
    window = run.trace_window()
    if window is None or not run.trace.devices:
        return None
    runs = xplane.programs_matching(run.trace.devices[0], DECODE, *window)
    if not runs:
        return None
    mix = cell.traffic
    mean_prompt = sum(int(s) * w for s, w in mix["prompt_buckets"].items())
    position = mean_prompt + (mix["max_new_tokens"] - 2) / 2
    least = flops.decode_least_bytes(cell.model, mix["batch"], position)
    t = xplane.median(p[1] - p[0] for p in runs)
    return 100.0 * least / (t * peaks(run.device_kind).hbm_bytes_per_s)
