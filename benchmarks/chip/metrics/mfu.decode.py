"""Model FLOPs of every prefill and generated token of the flows completed
in the window, over the window at the chip's bf16 peak, percent: the whole
serving step's share of the peak."""

from benchmarks.chip import flops
from benchmarks.chip.peaks import peaks
from benchmarks.chip.readers import flows_in_window


def read(run, cell):
    done = flows_in_window(run)
    if not done:
        return None
    b, new = run.data["batch"], run.data["new_tokens"]
    work = sum(flops.generate_flops(cell.model, b, f["prompt_len"], new)
               for f in done)
    return 100.0 * work / (run.window_s * peaks(run.device_kind).bf16_flops)
