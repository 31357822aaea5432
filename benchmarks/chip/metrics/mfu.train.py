"""The whole training step's share of the chips' bf16 peak, percent:
tokens per second of this run's window times the model FLOPs a token
needs (6 x the matmul weights plus causal attention, forward and backward;
recomputation under remat not counted), over chips x peak."""

from benchmarks.chip import flops
from benchmarks.chip.peaks import peaks
from benchmarks.chip.training import step_tokens_per_s


def read(run, cell):
    rate = step_tokens_per_s(run)
    if rate is None:
        return None
    work = rate * flops.train_flops_per_token(cell.model, run.data["seq_len"])
    return 100.0 * work / (run.data["chips"] * peaks(run.device_kind).bf16_flops)
