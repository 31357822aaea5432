"""One training job as a flow of segments over a ``TrainingFabric``."""

from benchmarks.chip import training


def run(cell, seed, seconds, trace, t_process):
    return training.run(cell, seed, seconds, trace, t_process)
