"""TrainingFabric: a restored run replays the lost steps exactly."""

import jax

from repro import configs
from repro.configs.base import TrainConfig
from repro.train.fabric import TrainingFabric


def test_restore_replays_lost_steps_exactly(tmp_path):
    cfg = configs.get("internlm2-1.8b", smoke=True)
    fabric = TrainingFabric(
        cfg, TrainConfig(total_steps=8, warmup_steps=1, learning_rate=1e-3),
        batch=2, seq_len=16, ckpt_dir=str(tmp_path),
    )
    fabric.train_steps(n_steps=2)
    fabric.save_checkpoint()
    lost = fabric.train_steps(n_steps=2)
    assert lost["step"] == 4
    assert fabric.restore_latest() == {"restored_step": 2}
    assert int(jax.device_get(fabric.state.step)) == 2
    # the same state meets the same batches (drawn by step, not by call)
    again = fabric.train_steps(n_steps=2)
    assert again["losses"] == lost["losses"]
