"""Elastic training: device failure -> catch -> restore onto a SMALLER mesh.

The headline fault-tolerance scenario for large fleets: a training job on an
N-device mesh loses devices mid-run; the training flow catches the failure
and resumes from the latest checkpoint on a smaller mesh (elastic shrink),
with all parameter/optimizer state resharded at restore time.

This example runs with 4 simulated host devices (set before JAX imports):
train on a (2, 2) data x model mesh, inject a NodeFailure, reshard to
(1, 2) — "half the fleet is gone" — and train to completion.

    PYTHONPATH=src python examples/elastic_training.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import tempfile  # noqa: E402

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.core import FlowsService, RealClock  # noqa: E402
from repro.core.actions import ActionRegistry  # noqa: E402
from repro.core.engine import PollingPolicy  # noqa: E402
from repro.core.providers import ComputeProvider  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.train.fabric import TrainingFabric  # noqa: E402


def main():
    workdir = tempfile.mkdtemp(prefix="elastic-")
    cfg = configs.get("internlm2-1.8b", smoke=True)
    big_mesh = make_mesh((2, 2), ("data", "model"))
    small_mesh = make_mesh((1, 2), ("data", "model"))

    fabric = TrainingFabric(
        cfg,
        TrainConfig(total_steps=30, warmup_steps=2, learning_rate=1e-3),
        batch=4, seq_len=32,
        ckpt_dir=os.path.join(workdir, "ckpt"),
        mesh=big_mesh,
    )
    # the state is created already sharded: each device holds a quarter of
    # the largest weight, and no device ever held all of it
    big = max(jax.tree_util.tree_leaves(fabric.state.params), key=lambda x: x.size)
    assert all(s.data.size * 4 == big.size for s in big.addressable_shards)
    fabric.save_checkpoint()
    fabric.inject_failure_at = 6  # devices "die" during the second segment

    clock = RealClock()
    registry = ActionRegistry()
    compute = ComputeProvider(clock=clock)
    registry.register(compute)
    flows = FlowsService(
        registry, clock=clock,
        polling=PollingPolicy(initial_seconds=0.05, cap_seconds=0.5,
                              use_callbacks=True),
    )
    eid = compute.register_endpoint("pod")
    f_train = compute.register_function(
        lambda: fabric.train_steps(n_steps=5), name="train5")
    f_ckpt = compute.register_function(
        lambda: fabric.save_checkpoint(), name="ckpt")
    f_shrink = compute.register_function(
        lambda: fabric.reshard(small_mesh), name="shrink")

    definition = {
        "Comment": "Elastic training: failure -> reshard -> resume",
        "StartAt": "Train1",
        "States": {
            "Train1": {
                "Type": "Action", "ActionUrl": "ap://compute",
                "Parameters": {"endpoint_id": eid, "function_id": f_train,
                                "kwargs": {}},
                "ResultPath": "$.t1", "Next": "Ckpt1"},
            "Ckpt1": {
                "Type": "Action", "ActionUrl": "ap://compute",
                "Parameters": {"endpoint_id": eid, "function_id": f_ckpt,
                                "kwargs": {}},
                "ResultPath": "$.c1", "Next": "Train2"},
            "Train2": {
                "Type": "Action", "ActionUrl": "ap://compute",
                "Parameters": {"endpoint_id": eid, "function_id": f_train,
                                "kwargs": {}},
                "ResultPath": "$.t2",
                "Catch": [{"ErrorEquals": ["ActionFailedException"],
                            "ResultPath": "$.failure",
                            "Next": "ShrinkAndRestore"}],
                "Next": "Done"},
            "ShrinkAndRestore": {
                "Type": "Action", "ActionUrl": "ap://compute",
                "Parameters": {"endpoint_id": eid, "function_id": f_shrink,
                                "kwargs": {}},
                "ResultPath": "$.reshard", "Next": "Train2"},
            "Done": {"Type": "Succeed"},
        },
    }
    record = flows.publish_flow(definition, title="Elastic training")
    run = flows.run_flow(record.flow_id, {}, label="elastic-demo")
    flows.engine.wait(run.run_id, timeout=1200)

    print(f"run: {run.status}")
    assert run.status == "SUCCEEDED", run.error
    failure = run.context.get("failure")
    print("caught failure:", failure["Details"]["error"])
    reshard = run.context["reshard"]["details"]["results"][0]
    print(f"resharded: {reshard['old_mesh']} -> {reshard['new_mesh']}, "
          f"restored step {reshard['restored_step']}")
    print("loss history:",
          [(h["step"], round(h["loss"], 3)) for h in fabric.history])
    final_step = fabric.history[-1]["step"]
    assert final_step >= 10, "training must have resumed after reshard"
    assert fabric.mesh.devices.shape == (1, 2)
    print("Elastic training complete: survived device loss, "
          "resumed on half the mesh.")


if __name__ == "__main__":
    main()
