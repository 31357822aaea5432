"""TrainingFabric: the compute-plane object the automation flows drive.

The paper's pattern is funcX-mediated: flows invoke *registered functions*
on *compute endpoints*.  ``TrainingFabric`` owns a model + optimizer state +
data source and exposes exactly such functions (``train_steps``, ``evaluate``,
``save_checkpoint``, ``restore_latest``, ``export_metrics``), which launchers
register with the Compute action provider.  Fault tolerance:

* ``inject_failure_at`` makes a training action raise
  :class:`repro.core.errors.NodeFailure` at a chosen step — flows catch it
  (``ErrorEquals: ["NodeFailure"]``) and route to restore states;
* ``reshard(mesh)`` rebuilds the jitted step + re-places state for a NEW
  mesh (elastic shrink/grow), restoring from the latest checkpoint.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.errors import NodeFailure
from repro.models.model import Model, param_axes, param_shapes
from repro.parallel.sharding import (
    ACT_RULES,
    PARAM_RULES,
    param_shardings,
    use_rules,
)
from repro.train import checkpoint as ckpt
from repro.train.data import SyntheticTokens
from repro.train.loop import TrainState, init_state, make_eval_step, make_train_step
from repro.train.optimizer import AdamWState


class TrainingFabric:
    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        batch: int,
        seq_len: int,
        ckpt_dir: str,
        mesh=None,
        data=None,
        seed: int = 0,
    ):
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        self.batch = batch
        self.seq_len = seq_len
        self.ckpt_dir = ckpt_dir
        self.mesh = mesh
        self.data = data or SyntheticTokens(
            model_cfg.vocab_size, batch, seq_len, seed=seed
        )
        self.model = Model(model_cfg)
        self.axes = param_axes(model_cfg)
        self.state: TrainState | None = None
        self.history: list[dict] = []
        self.inject_failure_at: int | None = None
        self.checkpointer = ckpt.AsyncCheckpointer(ckpt_dir)
        self._build()

    # ------------------------------------------------------------- plumbing
    def _state_shardings(self):
        """Per-leaf NamedShardings of the train state on ``self.mesh``.

        ``None`` without a mesh (one default device).  Optimizer m/v follow
        the parameter shardings; the step counter is replicated.
        """
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        p_sh = param_shardings(
            self.axes, self.mesh, PARAM_RULES,
            param_shapes=param_shapes(self.model_cfg),
        )
        replicated = NamedSharding(self.mesh, PartitionSpec())
        return TrainState(params=p_sh,
                          opt=AdamWState(step=replicated, m=p_sh, v=p_sh))

    def _build(self):
        state_sh = self._state_shardings()
        if self.state is None:
            # created where it lives: each device materializes only its own
            # shards, so no device ever holds the whole state
            key = jax.random.PRNGKey(self.train_cfg.seed)
            self.state = jax.jit(
                lambda k: init_state(self.model, k)[0], out_shardings=state_sh
            )(key)
        step_fn = make_train_step(self.model, self.train_cfg)
        eval_step = make_eval_step(self.model)
        if self.mesh is not None:
            def train_step(state, batch):
                with use_rules(PARAM_RULES, ACT_RULES, self.mesh):
                    return step_fn(state, batch)

            self._train_step = jax.jit(train_step, donate_argnums=0)
        else:
            self._train_step = jax.jit(step_fn, donate_argnums=0)
        self._eval_step = jax.jit(eval_step)

    def _batch(self, step: int) -> dict:
        """The batch for ``step``, drawn by index, so steps replayed after a
        restore see the batches the lost steps saw."""
        host = self.data.batch_at(step)
        return {k: jnp.asarray(v) for k, v in host.items()}

    # ------------------------------------------------------------ functions
    def train_steps(self, n_steps: int = 10, **_) -> dict:
        """Run n training steps; raises NodeFailure at the injected step."""
        t0 = time.time()
        metrics, losses = {}, []
        for _ in range(n_steps):
            # the host waits here for the previous step to finish
            with obs.span("train.sync"):
                step_now = int(jax.device_get(self.state.step))
            if (
                self.inject_failure_at is not None
                and step_now >= self.inject_failure_at
            ):
                self.inject_failure_at = None
                raise NodeFailure(
                    f"simulated device loss at step {step_now}"
                )
            with obs.span("train.batch", step=step_now):
                batch = self._batch(step_now)
            with obs.span("train.dispatch", step=step_now):
                self.state, metrics = self._train_step(self.state, batch)
            losses.append(metrics["loss"])
        metrics = {k: float(v) for k, v in jax.device_get(metrics).items()}
        record = {
            "step": int(jax.device_get(self.state.step)),
            "seconds": time.time() - t0,
            **metrics,
            "losses": [float(x) for x in jax.device_get(losses)],
        }
        self.history.append(record)
        return record

    def evaluate(self, n_batches: int = 2, **_) -> dict:
        losses = []
        for i in range(n_batches):
            batch = {
                k: jnp.asarray(v)
                for k, v in self.data.batch_at(10_000 + i).items()
            }
            losses.append(
                float(jax.device_get(
                    self._eval_step(self.state.params, batch)["loss"]
                ))
            )
        return {
            "eval_loss": float(np.mean(losses)),
            "step": int(jax.device_get(self.state.step)),
        }

    def save_checkpoint(self, synchronous: bool = True, **_) -> dict:
        step = int(jax.device_get(self.state.step))
        if synchronous:
            path = ckpt.save(self.ckpt_dir, step, self.state)
        else:
            self.checkpointer.save(step, self.state)
            path = f"{self.ckpt_dir}/step_{step:08d} (async)"
        return {"checkpoint": path, "step": step}

    def restore_latest(self, **_) -> dict:
        """Replace the state with the latest checkpoint, placed leaf by leaf
        straight onto this fabric's shardings."""
        self.checkpointer.wait()
        target = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state
        )
        # the lost state goes first, so the two never share device memory
        self.state = None
        self.state, meta = ckpt.restore(
            self.ckpt_dir, target, shardings=self._state_shardings()
        )
        return {"restored_step": meta["step"]}

    def reshard(self, mesh, **_) -> dict:
        """Elastic rescale: rebuild the step for a new mesh + restore."""
        self.checkpointer.wait()
        old = self.mesh.devices.shape if self.mesh is not None else None
        self.mesh = mesh
        self._build()
        result = self.restore_latest()
        return {
            "old_mesh": old,
            "new_mesh": mesh.devices.shape if mesh is not None else None,
            **result,
        }

    def export_metrics(self, **_) -> dict:
        return {"history": self.history[-20:],
                "step": int(jax.device_get(self.state.step))}

    # -------------------------------------------------------- registration
    def register_all(self, compute_provider, endpoint_name="training-fabric",
                     mode="inline") -> dict:
        """Register every fabric function with a Compute action provider.

        Returns {"endpoint_id": ..., "functions": {name: function_id}}.
        """
        eid = compute_provider.register_endpoint(endpoint_name, mode=mode)
        fns = {}
        for name in ("train_steps", "evaluate", "save_checkpoint",
                     "restore_latest", "export_metrics"):
            fns[name] = compute_provider.register_function(
                getattr(self, name), name=name
            )
        return {"endpoint_id": eid, "functions": fns}
