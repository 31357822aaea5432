"""Faults planted in the timed path, to show that ``correct`` catches them
and to read the numbers they give, and the control put in the program's
place.  Used by the tests and by ``calibrate.py``; the benchmark's own runs
never plant one."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.references import dense_lm

FAULTS = ("unchanged", "half_batch", "no_exchange", "altered_token",
          "control")


def plant(fault: str, monkeypatch) -> None:
    """Break the program underneath the harness.  ``monkeypatch`` has
    ``setattr(obj, name, value)`` (pytest's fixture, or :class:`Patch`).

    * ``unchanged``: every train step returns its state unchanged;
    * ``half_batch``: every train step sees the first half of its batch
      only, and takes the mean over it;
    * ``no_exchange`` (a fabric with a mesh): no gradient crosses between
      the data-parallel groups: each takes the gradient of its own rows
      for the parameter shards it holds (the first group's for those it
      shares) and reports its own loss;
    * ``altered_token``: the last token each served batch generates is
      moved to the next id of the vocabulary;
    * ``control``: the plain reference with every matmul in fp8
      (``references/dense_lm.fp8``) in the program's place: each train step
      takes its loss and gradient from it, and each served batch is its
      greedy tokens, over the program's own weights.
    """
    if fault == "control":
        _plant_control(monkeypatch)
    elif fault in ("unchanged", "half_batch"):
        from repro.train.fabric import TrainingFabric
        from repro.train.loop import make_train_step

        build = TrainingFabric._build

        def patched(self):
            build(self)
            step = _on_mesh(self, make_train_step(self.model, self.train_cfg))
            if fault == "unchanged":
                self._train_step = jax.jit(lambda s, b: (s, step(s, b)[1]))
            else:
                self._train_step = jax.jit(
                    lambda s, b: step(s, {k: v[: v.shape[0] // 2]
                                          for k, v in b.items()}),
                    donate_argnums=0)

        monkeypatch.setattr(TrainingFabric, "_build", patched)
    elif fault == "no_exchange":
        from repro.train.fabric import TrainingFabric

        build = TrainingFabric._build

        def patched(self):
            build(self)
            self._train_step = jax.jit(_on_mesh(self, _no_exchange(self)),
                                       donate_argnums=0)

        monkeypatch.setattr(TrainingFabric, "_build", patched)
    elif fault == "altered_token":
        from repro.serve.engine import ServeEngine

        generate = ServeEngine.generate

        def altered(self, prompts, max_new_tokens=32, **kw):
            out = generate(self, prompts, max_new_tokens=max_new_tokens, **kw)
            tokens = out["tokens"].copy()
            tokens[:, -1] = (tokens[:, -1] + 1) % self.model.cfg.vocab_size
            return {**out, "tokens": tokens}

        monkeypatch.setattr(ServeEngine, "generate", altered)
    else:
        raise KeyError(f"no fault {fault!r}; known: {FAULTS}")


def _on_mesh(fabric, step):
    """``step`` under the fabric's sharding rules, as its own step runs,
    where the fabric has a mesh."""
    if fabric.mesh is None:
        return step
    from repro.parallel.sharding import ACT_RULES, PARAM_RULES, use_rules

    def on_mesh(state, batch):
        with use_rules(PARAM_RULES, ACT_RULES, fabric.mesh):
            return step(state, batch)

    return on_mesh


def _no_exchange(fabric):
    """A train step of ``fabric`` whose data-parallel groups share no
    gradient (``no_exchange``)."""
    from repro.train import optimizer
    from repro.train.loop import TrainState

    n = dict(zip(fabric.mesh.axis_names, fabric.mesh.devices.shape))["data"]
    specs = jax.tree_util.tree_map(lambda x: x.sharding.spec,
                                   fabric.state.params)

    def own(spec, *grads):
        """Each group's gradient on the slice of the leaf that it holds."""
        g = grads[0]
        dims = [d for d, part in enumerate(spec)
                if "data" in (part if isinstance(part, tuple) else (part,))]
        if not dims:
            return g
        group = jax.lax.broadcasted_iota(jnp.int32, g.shape, dims[0]) \
            // (g.shape[dims[0]] // n)
        return jnp.select([group == k for k in range(n)], list(grads))

    def step(state, batch):
        rows = batch["tokens"].shape[0] // n
        losses, grads = zip(*[jax.value_and_grad(fabric.model.loss)(
            state.params, {k: v[i * rows:(i + 1) * rows]
                           for k, v in batch.items()}) for i in range(n)])
        mixed = jax.tree_util.tree_map(own, specs, *grads,
                                       is_leaf=lambda x: isinstance(
                                           x, jax.sharding.PartitionSpec))
        params, opt_state, metrics = optimizer.adamw_update(
            state.params, mixed, state.opt, fabric.train_cfg)
        return TrainState(params, opt_state), {"loss": losses[0], **metrics}

    return step


def _model_cfg(model) -> dict:
    return dataclasses.asdict(model.cfg)


def _plant_control(monkeypatch) -> None:
    from repro.serve.engine import ServeEngine
    from repro.train import optimizer
    from repro.train.fabric import TrainingFabric
    from repro.train.loop import TrainState

    build = TrainingFabric._build

    def patched(self):
        build(self)
        cfg, tcfg = _model_cfg(self.model), self.train_cfg

        def step(state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: dense_lm.cross_entropy(
                    cfg, p, batch["tokens"], batch["labels"], dense_lm.fp8)
            )(state.params)
            params, opt_state, metrics = optimizer.adamw_update(
                state.params, grads, state.opt, tcfg)
            return TrainState(params, opt_state), {"loss": loss, **metrics}

        self._train_step = jax.jit(step, donate_argnums=0)

    generate = ServeEngine.generate

    def controlled(self, prompts, max_new_tokens=32, **kw):
        out = generate(self, prompts, max_new_tokens=max_new_tokens, **kw)
        tokens = _control_greedy(dense_lm.items(_model_cfg(self.model)),
                                 self.params, np.asarray(prompts),
                                 max_new_tokens)
        return {**out, "tokens": np.asarray(tokens)}

    monkeypatch.setattr(TrainingFabric, "_build", patched)
    monkeypatch.setattr(ServeEngine, "generate", controlled)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _control_greedy(cfg_items: tuple, params, prompts, new: int):
    """``new`` greedy tokens of the fp8 control after each prompt."""
    cfg = dict(cfg_items)
    b, s = prompts.shape
    buf = jnp.zeros((b, s + new), jnp.int32).at[:, :s].set(prompts)

    def body(i, carry):
        buf, out = carry
        lg = dense_lm.logits(cfg, params, buf, 0, mm=dense_lm.fp8)
        tok = jnp.argmax(jax.lax.dynamic_index_in_dim(lg, s - 1 + i, 1,
                                                      keepdims=False), -1)
        return (jax.lax.dynamic_update_index_in_dim(buf, tok, s + i, 1),
                jax.lax.dynamic_update_index_in_dim(out, tok, i, 1))

    _, out = jax.lax.fori_loop(0, new, body,
                               (buf, jnp.zeros((b, new), jnp.int32)))
    return out


class Patch:
    """A minimal ``monkeypatch`` for scripts: ``undo()`` restores."""

    def __init__(self):
        self._saved = []

    def setattr(self, obj, name, value):
        self._saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self._saved:
            obj, name, value = self._saved.pop()
            setattr(obj, name, value)
