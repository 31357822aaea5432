"""Batched serving engine: prefill + lockstep decode with KV/state caches.

Requests are grouped into generation batches (arrival-window batching);
each batch is prefim-filled once and decoded in lockstep, with per-row EOS
masking.  Attention families use prefill+KV cache; recurrent families
(xlstm / zamba2) consume the prompt through their O(1)-state decode path.
The jitted step functions are cached per (batch, prompt_len) bucket, and
read a copy of the weights cast to the compute dtype once per weight
assignment (``Model.serving_params``), not at every step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.model import Model


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params,
        max_len: int = 512,
        eos_token: int | None = None,
        greedy: bool = True,
        seed: int = 0,
    ):
        self.model = model
        self.max_len = max_len
        self.eos = eos_token
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.stats = {"requests": 0, "batches": 0, "tokens_generated": 0,
                      "prefill_tokens": 0, "weight_views": 0,
                      "weight_view_bytes": 0}
        self.params = params

        # the programs hold no reference to the engine, so dropping the
        # engine frees its serving weights at once
        def prefill(params, batch):
            return model.prefill(params, batch, max_len)

        self._jit_prefill = jax.jit(prefill)
        self._jit_decode = jax.jit(model.decode_step)

    @property
    def params(self):
        """The weights as assigned, in the model's ``param_dtype``.

        Assigning them builds, once, the view the programs read
        (``serving_params``, from ``Model.serving_params``):
        ``stats["weight_views"]`` counts the views built and
        ``stats["weight_view_bytes"]`` holds the device bytes that the
        current view adds beside ``params``.
        """
        return self._params

    @params.setter
    def params(self, params):
        self._params = params
        self.serving_params = None  # the old view goes before the new
        with obs.span("serve.weights"):
            self.serving_params = jax.block_until_ready(
                self.model.serving_params(params))
        self.stats["weight_views"] += 1
        self.stats["weight_view_bytes"] = sum(
            int(view.nbytes) for view, leaf in zip(
                jax.tree_util.tree_leaves(self.serving_params),
                jax.tree_util.tree_leaves(params))
            if view is not leaf)

    # ------------------------------------------------------------------
    def _sample(self, logits: jnp.ndarray) -> jnp.ndarray:
        if self.greedy:
            return jnp.argmax(logits[:, -1, :], axis=-1)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(sub, logits[:, -1, :])

    def generate(
        self,
        prompts: np.ndarray,       # [B, S_prompt] int32
        max_new_tokens: int = 32,
        frames: np.ndarray | None = None,     # encdec
        pixel_embeds: np.ndarray | None = None,  # vlm
    ) -> dict:
        """Generate for a batch of equal-length prompts.

        Returns the generated ``tokens`` [B, max_new_tokens] and, kept on
        the device for checking against a reference, ``logits``: the
        prefill's last-position logits and those of the first decode step
        (each [B, 1, V]).
        """
        B, S = prompts.shape
        self.stats["requests"] += B
        self.stats["batches"] += 1
        self.stats["prefill_tokens"] += int(B * S)
        # the prefill's span runs until its token is on the host
        with obs.span("serve.prefill", rows=B, length=S):
            tokens = jnp.asarray(prompts, jnp.int32)
            batch = {"tokens": tokens}
            if frames is not None:
                batch["frames"] = jnp.asarray(frames)
            if pixel_embeds is not None:
                batch["pixel_embeds"] = jnp.asarray(pixel_embeds)
            logits, cache = self._jit_prefill(self.serving_params, batch)
            cur = np.asarray(self._sample(logits))
        kept_logits = [logits]
        position = S

        out = []
        done = np.zeros(B, bool)
        for step in range(max_new_tokens):
            out.append(np.where(done, self.eos or 0, cur))
            if self.eos is not None:
                done |= cur == self.eos
                if done.all():
                    break
            if step == max_new_tokens - 1:
                break
            # one decode step: upload the token and position, dispatch the
            # step, then pull its token, where the host waits on the device
            with obs.span("serve.decode"):
                logits, cache = self._jit_decode(
                    self.serving_params, jnp.asarray(cur[:, None], jnp.int32),
                    cache, jnp.asarray(position, jnp.int32),
                )
            position += 1
            if step == 0:
                kept_logits.append(logits)
            with obs.span("serve.pull"):
                cur = np.asarray(self._sample(logits))
        generated = np.stack(out, axis=1) if out else np.zeros((B, 0), np.int32)
        self.stats["tokens_generated"] += int(generated.size)
        # every token after the first came from one decode step
        self._count_experts(cache, max(len(out) - 1, 0))
        return {"tokens": generated, "prompt_len": S, "logits": kept_logits}

    def _count_experts(self, cache, steps: int) -> None:
        """Add the expert layers' counter, which the decode steps sum on
        the device in the cache, to ``stats``, pulled once per call:
        ``moe_routed_pairs`` (token, held expert) pairs and
        ``moe_experts_hit`` held experts with at least one, each summed
        over layers and ``moe_decode_steps`` steps."""
        if not isinstance(cache, dict) or "moe_counts" not in cache:
            return
        routed, hit = (int(n) for n in np.asarray(cache["moe_counts"]))
        for key, n in (("moe_decode_steps", steps),
                       ("moe_routed_pairs", routed),
                       ("moe_experts_hit", hit)):
            self.stats[key] = self.stats.get(key, 0) + n


class BatchAccumulator:
    """Arrival-window request batching: collect up to ``max_batch`` requests
    (padding prompts to a bucket length) before dispatching to the engine."""

    def __init__(self, engine: ServeEngine, max_batch: int = 8,
                 pad_token: int = 0):
        self.engine = engine
        self.max_batch = max_batch
        self.pad = pad_token
        self._pending: list[tuple[np.ndarray, dict]] = []

    def submit(self, prompt: np.ndarray, **kw) -> None:
        self._pending.append((np.asarray(prompt, np.int32), kw))

    def flush(self, max_new_tokens: int = 32) -> list[dict]:
        if not self._pending:
            return []
        results = []
        while self._pending:
            chunk = self._pending[: self.max_batch]
            self._pending = self._pending[self.max_batch :]
            width = max(len(p) for p, _ in chunk)
            batch = np.full((len(chunk), width), self.pad, np.int32)
            for i, (p, _) in enumerate(chunk):
                batch[i, width - len(p):] = p  # left-pad
            out = self.engine.generate(batch, max_new_tokens=max_new_tokens)
            for i in range(len(chunk)):
                results.append(
                    {"tokens": out["tokens"][i], "prompt_len": len(chunk[i][0])}
                )
        return results
