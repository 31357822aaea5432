"""Model facade: one uniform interface over all assigned families.

    model = Model(config)
    params, axes = model.init(key)          # or jax.eval_shape(model.init_fn)
    logits, aux  = model.forward(params, batch)
    loss         = model.loss(params, batch)
    logits, cache = model.prefill(params, batch, max_len)
    logits, cache = model.decode_step(params, tokens, cache, position)
    weights      = model.serving_params(params)  # what prefill/decode read

``batch`` keys by family:
    lm / moe / ssm / hybrid : tokens [B,S], labels [B,S]
    vlm                     : + pixel_embeds [B,K,D]
    encdec                  : frames [B,S_enc,D], tokens [B,S_dec], labels
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import encdec as encdec_mod
from repro.models import layers as L
from repro.models import transformer as tfm
from repro.models.layers import ParamSpec


def init_spec(cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec_mod.init_encdec(cfg)
    if cfg.family == "ssm":
        return tfm.init_xlstm(cfg)
    if cfg.family == "hybrid":
        return tfm.init_zamba(cfg)
    return tfm.init_lm(cfg)  # dense | moe | vlm


def param_axes(cfg: ModelConfig):
    spec = init_spec(cfg)
    return jax.tree_util.tree_map(
        lambda s: s.axes, spec, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def param_shapes(cfg: ModelConfig):
    spec = init_spec(cfg)
    return jax.tree_util.tree_map(
        lambda s: s.shape, spec, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def count_params_analytic(cfg: ModelConfig) -> int:
    shapes = param_shapes(cfg)
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    total = 0
    for shape in leaves:
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def active_params_analytic(cfg: ModelConfig) -> int:
    """MoE: parameters touched per token (for 6·N_active·D roofline FLOPs)."""
    total = count_params_analytic(cfg)
    if cfg.moe is None:
        return total
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    per_expert = 3 * cfg.d_model * cfg.moe.d_ff
    expert_total = cfg.n_layers * e * per_expert
    expert_active = cfg.n_layers * k * per_expert
    return total - expert_total + expert_active


@functools.partial(jax.jit, static_argnums=1)
def _cast_leaves(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init(self, key):
        spec = init_spec(self.cfg)
        return L.materialize(key, spec, jnp.dtype(self.cfg.param_dtype))

    def init_fn(self, key):
        params, _ = self.init(key)
        return params

    # --------------------------------------------------------------- forward
    def forward(self, params, batch):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec_mod.forward_encdec(
                params, cfg, batch["frames"], batch["tokens"]
            )
        if cfg.family == "ssm":
            return tfm.forward_xlstm(params, cfg, batch["tokens"])
        if cfg.family == "hybrid":
            return tfm.forward_zamba(params, cfg, batch["tokens"])
        return tfm.forward_lm(
            params, cfg, batch["tokens"], batch.get("pixel_embeds")
        )

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        ce = L.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
        if self.cfg.moe is not None:
            ce = ce + self.cfg.moe.aux_loss_weight * aux
        return ce

    # --------------------------------------------------------------- serving
    def serving_params(self, params):
        """The weights as the serving programs read them: each leaf that
        every program casts to ``compute_dtype`` before use
        (``layers.cast_at_use``) cast once, in one jitted call; every other
        leaf is the very array of ``params``.  ``prefill`` and
        ``decode_step`` give bitwise the same results from this tree as
        from ``params``.  Training differentiates through ``params`` and
        keeps its casts."""
        dtype = jnp.dtype(self.cfg.compute_dtype)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [leaf for _, leaf in flat]
        cast = [i for i, (path, leaf) in enumerate(flat)
                if leaf.dtype != dtype
                and L.cast_at_use(tuple(getattr(k, "key", None)
                                        for k in path))]
        if cast:
            new = _cast_leaves([leaves[i] for i in cast], dtype)
            for i, leaf in zip(cast, new):
                leaves[i] = leaf
        return treedef.unflatten(leaves)

    def prefill(self, params, batch, max_len: int):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec_mod.prefill_encdec(
                params, cfg, batch["frames"], batch["tokens"]
            )
        if cfg.family == "ssm":
            return tfm.prefill_xlstm(params, cfg, batch["tokens"])
        if cfg.family == "hybrid":
            return tfm.prefill_zamba(params, cfg, batch["tokens"], max_len)
        return tfm.prefill_lm(
            params, cfg, batch["tokens"], max_len, batch.get("pixel_embeds")
        )

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        if cfg.family == "encdec":
            return encdec_mod.init_encdec_cache(cfg, batch_size, max_len, dtype)
        if cfg.family == "ssm":
            return tfm.init_xlstm_cache(cfg, batch_size, dtype)
        if cfg.family == "hybrid":
            return tfm.init_zamba_cache(cfg, batch_size, max_len, dtype)
        cache = attn_mod.init_kv_cache(cfg, batch_size, max_len, dtype)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), cache
        )

    def decode_step(self, params, tokens_new, cache, position):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec_mod.decode_encdec(params, cfg, tokens_new, cache, position)
        if cfg.family == "ssm":
            return tfm.decode_xlstm(params, cfg, tokens_new, cache, position)
        if cfg.family == "hybrid":
            return tfm.decode_zamba(params, cfg, tokens_new, cache, position)
        return tfm.decode_lm(params, cfg, tokens_new, cache, position)
