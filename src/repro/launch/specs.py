"""ShapeDtypeStruct input stands-ins for every (arch × shape) dry-run cell.

``input_specs(cfg, shape, mesh)`` returns sharded ShapeDtypeStructs for the
step function arguments — weak-type-correct, shardable, never allocated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.model import Model, param_axes
from repro.parallel.sharding import (
    ACT_RULES,
    PARAM_RULES,
    ShardingRules,
    logical_spec,
    param_shardings,
)
from repro.train.loop import TrainState, init_state
from repro.train.optimizer import AdamWState

#: whisper's architectural decoder-position cap
WHISPER_DECODER_LEN = 448


def act_rules_for(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                  overrides: dict | None = None) -> ShardingRules:
    """Activation rules, adapted per cell.

    long_500k (batch=1) cannot shard the batch axis — shard the KV/sequence
    axis over "data" instead (sequence parallelism for the cache).
    """
    data_size = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for ax in ("pod", "data"):
        data_size *= sizes.get(ax, 1)
    rules = ACT_RULES
    if shape.global_batch < data_size:
        rules = rules.merged({"kv_seq": ("pod", "data"), "seq": None})
    if overrides:
        rules = rules.merged(overrides)
    return rules


def _sds(shape, dtype, mesh, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _spec(rules: ShardingRules, axes, shape, mesh) -> P:
    return logical_spec(tuple(axes), rules, tuple(shape), mesh)


def _effective(rules: ShardingRules, mesh: Mesh) -> ShardingRules:
    mesh_axes = set(mesh.axis_names)
    out = {}
    for k, v in rules.rules.items():
        if v is None:
            out[k] = None
        else:
            targets = v if isinstance(v, tuple) else (v,)
            kept = tuple(t for t in targets if t in mesh_axes)
            out[k] = kept if len(kept) > 1 else (kept[0] if kept else None)
    return ShardingRules(out)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                seq_len: int | None = None, overrides: dict | None = None) -> dict:
    """Training/prefill batch ShapeDtypeStructs."""
    rules = _effective(act_rules_for(cfg, shape, mesh, overrides), mesh)
    B = shape.global_batch
    S = seq_len if seq_len is not None else shape.seq_len
    bspec = lambda shp, axes, dtype=jnp.int32: _sds(
        shp, dtype, mesh, _spec(rules, axes, shp, mesh)
    )
    if cfg.family == "encdec":
        dec = min(S, cfg.max_target_positions or S)
        batch = {
            "frames": bspec((B, S, cfg.d_model), ("batch", "seq", "embed"),
                            jnp.dtype(cfg.compute_dtype)),
            "tokens": bspec((B, dec), ("batch", "seq")),
            "labels": bspec((B, dec), ("batch", "seq")),
        }
    else:
        batch = {
            "tokens": bspec((B, S), ("batch", "seq")),
            "labels": bspec((B, S), ("batch", "seq")),
        }
        if cfg.family == "vlm":
            batch["pixel_embeds"] = bspec(
                (B, cfg.n_image_tokens, cfg.d_model),
                ("batch", "seq", "embed"), jnp.dtype(cfg.compute_dtype),
            )
    if shape.kind != "train":
        batch.pop("labels", None)
    return batch


def state_specs(cfg: ModelConfig, mesh: Mesh,
                rules: ShardingRules = PARAM_RULES):
    """TrainState ShapeDtypeStructs with FSDP/TP shardings attached."""
    model = Model(cfg)

    def abstract_init():
        state, _ = init_state(model, jax.random.PRNGKey(0))
        return state

    state_shape = jax.eval_shape(abstract_init)
    axes = param_axes(cfg)
    shapes = jax.tree_util.tree_map(lambda s: s.shape, state_shape.params)
    p_sh = param_shardings(axes, mesh, rules, param_shapes=shapes)

    def attach(sds, sharding):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sharding)

    params = jax.tree_util.tree_map(attach, state_shape.params, p_sh)
    m = jax.tree_util.tree_map(attach, state_shape.opt.m, p_sh)
    v = jax.tree_util.tree_map(attach, state_shape.opt.v, p_sh)
    step = jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=NamedSharding(mesh, P())
    )
    return TrainState(params=params, opt=AdamWState(step=step, m=m, v=v))


def param_specs(cfg: ModelConfig, mesh: Mesh,
                rules: ShardingRules = PARAM_RULES,
                dtype=None):
    """Parameter-only ShapeDtypeStructs (serving paths)."""
    model = Model(cfg)
    p_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    axes = param_axes(cfg)
    shapes = jax.tree_util.tree_map(lambda s: s.shape, p_shape)
    p_sh = param_shardings(axes, mesh, rules, param_shapes=shapes)
    return jax.tree_util.tree_map(
        lambda sds, sh: jax.ShapeDtypeStruct(
            sds.shape, dtype or sds.dtype, sharding=sh
        ),
        p_shape, p_sh,
    )


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def _stackN(axes_tree, *prefix):
    return jax.tree_util.tree_map(
        lambda axes: tuple(prefix) + tuple(axes),
        axes_tree,
        is_leaf=lambda v: isinstance(v, tuple)
        and all(isinstance(e, (str, type(None))) for e in v),
    )


def cache_axes(cfg: ModelConfig) -> object:
    if cfg.family == "encdec":
        kv = attn_mod.kv_cache_axes()
        return {
            "self": _stackN(kv, "layers"),
            "cross_kv": _stackN(kv, "layers"),
        }
    if cfg.family == "ssm":
        return {
            "mlstm": _stackN(xlstm_mod.mlstm_cache_axes(), "blocks", "layers"),
            "slstm": {"state": _stackN(xlstm_mod.slstm_state_axes(), "blocks")},
        }
    if cfg.family == "hybrid":
        from repro.models.transformer import zamba_structure

        _, _, tail = zamba_structure(cfg)
        out = {
            "groups": _stackN(ssm_mod.mamba_cache_axes(), "blocks", "layers"),
            "shared": _stackN(attn_mod.kv_cache_axes(), "blocks"),
            "tail": _stackN(ssm_mod.mamba_cache_axes(), "layers") if tail
            else None,
        }
        return out
    if cfg.mla is not None:
        from repro.models.transformer import latent_stacks

        latent = {"ckv": ("batch", "kv_seq", None),
                  "kpe": ("batch", "kv_seq", None)}
        out = {name: _stackN(latent, "layers")
               for name, _, _ in latent_stacks(cfg)}
        if cfg.moe is not None:
            out["moe_counts"] = (None,)
        return out
    return _stackN(attn_mod.kv_cache_axes(), "layers")


def cache_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                overrides: dict | None = None) -> object:
    model = Model(cfg)
    B = shape.global_batch
    max_len = shape.seq_len
    cache_shape = jax.eval_shape(lambda: model.init_cache(B, max_len))
    axes = cache_axes(cfg)
    rules = _effective(act_rules_for(cfg, shape, mesh, overrides), mesh)
    # cache stacking axes replicate
    rules = rules.merged({"layers": None, "blocks": None})

    def attach(sds, ax):
        spec = _spec(rules, ax, sds.shape, mesh)
        return _sds(sds.shape, sds.dtype, mesh, spec)

    return jax.tree_util.tree_map(
        attach, cache_shape, axes,
        is_leaf=lambda v: isinstance(v, tuple)
        and all(isinstance(e, (str, type(None))) for e in v),
    )


def decode_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                 overrides: dict | None = None) -> dict:
    rules = _effective(act_rules_for(cfg, shape, mesh, overrides), mesh)
    B = shape.global_batch
    tokens = _sds((B, 1), jnp.int32, mesh,
                  _spec(rules, ("batch", "seq"), (B, 1), mesh))
    position = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
    return {
        "params": param_specs(cfg, mesh, dtype=jnp.dtype(cfg.compute_dtype)),
        "tokens_new": tokens,
        "cache": cache_specs(cfg, shape, mesh, overrides),
        "position": position,
    }


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                overrides: dict | None = None) -> dict:
    """All step-function argument specs for one dry-run cell."""
    if shape.kind == "train":
        return {
            "state": state_specs(cfg, mesh),
            "batch": batch_specs(cfg, shape, mesh, overrides=overrides),
        }
    if shape.kind == "prefill":
        return {
            "params": param_specs(cfg, mesh, dtype=jnp.dtype(cfg.compute_dtype)),
            "batch": batch_specs(cfg, shape, mesh, overrides=overrides),
        }
    return decode_specs(cfg, shape, mesh, overrides)
