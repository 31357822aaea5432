"""Decoder-only LM assembly: dense, MoE, hybrid (zamba2), xLSTM stacks.

Layers are **scanned** (stacked parameters with a leading layer axis) so that
the lowered HLO stays compact for 24–94-layer models: one block body is
compiled once regardless of depth, which keeps the multi-pod dry-run cheap
and makes remat policies uniform.  Heterogeneous stacks are block-structured:

* zamba2: 13 super-blocks of (6 Mamba2 layers + 1 shared-attention
  application with per-application LoRA) + a 3-layer Mamba tail;
* xlstm:  6 super-blocks of (7 mLSTM + 1 sLSTM);
* latent attention (moonlight, the DeepSeek-V3 block): ``n_dense_layers``
  leading dense layers, then the scanned MoE stack, each layer with
  multi-head latent attention and its latent cache.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import ParamSpec
from repro.parallel.sharding import shard


# ---------------------------------------------------------------------------
# Spec utilities
# ---------------------------------------------------------------------------


def stack_specs(spec, n: int, axis_name: str = "layers"):
    """Prepend a stacking dimension to every ParamSpec in a pytree."""

    def bump(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale)

    return jax.tree_util.tree_map(
        bump, spec, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def scan_layers(cfg: ModelConfig, body, carry, xs):
    """lax.scan over stacked layer params, or an unrolled python loop.

    Unrolling is used by the dry-run: XLA's HLO cost analysis counts
    while-loop bodies once, so roofline FLOPs/bytes need the layers
    materialized.  Semantics are identical.
    """
    if not cfg.unroll_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


# ---------------------------------------------------------------------------
# One transformer block (dense or MoE FFN)
# ---------------------------------------------------------------------------


def init_block(cfg: ModelConfig):
    spec: dict[str, Any] = {
        "attn_norm": L.init_norm(cfg.d_model, cfg.norm_type, cfg.use_bias),
        "attn": attn.init_attention(cfg),
    }
    if not cfg.parallel_residual:
        spec["mlp_norm"] = L.init_norm(cfg.d_model, cfg.norm_type, cfg.use_bias)
    if cfg.family == "moe" or (cfg.moe is not None and cfg.family != "dense"):
        spec["moe"] = moe_mod.init_moe(cfg)
    else:
        spec["mlp"] = L.init_mlp(cfg.d_model, cfg.d_ff, cfg.mlp_type, cfg.use_bias)
    return spec


def apply_block(
    params,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: dict | None = None,
    cache_position=None,
):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(params["attn_norm"], x, cfg.norm_type, cfg.norm_eps)
    attn_out, new_cache = attn.apply_attention(
        params["attn"], cfg, h, positions,
        cache=cache, cache_position=cache_position,
        window=cfg.sliding_window,
    )
    if cfg.parallel_residual:
        # command-r style: attention and FFN read the same normed input
        if "moe" in params:
            ffn_out, aux = moe_mod.apply_moe(params["moe"], cfg, h)
        else:
            ffn_out = L.apply_mlp(params["mlp"], h, cfg.mlp_type)
        x = x + attn_out + ffn_out
    else:
        x = x + attn_out
        h = L.apply_norm(params["mlp_norm"], x, cfg.norm_type, cfg.norm_eps)
        if "moe" in params:
            ffn_out, aux = moe_mod.apply_moe(params["moe"], cfg, h)
        else:
            ffn_out = L.apply_mlp(params["mlp"], h, cfg.mlp_type)
        x = x + ffn_out
    x = shard(x, "batch", "seq", "embed")
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Embeddings / head
# ---------------------------------------------------------------------------


def init_lm_shell(cfg: ModelConfig):
    spec = {
        "embed": L.init_embedding(cfg.vocab_size, cfg.d_model),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm_type, cfg.use_bias),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = {
            "w": ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        }
    return spec


def embed_tokens(params, cfg: ModelConfig, tokens, pixel_embeds=None):
    x = L.apply_embedding(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))
    if pixel_embeds is not None:
        # VLM stub frontend: precomputed patch embeddings occupy the first
        # n_image_tokens positions (InternVL-style prefix)
        k = pixel_embeds.shape[1]
        x = jnp.concatenate(
            [pixel_embeds.astype(x.dtype), x[:, k:, :]], axis=1
        )
    return shard(x, "batch", "seq", "embed")


def lm_logits(params, cfg: ModelConfig, x):
    x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    if cfg.tie_embeddings:
        return L.apply_unembed(params["embed"], x, cfg.attn_logit_softcap)
    logits = L.apply_dense(params["lm_head"], x)
    return shard(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Dense / MoE LM (homogeneous stack)
# ---------------------------------------------------------------------------


def init_lm(cfg: ModelConfig):
    if cfg.mla is not None:
        return init_latent_lm(cfg)
    spec = init_lm_shell(cfg)
    spec["blocks"] = stack_specs(init_block(cfg), cfg.n_layers)
    return spec


def forward_lm(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    pixel_embeds: jnp.ndarray | None = None,
):
    """Training/eval forward.  Returns (logits, aux_loss)."""
    if cfg.mla is not None:
        return forward_latent_lm(params, cfg, tokens)
    x = embed_tokens(params, cfg, tokens, pixel_embeds)
    positions = jnp.arange(tokens.shape[1])

    def body(carry, block_params):
        x, aux = carry
        x, _, a = apply_block(block_params, cfg, x, positions)
        return (x, aux + a), None

    (x, aux), _ = scan_layers(
        cfg, _remat(body, cfg), (x, jnp.zeros((), jnp.float32)),
        params["blocks"],
    )
    return lm_logits(params, cfg, x), aux / max(cfg.n_layers, 1)


def prefill_lm(params, cfg: ModelConfig, tokens, max_len: int,
               pixel_embeds=None):
    """Prefill: forward + build the KV cache.  Returns (logits, cache)."""
    if cfg.mla is not None:
        return prefill_latent_lm(params, cfg, tokens, max_len)
    B, S = tokens.shape
    x = embed_tokens(params, cfg, tokens, pixel_embeds)
    positions = jnp.arange(S)
    dtype = jnp.dtype(cfg.compute_dtype)
    init_cache = attn.init_kv_cache(cfg, B, max_len, dtype)

    def body(carry, block_params):
        x = carry
        x, new_cache, _ = apply_block(
            block_params, cfg, x, positions,
            cache=init_cache, cache_position=jnp.zeros((), jnp.int32),
        )
        return x, new_cache

    x, caches = scan_layers(cfg, _remat(body, cfg), x, params["blocks"])
    return lm_logits(params, cfg, x[:, -1:, :]), caches


def decode_lm(params, cfg: ModelConfig, tokens_new, caches, position):
    """One decode step.  tokens_new [B,1]; caches stacked [L,...]."""
    if cfg.mla is not None:
        return decode_latent_lm(params, cfg, tokens_new, caches, position)
    x = embed_tokens(params, cfg, tokens_new)
    positions = jnp.full((tokens_new.shape[0], 1), position, jnp.int32)

    def body(x, xs):
        block_params, cache = xs
        x, new_cache, _ = apply_block(
            block_params, cfg, x, positions,
            cache=cache, cache_position=position,
        )
        return x, new_cache

    x, new_caches = scan_layers(cfg, body, x, (params["blocks"], caches))
    return lm_logits(params, cfg, x), new_caches


# ---------------------------------------------------------------------------
# Latent-attention LM: leading dense layers, then the scanned MoE stack
# ---------------------------------------------------------------------------
#
# Parameters and caches hold one stacked tree per stack: ``dense_blocks``
# (the leading dense layers, where there are any) and ``blocks``.  With
# experts, the cache also holds ``moe_counts`` (int32 [2]): the (token, held
# expert) pairs and the held experts hit, summed over the layers of every
# decode step since the prefill, which leaves it at zero.


def latent_stacks(cfg: ModelConfig) -> list:
    """``(name, layers, dense)`` of each scanned stack, in order."""
    stacks = []
    if cfg.n_dense_layers:
        stacks.append(("dense_blocks", cfg.n_dense_layers, True))
    stacks.append(("blocks", cfg.n_layers - cfg.n_dense_layers,
                   cfg.moe is None))
    return stacks


def init_latent_block(cfg: ModelConfig, dense: bool):
    spec = {
        "attn_norm": L.init_norm(cfg.d_model, cfg.norm_type),
        "attn": mla_mod.init_mla(cfg),
        "mlp_norm": L.init_norm(cfg.d_model, cfg.norm_type),
    }
    if dense:
        spec["mlp"] = L.init_mlp(cfg.d_model, cfg.d_ff, cfg.mlp_type)
    else:
        spec["moe"] = moe_mod.init_moe_ep(cfg)
    return spec


def init_latent_lm(cfg: ModelConfig):
    spec = init_lm_shell(cfg)
    for name, n, dense in latent_stacks(cfg):
        spec[name] = stack_specs(init_latent_block(cfg, dense), n)
    return spec


def init_latent_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    one = mla_mod.init_cache(cfg, batch, max_len, dtype)
    cache = {name: jax.tree_util.tree_map(
        lambda a, n=n: jnp.broadcast_to(a, (n,) + a.shape), one)
        for name, n, _ in latent_stacks(cfg)}
    if cfg.moe is not None:
        cache["moe_counts"] = jnp.zeros((2,), jnp.int32)
    return cache


def apply_latent_block(params, cfg: ModelConfig, x, positions, *,
                       cache=None, position=None, max_len=None):
    """One layer; the attention's modes are ``mla.apply_mla``'s.  Returns
    (x, cache, counts): counts as ``moe.apply_moe_ep`` gives them, zero for
    a dense layer."""
    h = L.apply_norm(params["attn_norm"], x, cfg.norm_type, cfg.norm_eps)
    out, cache = mla_mod.apply_mla(params["attn"], cfg, h, positions,
                                   cache=cache, position=position,
                                   max_len=max_len)
    x = x + out
    h = L.apply_norm(params["mlp_norm"], x, cfg.norm_type, cfg.norm_eps)
    if "moe" in params:
        out, counts = moe_mod.apply_moe_ep(params["moe"], cfg, h)
    else:
        out = L.apply_mlp(params["mlp"], h, cfg.mlp_type)
        counts = jnp.zeros((2,), jnp.int32)
    x = shard(x + out, "batch", "seq", "embed")
    return x, cache, counts


def forward_latent_lm(params, cfg: ModelConfig, tokens):
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])

    def body(x, block_params):
        x, _, _ = apply_latent_block(block_params, cfg, x, positions)
        return x, None

    for name, _, _ in latent_stacks(cfg):
        x, _ = scan_layers(cfg, _remat(body, cfg), x, params[name])
    # the dropless layer has no auxiliary loss (noaux_tc routing)
    return lm_logits(params, cfg, x), jnp.zeros((), jnp.float32)


def prefill_latent_lm(params, cfg: ModelConfig, tokens, max_len: int):
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])

    def body(x, block_params):
        x, cache, _ = apply_latent_block(block_params, cfg, x, positions,
                                         max_len=max_len)
        return x, cache

    caches = {}
    for name, _, _ in latent_stacks(cfg):
        x, caches[name] = scan_layers(cfg, _remat(body, cfg), x,
                                      params[name])
    if cfg.moe is not None:
        caches["moe_counts"] = jnp.zeros((2,), jnp.int32)
    return lm_logits(params, cfg, x[:, -1:, :]), caches


def decode_latent_lm(params, cfg: ModelConfig, tokens_new, caches, position):
    x = embed_tokens(params, cfg, tokens_new)
    positions = jnp.full((tokens_new.shape[0], 1), position, jnp.int32)

    def body(carry, xs):
        x, counts = carry
        block_params, cache = xs
        x, cache, c = apply_latent_block(block_params, cfg, x, positions,
                                         cache=cache, position=position)
        return (x, counts + c), cache

    carry = (x, jnp.zeros((2,), jnp.int32))
    new_caches = {}
    for name, _, _ in latent_stacks(cfg):
        carry, new_caches[name] = scan_layers(
            cfg, body, carry, (params[name], caches[name]))
    x, counts = carry
    if "moe_counts" in caches:
        new_caches["moe_counts"] = caches["moe_counts"] + counts
    return lm_logits(params, cfg, x), new_caches


# ---------------------------------------------------------------------------
# Zamba2 hybrid: Mamba2 backbone + shared attention block with LoRA
# ---------------------------------------------------------------------------


def zamba_structure(cfg: ModelConfig):
    period = cfg.zamba.shared_period
    n_groups = cfg.n_layers // period
    tail = cfg.n_layers - n_groups * period
    return n_groups, period, tail


def init_shared_block(cfg: ModelConfig):
    """The shared transformer block (attention + MLP), applied repeatedly."""
    return {
        "attn_norm": L.init_norm(cfg.d_model, cfg.norm_type),
        "attn": attn.init_attention(cfg),
        "mlp_norm": L.init_norm(cfg.d_model, cfg.norm_type),
        "mlp": L.init_mlp(cfg.d_model, cfg.d_ff, cfg.mlp_type),
    }


def init_lora(cfg: ModelConfig, n_apps: int):
    r = cfg.zamba.lora_rank
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    return {
        "qkv_a": ParamSpec((n_apps, d, r), ("blocks", "embed", "rank")),
        "qkv_b": ParamSpec(
            (n_apps, r, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
            ("blocks", "rank", "qkv"), init="zeros",
        ),
        "mlp_a": ParamSpec((n_apps, d, r), ("blocks", "embed", "rank")),
        "mlp_b": ParamSpec((n_apps, r, cfg.d_ff), ("blocks", "rank", "mlp"),
                           init="zeros"),
    }


def init_zamba(cfg: ModelConfig):
    n_groups, period, tail = zamba_structure(cfg)
    mamba_spec = {
        "norm": L.init_norm(cfg.d_model, cfg.norm_type),
        "mamba": ssm_mod.init_mamba(cfg),
    }
    spec = init_lm_shell(cfg)
    spec["groups"] = stack_specs(
        stack_specs(mamba_spec, period, "layers"), n_groups, "blocks"
    )
    if tail:
        spec["tail"] = stack_specs(mamba_spec, tail, "layers")
    spec["shared"] = init_shared_block(cfg)
    spec["lora"] = init_lora(cfg, n_groups)
    return spec


def _apply_mamba_layer(p, cfg, x, cache=None, prefill=False):
    h = L.apply_norm(p["norm"], x, cfg.norm_type, cfg.norm_eps)
    out, new_cache = ssm_mod.apply_mamba(
        p["mamba"], cfg, h, cache=cache, return_cache=prefill
    )
    return x + out, new_cache


def _apply_shared_with_lora(shared, lora_slice, cfg, x, positions,
                            cache=None, cache_position=None):
    """Shared attention block; LoRA delta on the fused QKV and MLP-up."""
    hd = cfg.resolved_head_dim
    nq = cfg.n_heads * hd
    nk = cfg.n_kv_heads * hd
    h = L.apply_norm(shared["attn_norm"], x, cfg.norm_type, cfg.norm_eps)
    # base QKV + low-rank per-application delta
    delta = (h @ lora_slice["qkv_a"].astype(h.dtype)) @ lora_slice[
        "qkv_b"
    ].astype(h.dtype)
    dq, dk, dv = jnp.split(delta, [nq, nq + nk], axis=-1)
    ap = shared["attn"]
    q = L.apply_dense(ap["wq"], h) + dq
    k = L.apply_dense(ap["wk"], h) + dk
    v = L.apply_dense(ap["wv"], h) + dv
    B, S = h.shape[0], h.shape[1]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if cache is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_position, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_position, 1)
        new_cache = {"k": ck, "v": cv}
        T = ck.shape[1]
        valid = jnp.arange(T)[None, :] < cache_position + S
        valid = jnp.broadcast_to(valid, (B, T))
        out = attn.attend_xla(q, ck, cv, causal=True, q_positions=positions,
                              kv_positions=jnp.arange(T), kv_valid=valid)
    else:
        out = attn.attend_xla(q, k, v, causal=True, q_positions=positions,
                              kv_positions=positions)
    x = x + L.apply_dense(ap["wo"], out.reshape(B, S, nq))
    h = L.apply_norm(shared["mlp_norm"], x, cfg.norm_type, cfg.norm_eps)
    dup = (h @ lora_slice["mlp_a"].astype(h.dtype)) @ lora_slice["mlp_b"].astype(
        h.dtype
    )
    gate = jax.nn.silu(L.apply_dense(shared["mlp"]["gate"], h))
    up = L.apply_dense(shared["mlp"]["up"], h) + dup
    x = x + L.apply_dense(shared["mlp"]["down"], gate * up)
    return x, new_cache


def forward_zamba(params, cfg: ModelConfig, tokens):
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])
    n_groups, period, tail = zamba_structure(cfg)

    def inner(x, layer_params):
        x, _ = _apply_mamba_layer(layer_params, cfg, x)
        return x, None

    def outer(x, xs):
        group_params, lora_slice = xs
        x, _ = scan_layers(cfg, _remat(inner, cfg), x, group_params)
        x, _ = _apply_shared_with_lora(
            params["shared"], lora_slice, cfg, x, positions
        )
        return x, None

    x, _ = scan_layers(cfg, outer, x, (params["groups"], params["lora"]))
    if tail:
        x, _ = scan_layers(cfg, _remat(inner, cfg), x, params["tail"])
    return lm_logits(params, cfg, x), jnp.zeros((), jnp.float32)


def prefill_zamba(params, cfg: ModelConfig, tokens, max_len: int):
    """Prompt pass building all decode caches (SSM states + shared KV)."""
    x = embed_tokens(params, cfg, tokens)
    B, S = tokens.shape
    positions = jnp.arange(S)
    dtype = jnp.dtype(cfg.compute_dtype)
    zero_kv = attn.init_kv_cache(cfg, B, max_len, dtype)

    def inner(x, layer_params):
        x, cache = _apply_mamba_layer(layer_params, cfg, x, prefill=True)
        return x, cache

    def outer(x, xs):
        group_params, lora_slice = xs
        x, group_cache = scan_layers(cfg, inner, x, group_params)
        x, shared_cache = _apply_shared_with_lora(
            params["shared"], lora_slice, cfg, x, positions,
            cache=zero_kv, cache_position=jnp.zeros((), jnp.int32),
        )
        return x, (group_cache, shared_cache)

    n_groups, period, tail = zamba_structure(cfg)
    x, (group_caches, shared_caches) = scan_layers(
        cfg, outer, x, (params["groups"], params["lora"])
    )
    caches = {"groups": group_caches, "shared": shared_caches, "tail": None}
    if tail:
        x, tail_caches = scan_layers(cfg, inner, x, params["tail"])
        caches["tail"] = tail_caches
    return lm_logits(params, cfg, x[:, -1:, :]), caches


def init_zamba_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    n_groups, period, tail = zamba_structure(cfg)

    def stack(n, tree):
        return jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (n,) + leaf.shape), tree
        )

    one = ssm_mod.init_mamba_cache(cfg, batch, dtype)
    return {
        "groups": stack(n_groups, stack(period, one)),
        "tail": stack(tail, one) if tail else None,
        "shared": stack(n_groups, attn.init_kv_cache(cfg, batch, max_len, dtype)),
    }


def decode_zamba(params, cfg: ModelConfig, tokens_new, caches, position):
    x = embed_tokens(params, cfg, tokens_new)
    positions = jnp.full((tokens_new.shape[0], 1), position, jnp.int32)
    n_groups, period, tail = zamba_structure(cfg)

    def inner(x, xs):
        layer_params, cache = xs
        x, new_cache = _apply_mamba_layer(layer_params, cfg, x, cache=cache)
        return x, new_cache

    def outer(x, xs):
        group_params, lora_slice, group_cache, shared_cache = xs
        x, new_group_cache = scan_layers(cfg, inner, x, (group_params, group_cache))
        x, new_shared = _apply_shared_with_lora(
            params["shared"], lora_slice, cfg, x, positions,
            cache=shared_cache, cache_position=position,
        )
        return x, (new_group_cache, new_shared)

    x, (new_groups, new_shared) = scan_layers(
        cfg, outer, x,
        (params["groups"], params["lora"], caches["groups"], caches["shared"]),
    )
    new_caches = {"groups": new_groups, "shared": new_shared, "tail": None}
    if tail:
        x, new_tail = scan_layers(cfg, inner, x, (params["tail"], caches["tail"]))
        new_caches["tail"] = new_tail
    return lm_logits(params, cfg, x), new_caches


# ---------------------------------------------------------------------------
# xLSTM stack
# ---------------------------------------------------------------------------


def xlstm_structure(cfg: ModelConfig):
    every = cfg.xlstm.slstm_every
    n_super = cfg.n_layers // every
    assert n_super * every == cfg.n_layers, "xlstm layers must tile"
    return n_super, every - 1  # (super-blocks, mLSTM per super-block)


def init_xlstm(cfg: ModelConfig):
    n_super, n_m = xlstm_structure(cfg)
    spec = init_lm_shell(cfg)
    spec["super"] = {
        "mlstm": stack_specs(
            stack_specs(xlstm_mod.init_mlstm_block(cfg), n_m, "layers"),
            n_super, "blocks",
        ),
        "slstm": stack_specs(xlstm_mod.init_slstm_block(cfg), n_super, "blocks"),
    }
    return spec


def forward_xlstm(params, cfg: ModelConfig, tokens):
    x = embed_tokens(params, cfg, tokens)

    def inner(x, p):
        x, _ = xlstm_mod.apply_mlstm_block(p, cfg, x)
        return x, None

    def outer(x, xs):
        mlstm_params, slstm_params = xs
        x, _ = scan_layers(cfg, _remat(inner, cfg), x, mlstm_params)
        x, _ = xlstm_mod.apply_slstm_block(slstm_params, cfg, x)
        return x, None

    x, _ = scan_layers(
        cfg, outer, x, (params["super"]["mlstm"], params["super"]["slstm"])
    )
    return lm_logits(params, cfg, x), jnp.zeros((), jnp.float32)


def prefill_xlstm(params, cfg: ModelConfig, tokens):
    """Prompt pass building mLSTM (C,n,m,conv) and sLSTM states."""
    x = embed_tokens(params, cfg, tokens)

    def inner(x, p):
        x, cache = xlstm_mod.apply_mlstm_block(p, cfg, x, return_cache=True)
        return x, cache

    def outer(x, xs):
        mlstm_params, slstm_params = xs
        x, m_caches = scan_layers(cfg, inner, x, mlstm_params)
        x, s_cache = xlstm_mod.apply_slstm_block(
            slstm_params, cfg, x, return_cache=True
        )
        return x, (m_caches, s_cache)

    x, (m_caches, s_caches) = scan_layers(
        cfg, outer, x, (params["super"]["mlstm"], params["super"]["slstm"])
    )
    return (
        lm_logits(params, cfg, x[:, -1:, :]),
        {"mlstm": m_caches, "slstm": s_caches},
    )


def init_xlstm_cache(cfg: ModelConfig, batch: int, dtype):
    n_super, n_m = xlstm_structure(cfg)

    def stack(n, tree):
        return jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (n,) + leaf.shape), tree
        )

    return {
        "mlstm": stack(n_super, stack(n_m, xlstm_mod.init_mlstm_cache(
            cfg, batch, dtype))),
        "slstm": stack(
            n_super, {"state": xlstm_mod.init_slstm_state(cfg, batch)}
        ),
    }


def decode_xlstm(params, cfg: ModelConfig, tokens_new, caches, position):
    x = embed_tokens(params, cfg, tokens_new)

    def inner(x, xs):
        p, cache = xs
        x, new_cache = xlstm_mod.apply_mlstm_block(p, cfg, x, cache=cache)
        return x, new_cache

    def outer(x, xs):
        mlstm_params, slstm_params, mlstm_cache, slstm_cache = xs
        x, new_m = scan_layers(cfg, inner, x, (mlstm_params, mlstm_cache))
        x, new_s = xlstm_mod.apply_slstm_block(
            slstm_params, cfg, x, cache=slstm_cache
        )
        return x, (new_m, new_s)

    x, (new_m, new_s) = scan_layers(
        cfg, outer, x,
        (params["super"]["mlstm"], params["super"]["slstm"],
         caches["mlstm"], caches["slstm"]),
    )
    return lm_logits(params, cfg, x), {"mlstm": new_m, "slstm": new_s}
