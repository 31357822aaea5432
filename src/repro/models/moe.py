"""Mixture-of-Experts: two layers.

* The capacity layer (mixtral, qwen3; below): top-k routing with grouped,
  capacity-bounded dispatch.
* The dropless expert-parallel layer (the latent-attention stack; at the
  end of this file): shared experts beside routed ones, of which it holds a
  share, with grouped (ragged) matmuls over the pairs routed to them.

The capacity layer:

TPU-native (GShard-style) formulation: tokens are split into **groups** (the
group dim shards over the data axes); each group independently builds a small
``[T_g, E, C]`` dispatch/combine tensor and dispatches tokens to experts via
einsums.  With experts sharded over the "model" axis, XLA's SPMD partitioner
turns the dispatch/return einsums into the expert all-to-all — no token
sorting (a GPU idiom that shards badly) required.  Capacity is rounded up to
a multiple of 8 for MXU-friendly shapes; overflow tokens are dropped (their
combine weight is zero), the standard capacity-factor trade-off.

An alternative expert-compute path through the grouped-matmul Pallas kernel
(:mod:`repro.kernels.moe_gmm`) is selected with ``use_gmm=True``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.layers import ParamSpec
from repro.parallel.sharding import shard

DEFAULT_GROUP_SIZE = 2048


def init_moe(cfg: ModelConfig):
    moe = cfg.moe
    d, f, e = cfg.d_model, moe.d_ff, moe.n_experts
    return {
        "router": {
            "w": ParamSpec((d, e), ("embed", "experts"), scale=1.0),
        },
        "experts": {
            "gate": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
            "up": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
            "down": ParamSpec((e, f, d), ("experts", "mlp", "embed")),
        },
    }


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def capacity_for(tokens_per_group: int, cfg: ModelConfig) -> int:
    moe = cfg.moe
    c = int(tokens_per_group * moe.top_k * moe.capacity_factor / moe.n_experts)
    return max(_round_up(max(c, 1), 8), 8)


def group_tokens(n_tokens: int, group_size: int = DEFAULT_GROUP_SIZE) -> int:
    """Number of dispatch groups (must divide the token count)."""
    groups = max(1, n_tokens // group_size)
    while n_tokens % groups:
        groups -= 1
    return groups


def top_k_dispatch(
    logits: jnp.ndarray,  # [G, T, E] router logits (fp32)
    cfg: ModelConfig,
    capacity: int,
):
    """Build dispatch/combine tensors per group.

    Returns (dispatch [G,T,E,C] bf16-ish mask, combine [G,T,E,C], aux_loss).
    """
    moe = cfg.moe
    G, T, E = logits.shape
    k = moe.top_k
    probs = jax.nn.softmax(logits, axis=-1)  # [G,T,E] fp32

    gate_vals, expert_idx = jax.lax.top_k(logits, k)  # [G,T,k]
    gates = jax.nn.softmax(gate_vals, axis=-1)  # normalize over the top-k

    counts = jnp.zeros((G, E), jnp.int32)
    dispatch = jnp.zeros((G, T, E, capacity), jnp.bool_)
    combine = jnp.zeros((G, T, E, capacity), jnp.float32)
    for j in range(k):
        onehot = jax.nn.one_hot(expert_idx[..., j], E, dtype=jnp.int32)  # [G,T,E]
        pos = counts[:, None, :] + jnp.cumsum(onehot, axis=1) - onehot  # [G,T,E]
        fits = (pos < capacity) & (onehot > 0)
        counts = counts + jnp.sum(onehot, axis=1)
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [G,T,E,C]
        placed = slot * fits[..., None].astype(jnp.float32)
        dispatch = dispatch | (placed > 0)
        combine = combine + gates[..., j, None, None] * placed

    # Switch-style load-balance auxiliary loss
    me = jnp.mean(probs, axis=1)                      # [G,E] mean router prob
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32), axis=1
    )                                                  # fraction (top-1 proxy)
    aux = E * jnp.mean(jnp.sum(me * ce, axis=-1))
    return dispatch, combine, aux


def apply_moe(
    params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # [B, S, D]
    group_size: int = DEFAULT_GROUP_SIZE,
    use_gmm: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output [B,S,D], aux_loss scalar)."""
    B, S, D = x.shape
    T = B * S
    groups = group_tokens(T, group_size)
    tg = T // groups
    xg = x.reshape(groups, tg, D)
    xg = shard(xg, "batch", None, "embed")

    logits = (
        xg.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32)
    )  # [G,T,E]
    capacity = capacity_for(tg, cfg)
    dispatch, combine, aux = top_k_dispatch(logits, cfg, capacity)
    dispatch_t = dispatch.astype(x.dtype)
    dispatch_t = shard(dispatch_t, "batch", None, "experts", None)

    # dispatch einsum -> [G, E, C, D]; E sharded over "model" => all-to-all.
    # "expert_capacity" is None by default; overriding it to "model" slot-
    # shards dispatch when n_experts < model-axis size (hillclimb lever).
    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch_t, xg)
    expert_in = shard(expert_in, "batch", "experts", "expert_capacity", "embed")

    if use_gmm:
        from repro.kernels import ops as kernel_ops

        expert_out = kernel_ops.moe_expert_mlp(
            expert_in, params["experts"], cfg
        )
    else:
        w_gate = params["experts"]["gate"].astype(x.dtype)
        w_up = params["experts"]["up"].astype(x.dtype)
        w_down = params["experts"]["down"].astype(x.dtype)
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, w_gate))
        h = h * jnp.einsum("gecd,edf->gecf", expert_in, w_up)
        h = shard(h, "batch", "experts", "expert_capacity", "mlp")
        expert_out = jnp.einsum("gecf,efd->gecd", h, w_down)
    expert_out = shard(expert_out, "batch", "experts", None, "embed")

    out = jnp.einsum(
        "gtec,gecd->gtd", combine.astype(x.dtype), expert_out
    )
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Dropless expert-parallel layer (DeepSeek-V3 style: shared + routed experts)
# ---------------------------------------------------------------------------
#
# The layer is told which experts it holds: ``n_experts`` of the router's
# ``router_experts``, from index ``first_expert``.  It routes every token
# over all of them and computes only its own experts' part of the result:
# the (token, expert) pairs that fall on a held expert, sorted by expert and
# run through grouped (ragged) matmuls, so that no assignment is dropped.
# What the other experts would add is computed where they are held; on one
# chip the layer runs without the exchange that would bring it.

#: tokens dispatched at a time; a larger batch (a prefill) goes through in
#: chunks, which bounds the sorted copy of the tokens at chunk x top_k rows
DROPLESS_CHUNK = 8192


def init_moe_ep(cfg: ModelConfig):
    moe = cfg.moe
    if moe.score_func != "sigmoid" or not moe.norm_topk:
        raise ValueError("the dropless layer scores with a sigmoid and "
                         "renormalises the top k (DeepSeek-V3 routing)")
    d, f, e, r = cfg.d_model, moe.d_ff, moe.n_experts, moe.n_routed
    spec = {
        "router": {
            "w": ParamSpec((d, r), ("embed", None), scale=1.0),
            # the correction bias of noaux_tc routing: it moves which
            # experts are chosen, never their weights
            "bias": ParamSpec((r,), (None,), init="zeros"),
        },
        "experts": {
            "gate": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
            "up": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
            "down": ParamSpec((e, f, d), ("experts", "mlp", "embed")),
        },
    }
    if moe.shared_d_ff:
        spec["shared"] = L.init_mlp(d, moe.shared_d_ff, "swiglu")
    return spec


def route(params, cfg: ModelConfig, x):
    """Top-k experts ``[T, k]`` (router indices) of tokens ``x`` ``[T, D]``
    and their combine weights ``[T, k]`` (float32): chosen by sigmoid score
    plus the correction bias, weighted by the unbiased score, renormalised
    over the top k, times ``routed_scale``."""
    moe = cfg.moe
    logits = x.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores + params["router"]["bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, moe.top_k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, weights * moe.routed_scale


def _held_experts(params, cfg: ModelConfig, x, idx, weights):
    """This chip's experts' part of the output for tokens ``x`` ``[T, D]``
    and the number of pairs routed to each held expert ``[E]``."""
    moe = cfg.moe
    t, d = x.shape
    e, k = moe.n_experts, moe.top_k
    local = idx - moe.first_expert
    held = (local >= 0) & (local < e)
    # pairs on experts held elsewhere sort last, past every group
    slot = jnp.where(held, local, e).reshape(-1)
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[slot].add(1)[:e]
    token = order // k
    xs = x[token]
    w = params["experts"]
    gate = jax.lax.ragged_dot(xs, w["gate"].astype(x.dtype), sizes)
    up = jax.lax.ragged_dot(xs, w["up"].astype(x.dtype), sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                           w["down"].astype(x.dtype), sizes)
    mine = jnp.arange(t * k) < jnp.sum(sizes)
    y = jnp.where(mine[:, None],
                  y.astype(jnp.float32) * weights.reshape(-1)[order][:, None],
                  0.0)
    return jnp.zeros((t, d), jnp.float32).at[token].add(y), sizes


def apply_moe_ep(params, cfg: ModelConfig, x):
    """The layer on ``x`` ``[B, S, D]``.  Returns the output ``[B, S, D]``
    and, as int32 ``[2]``, the (token, held expert) pairs routed here and
    the held experts that got at least one."""
    b, s, d = x.shape
    t = b * s
    chunks = group_tokens(t, DROPLESS_CHUNK)
    xc = x.reshape(chunks, t // chunks, d)

    def one_chunk(xt):
        with jax.named_scope("moe.route"):
            idx, weights = route(params, cfg, xt)
        with jax.named_scope("moe.experts"):
            return _held_experts(params, cfg, xt, idx, weights)

    if chunks == 1:
        out, sizes = one_chunk(xc[0])
        out, sizes = out[None], sizes[None]
    else:
        out, sizes = jax.lax.map(one_chunk, xc)
    out = out.reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("moe.shared"):
            out = out + L.apply_mlp(params["shared"], x, "swiglu").astype(
                jnp.float32)
    sizes = jnp.sum(sizes, axis=0)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return out.astype(x.dtype), counts
