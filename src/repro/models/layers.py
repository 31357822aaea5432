"""Primitive layers: norms, projections, embeddings, RoPE, MLPs.

Parameters are plain pytrees (nested dicts of jnp arrays).  Every layer has
an ``init_*`` returning ``(params, axes)`` where ``axes`` mirrors the params
pytree with tuples of logical axis names (consumed by
:mod:`repro.parallel.sharding`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard


@dataclasses.dataclass
class ParamSpec:
    """A parameter leaf paired with its logical axes (init-time only)."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones
    scale: float = 1.0


def _make(key, spec: ParamSpec, dtype) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    # a matrix's input dimension is its second-to-last: leading axes stack
    # layers or experts ([L, d_in, d_out], [L, E, d_in, d_out])
    fan_in = spec.shape[-2] if len(spec.shape) > 1 else spec.shape[-1]
    std = spec.scale / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.truncated_normal(key, -3, 3, spec.shape, jnp.float32)
            * std).astype(dtype)


def materialize(key, specs, dtype):
    """Build (params, axes) pytrees from a matching pytree of ParamSpec."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    params = treedef.unflatten([_make(k, s, dtype) for k, s in zip(keys, leaves)])
    axes = treedef.unflatten([s.axes for s in leaves])
    return params, axes


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(d: int, norm_type: str, use_bias: bool = False):
    spec = {"scale": ParamSpec((d,), ("embed",), init="ones")}
    if norm_type == "layernorm" and use_bias:
        spec["bias"] = ParamSpec((d,), ("embed",), init="zeros")
    return spec


def apply_norm(params, x, norm_type: str, eps: float):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Dense / Embedding
# ---------------------------------------------------------------------------


def init_dense(
    d_in: int,
    d_out: int,
    axes: tuple[str | None, str | None],
    use_bias: bool = False,
    scale: float = 1.0,
):
    spec = {"w": ParamSpec((d_in, d_out), axes, scale=scale)}
    if use_bias:
        spec["b"] = ParamSpec((d_out,), (axes[1],), init="zeros")
    return spec


def apply_dense(params, x):
    w = params["w"].astype(x.dtype)
    y = x @ w
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def init_embedding(vocab: int, d: int):
    return {"table": ParamSpec((vocab, d), ("vocab", "embed"), scale=1.0)}


def apply_embedding(params, tokens, compute_dtype):
    return params["table"].astype(compute_dtype)[tokens]


def apply_unembed(params, x, logit_softcap: float | None = None):
    """Project to vocabulary (optionally shared with the embedding table)."""
    table = params["table"].astype(x.dtype)
    logits = x @ table.T
    if logit_softcap:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    return logits


def cast_at_use(path: tuple[str, ...]) -> bool:
    """Whether every program casts the parameter leaf at ``path`` (its dict
    keys from the root) to the compute dtype before it reads it.

    So are a dense layer's ``w`` and ``b`` (``apply_dense``, whose
    activations are always in the compute dtype), an embedding ``table``
    (``apply_embedding``, ``apply_unembed``) and the MoE expert stacks
    (``moe.apply_moe``).  The MoE router's ``w`` is read in float32, as are
    norm scales and the SSM decay terms: those are not.  The serving view
    (``Model.serving_params``) casts such leaves once, which gives the very
    values these casts make at every use.
    """
    if "router" in path:
        return False
    if len(path) > 1 and path[-2] == "experts":
        return True
    return path[-1] in ("w", "b", "table")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim/2]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(d_model: int, d_ff: int, mlp_type: str, use_bias: bool = False):
    if mlp_type == "swiglu":
        return {
            "gate": init_dense(d_model, d_ff, ("embed", "mlp"), use_bias),
            "up": init_dense(d_model, d_ff, ("embed", "mlp"), use_bias),
            "down": init_dense(d_ff, d_model, ("mlp", "embed"), use_bias),
        }
    return {
        "up": init_dense(d_model, d_ff, ("embed", "mlp"), use_bias),
        "down": init_dense(d_ff, d_model, ("mlp", "embed"), use_bias),
    }


def apply_mlp(params, x, mlp_type: str):
    if mlp_type == "swiglu":
        h = jax.nn.silu(apply_dense(params["gate"], x)) * apply_dense(
            params["up"], x
        )
    else:
        h = jax.nn.gelu(apply_dense(params["up"], x), approximate=True)
    h = shard(h, *(("batch",) + ("seq",) * (h.ndim - 2) + ("mlp",)))
    return apply_dense(params["down"], h)


# ---------------------------------------------------------------------------
# Softmax cross-entropy
# ---------------------------------------------------------------------------


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, mask=None):
    """Mean next-token loss.  logits [..., V] fp32; labels int [...]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
