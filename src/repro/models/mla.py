"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434; the
attention of DeepSeek-V3 and Moonlight), with a latent cache.

Per token ``x`` (no query latent: ``q_lora_rank`` null)::

    q            = x Wq                  per head: q_nope (128) | q_pe (64)
    c | k_pe     = x Wkv_a               c the 512-wide latent, k_pe one head
    c            = rms(c)                (its own RMSNorm)
    k_nope | v   = c Wkv_b               per head: 128 | 128
    q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe)   k_pe shared by every head
    out          = softmax(q . [k_nope, k_pe] / sqrt(192)) v, heads merged, Wo

RoPE rotates consecutive pairs ``(2i, 2i + 1)``, the published pairing of
DeepSeek-V3 (``rope_interleave``), not the halves of ``layers.apply_rope``.

The cache holds ``c`` and the rotated ``k_pe`` of each position (``ckv``
``[B, T, 512]``, ``kpe`` ``[B, T, 64]``): 1,152 bytes a token a layer in
bf16, where the keys and values would take 10,240.  Two paths compute the
same attention:

* expanded (forward, prefill): ``k_nope`` and ``v`` made for every
  position, attention taken in blocks of ``QUERY_BLOCK`` queries so that no
  ``[B, H, S, S]`` score tensor is whole;
* absorbed (decode): ``W_uk`` (the key half of ``Wkv_b``) folded into the
  query and ``W_uv`` (the value half) into the output, so attention runs
  over the latent cache itself and no position is expanded.

Every operation runs under the ``mla`` named scope, which the benchmark's
trace readers look for.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.layers import ParamSpec

SCOPE = "mla"
#: the latent's RMSNorm epsilon: the published code builds that norm with
#: its class default, not the model's ``rms_norm_eps``
KV_NORM_EPS = 1e-6
#: queries a prefill attends at a time: a block's float32 scores at the
#: benchmark's 32 x 2048 prefill take 0.5 GB, where 256 queries would take
#: 1.1 GB and the whole prompt 8.6 GB
QUERY_BLOCK = 128


def init_mla(cfg: ModelConfig):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": L.init_dense(d, h * qk, ("embed", "qkv")),
        "wkv_a": L.init_dense(d, m.kv_lora_rank + m.qk_rope_head_dim,
                              ("embed", None)),
        "kv_norm": {"scale": ParamSpec((m.kv_lora_rank,), (None,),
                                       init="ones")},
        "wkv_b": L.init_dense(m.kv_lora_rank,
                              h * (m.qk_nope_head_dim + m.v_head_dim),
                              (None, "qkv")),
        "wo": L.init_dense(h * m.v_head_dim, d, ("qkv", "embed")),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}


def rope_pairs(x, positions, theta: float):
    """Rotate pairs ``(x[2i], x[2i+1])`` of ``x`` ``[..., S, H, D]`` by
    ``position * theta ** (-2i / D)``; positions broadcast to ``[..., S]``."""
    d = x.shape[-1]
    angles = (positions[..., :, None].astype(jnp.float32)
              * L.rope_frequencies(d, theta))
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _project(params, cfg: ModelConfig, x, positions):
    """Queries ``[B, S, H, nope]`` and ``[B, S, H, rope]``, the normed
    latent ``[B, S, R]`` and the rotated shared key ``[B, S, rope]``."""
    m = cfg.mla
    b, s, _ = x.shape
    q = L.apply_dense(params["wq"], x).reshape(b, s, cfg.n_heads, -1)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = rope_pairs(q_pe, positions, cfg.rope_theta)
    c, k_pe = jnp.split(L.apply_dense(params["wkv_a"], x), [m.kv_lora_rank],
                        axis=-1)
    c = L.apply_norm(params["kv_norm"], c, "rmsnorm", KV_NORM_EPS)
    k_pe = rope_pairs(k_pe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_pe, c, k_pe


def _scale(cfg: ModelConfig) -> float:
    return (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim) ** -0.5


def _expanded(params, cfg: ModelConfig, q_nope, q_pe, c, k_pe):
    """Causal attention of the ``S`` new positions over themselves, keys
    and values expanded from the latent, in blocks of queries (each block
    reads only the keys at or before its last query)."""
    m = cfg.mla
    b, s, h, _ = q_nope.shape
    kv = L.apply_dense(params["wkv_b"], c).reshape(b, s, h, -1)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], k_pe.shape[:2]
                                  + (h, m.qk_rope_head_dim))], axis=-1)
    block = min(QUERY_BLOCK, s)
    outs = []
    for lo in range(0, s, block):
        hi = min(lo + block, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                            preferred_element_type=jnp.float32) * _scale(cfg)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype),
                               v[:, :hi]))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def _absorbed(params, cfg: ModelConfig, q_nope, q_pe, ckv, kpe, positions):
    """Attention of the new positions over the latent cache: ``W_uk``
    folded into the query, ``W_uv`` into the output."""
    m = cfg.mla
    wkv_b = params["wkv_b"]["w"].astype(q_nope.dtype).reshape(
        m.kv_lora_rank, cfg.n_heads, -1)
    w_uk, w_uv = jnp.split(wkv_b, [m.qk_nope_head_dim], axis=-1)
    q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)
    scores = (jnp.einsum("bshc,btc->bhst", q_lat, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_pe, kpe,
                           preferred_element_type=jnp.float32)) * _scale(cfg)
    # [B, S, T]: a query sees the cached positions up to its own
    seen = jnp.arange(ckv.shape[1]) <= positions[..., :, None]
    seen = jnp.broadcast_to(seen, (q_nope.shape[0],) + seen.shape[-2:])
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1)
    o_lat = jnp.einsum("bhst,btc->bshc", probs.astype(ckv.dtype), ckv)
    return jnp.einsum("bshc,chv->bshv", o_lat, w_uv)


def apply_mla(params, cfg: ModelConfig, x, positions, *, cache=None,
              position=None, max_len: int | None = None):
    """Latent attention of ``x`` ``[B, S, D]`` at ``positions``.

    * ``cache`` given (decode): the new positions' latent is written at
      ``position`` and attention runs absorbed over the cache;
    * else (forward, prefill): expanded over ``x``'s own positions, which
      start at 0; with ``max_len`` the latent is returned as a cache of
      that many positions.

    Returns ``(out [B, S, D], cache or None)``.
    """
    with jax.named_scope(SCOPE):
        q_nope, q_pe, c, k_pe = _project(params, cfg, x, positions)
        b, s = x.shape[:2]
        if cache is not None:
            dtype = cache["ckv"].dtype
            cache = {
                "ckv": jax.lax.dynamic_update_slice_in_dim(
                    cache["ckv"], c.astype(dtype), position, 1),
                "kpe": jax.lax.dynamic_update_slice_in_dim(
                    cache["kpe"], k_pe.astype(dtype), position, 1),
            }
            out = _absorbed(params, cfg, q_nope, q_pe, cache["ckv"],
                            cache["kpe"], positions)
        else:
            out = _expanded(params, cfg, q_nope, q_pe, c, k_pe)
            if max_len is not None:
                pad = ((0, 0), (0, max_len - s), (0, 0))
                cache = {"ckv": jnp.pad(c, pad), "kpe": jnp.pad(k_pe, pad)}
        return L.apply_dense(params["wo"], out.reshape(b, s, -1)), cache
