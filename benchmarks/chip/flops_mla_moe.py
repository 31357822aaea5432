"""Analytic operation and byte counts of a latent-attention MoE decoder LM
(the DeepSeek-V3 block) holding a share of its routed experts, from its
sizes.

``cfg`` is the ``model`` section of a configuration file (the keys of
``ModelConfig``, with its ``mla`` and ``moe`` sections).  A multiply-add
counts 2 FLOPs.  The leading ``n_dense_layers`` have a SwiGLU of ``d_ff``;
every other layer routes each token to ``top_k`` of ``router_experts`` and
computes the pairs that land on the ``n_experts`` held here: in
expectation ``top_k x n_experts / router_experts`` experts a token (6 x
8 / 64 = 0.75 for one chip of eight).  Attention is counted at its causal
work: query ``i`` attends ``i + 1`` keys.  Prefill runs the expanded path
(per head and key: ``nope + rope`` score and ``v`` value multiply-adds);
decode the absorbed one (``kv_lora_rank + rope`` score and
``kv_lora_rank`` value multiply-adds).  Projections that the absorbed path
folds (``W_uk``, ``W_uv``) multiply as many weights as ``Wkv_b`` does in
the expanded path, so a token's matmul weights are the same on both.  The
embedding lookup is a gather, not a matmul.
"""

from __future__ import annotations

#: bytes of an element at each compute dtype
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mla_params(cfg: dict) -> int:
    """One layer's attention weights: Wq, Wkv_a, Wkv_b and Wo."""
    m, d, h = cfg["mla"], cfg["d_model"], cfg["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (d * h * qk + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * cfg["d_model"] * cfg["moe"]["d_ff"]


def moe_fixed_params(cfg: dict) -> int:
    """One expert layer's weights that every token multiplies: the router
    and the shared experts."""
    e = cfg["moe"]
    return cfg["d_model"] * (e["router_experts"] + 3 * e.get("shared_d_ff", 0))


def moe_layers(cfg: dict) -> int:
    return cfg["n_layers"] - cfg.get("n_dense_layers", 0)


def routed_per_token(cfg: dict) -> float:
    """Held experts a token is routed to, in expectation."""
    e = cfg["moe"]
    return e["top_k"] * e["n_experts"] / e["router_experts"]


def head_params(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def body_params(cfg: dict, experts_per_token: float) -> float:
    """Weights a token multiplies in the layers, with
    ``experts_per_token`` held experts in each expert layer."""
    dense = cfg.get("n_dense_layers", 0)
    return (cfg["n_layers"] * mla_params(cfg)
            + dense * 3 * cfg["d_model"] * cfg["d_ff"]
            + moe_layers(cfg) * (moe_fixed_params(cfg)
                                 + experts_per_token * expert_params(cfg)))


def total_params(cfg: dict) -> int:
    """All parameters held here: the matmuls with every held expert, the
    embedding table, the routers' correction biases and the norm
    scales."""
    d, e = cfg["d_model"], cfg["moe"]
    norms = cfg["n_layers"] * (2 * d + cfg["mla"]["kv_lora_rank"]) + d
    return int(body_params(cfg, e["n_experts"]) + 2 * head_params(cfg)
               + moe_layers(cfg) * e["router_experts"] + norms)


def _keys(first: int, count: int) -> float:
    return count * first + count * (count + 1) / 2


def prefill_attention_flops(cfg: dict, first: int, count: int) -> float:
    """Expanded causal attention of queries ``first .. first + count - 1``."""
    m = cfg["mla"]
    per_key = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return 2.0 * cfg["n_layers"] * cfg["n_heads"] * per_key * _keys(first,
                                                                    count)


def decode_attention_flops(cfg: dict, first: int, count: int) -> float:
    """Absorbed causal attention over the latent cache."""
    m = cfg["mla"]
    per_key = 2 * m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return 2.0 * cfg["n_layers"] * cfg["n_heads"] * per_key * _keys(first,
                                                                    count)


def prefill_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of a serving prefill over ``batch`` prompts of ``seq``
    tokens, which projects only each prompt's last position to the
    vocabulary."""
    body = 2.0 * body_params(cfg, routed_per_token(cfg)) * seq
    return batch * (body + 2.0 * head_params(cfg)
                    + prefill_attention_flops(cfg, 0, seq))


def decode_flops(cfg: dict, batch: int, position: int) -> float:
    """Forward FLOPs of one decode step for a token at ``position``."""
    work = body_params(cfg, routed_per_token(cfg)) + head_params(cfg)
    return batch * (2.0 * work + decode_attention_flops(cfg, position, 1))


def generate_flops(cfg: dict, batch: int, prompt: int, new_tokens: int
                   ) -> float:
    """A served batch: the prefill (which yields the first new token) and
    ``new_tokens - 1`` decode steps at positions ``prompt ..``."""
    total = prefill_flops(cfg, batch, prompt)
    for i in range(new_tokens - 1):
        total += decode_flops(cfg, batch, prompt + i)
    return total


def _width(cfg: dict) -> int:
    return _BYTES[cfg.get("compute_dtype", "bfloat16")]


def latent_cache_bytes(cfg: dict, batch: int, position: float) -> float:
    """The latent cache of every layer read up to ``position``."""
    m = cfg["mla"]
    return (cfg["n_layers"] * batch * (position + 1)
            * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * _width(cfg))


def mla_least_bytes(cfg: dict, batch: int, position: float) -> float:
    """Least HBM bytes of a decode step's attention: every layer's
    attention weights once and its latent cache up to ``position``."""
    return (cfg["n_layers"] * mla_params(cfg) * _width(cfg)
            + latent_cache_bytes(cfg, batch, position))


def moe_least_bytes(cfg: dict, hit_share: float) -> float:
    """Least HBM bytes of a decode step's expert layers: the router and
    shared experts once, and the held experts that a token was routed to
    (``hit_share`` of them)."""
    e = cfg["moe"]
    held = hit_share * e["n_experts"] * expert_params(cfg)
    return moe_layers(cfg) * (moe_fixed_params(cfg) + held) * _width(cfg)


def decode_least_bytes(cfg: dict, batch: int, position: float,
                       hit_share: float) -> float:
    """Least HBM bytes of one decode step: every matmul weight once, held
    experts as far as ``hit_share`` of them were hit, and the latent cache
    up to ``position``, at the compute dtype."""
    dense = cfg.get("n_dense_layers", 0) * 3 * cfg["d_model"] * cfg["d_ff"]
    return (mla_least_bytes(cfg, batch, position)
            + moe_least_bytes(cfg, hit_share)
            + (dense + head_params(cfg)) * _width(cfg))


def hit_share(stats: dict, cfg: dict) -> float | None:
    """The share of held experts that a decode step's tokens hit, over the
    expert layers and steps the engine's counter covers."""
    steps = stats.get("moe_decode_steps", 0)
    if not steps:
        return None
    slots = steps * moe_layers(cfg) * cfg["moe"]["n_experts"]
    return stats["moe_experts_hit"] / slots
