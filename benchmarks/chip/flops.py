"""Analytic operation and byte counts of a dense decoder LM, from its sizes.

``cfg`` is the ``model`` section of a configuration file (the keys of
``ModelConfig``).  A multiply-add counts 2 FLOPs.  Attention is counted at
its causal (needed) work: query ``i`` of a sequence attends ``i + 1`` keys,
so a kernel that skips masked blocks reads the same work as one that
computes and masks them.  The embedding lookup is a gather, not a matmul.
"""

from __future__ import annotations

#: bytes of an element at each compute dtype
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def block_matmul_params(cfg: dict) -> int:
    """Weights of one block's matmuls: q, k, v, o and the MLP."""
    d, hd = cfg["d_model"], head_dim(cfg)
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    n_mlp = 3 if cfg.get("mlp_type", "swiglu") == "swiglu" else 2
    return attn + n_mlp * d * cfg["d_ff"]


def head_params(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Every weight a token's forward pass multiplies: blocks and LM head."""
    return cfg["n_layers"] * block_matmul_params(cfg) + head_params(cfg)


def total_params(cfg: dict) -> int:
    """All parameters: matmuls, the embedding table and the norm scales."""
    d = cfg["d_model"]
    embed = 0 if cfg.get("tie_embeddings") else cfg["vocab_size"] * d
    norms = cfg["n_layers"] * 2 * d + d
    return matmul_params(cfg) + embed + norms


def attention_flops(cfg: dict, first: int, count: int) -> float:
    """Causal score and value FLOPs of queries at positions
    ``first .. first + count - 1`` (each attends position + 1 keys)."""
    keys = count * first + count * (count + 1) / 2
    return 4.0 * cfg["n_layers"] * cfg["n_heads"] * head_dim(cfg) * keys


def prefill_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of a serving prefill over ``batch`` prompts of ``seq``
    tokens, which projects only each prompt's last position to the
    vocabulary."""
    body = 2.0 * cfg["n_layers"] * block_matmul_params(cfg) * seq
    head = 2.0 * head_params(cfg)
    return batch * (body + head + attention_flops(cfg, 0, seq))


def decode_flops(cfg: dict, batch: int, position: int) -> float:
    """Forward FLOPs of one decode step for a token at ``position``."""
    return batch * (2.0 * matmul_params(cfg)
                    + attention_flops(cfg, position, 1))


def generate_flops(cfg: dict, batch: int, prompt: int, new_tokens: int
                   ) -> float:
    """A served batch: the prefill (which yields the first new token) and
    ``new_tokens - 1`` decode steps at positions ``prompt ..``."""
    total = prefill_flops(cfg, batch, prompt)
    for i in range(new_tokens - 1):
        total += decode_flops(cfg, batch, prompt + i)
    return total


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward FLOPs per trained token (3x the forward):
    6 x matmul weights plus the causal attention, averaged over the
    sequence.  Recomputation under remat is not counted."""
    return 3.0 * (2.0 * matmul_params(cfg)
                  + attention_flops(cfg, 0, seq) / seq)


def decode_least_bytes(cfg: dict, batch: int, position: int) -> float:
    """Least HBM bytes of one decode step: every matmul weight once, plus
    the K and V cache read up to ``position``, both at the compute dtype."""
    width = _BYTES[cfg.get("compute_dtype", "bfloat16")]   # the program's default
    kv = (2 * cfg["n_layers"] * batch * (position + 1) * cfg["n_kv_heads"]
          * head_dim(cfg))
    return (matmul_params(cfg) + kv) * width
