"""Jit'd public wrappers over the Pallas kernels, as the models call them.

The kernels compile for the TPU.  Nothing here falls back to interpret
mode: on a host without a TPU a call fails, unless the caller asked for
interpretation itself (the kernel tests do, with ``interpret=True`` or
``pltpu.force_tpu_interpret_mode()``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import moe_gmm as _gmm

#: sublane tiling of a TPU vreg: a block's second-to-last dimension must be
#: a multiple of it (or the whole dimension)
_SUBLANES = 8


def _seq_block(n: int, preferred: int) -> int:
    """Largest block <= ``preferred`` that tiles ``n`` and the TPU accepts.

    ``preferred`` (a power of two >= 8) is halved while it does not divide
    ``n``, but never below one vreg of sublanes.
    """
    if n <= preferred:
        return n
    b = preferred
    while n % b and b > _SUBLANES:
        b //= 2
    if n % b:
        raise ValueError(
            f"sequence length {n} has no block of {_SUBLANES}..{preferred} "
            f"rows that tiles it; pad it to a multiple of {_SUBLANES}"
        )
    return b


def flash_attention(q, k, v, causal=True, window=None, logit_softcap=None,
                    block_q=None, block_k=None):
    S, T = q.shape[1], k.shape[1]
    return _fa.flash_attention(
        q, k, v,
        causal=causal, window=window, logit_softcap=logit_softcap,
        block_q=block_q or _seq_block(S, _fa.DEFAULT_BLOCK_Q),
        block_k=block_k or _seq_block(T, _fa.DEFAULT_BLOCK_K),
    )


# The scan's chunk is the lane dimension of dt's block, so it is never
# shrunk to fit: it tiles S exactly or S is one chunk (the kernel raises
# otherwise).  The grouped matmul's blocks are fixed MXU tiles.
mamba_scan = _ms.mamba_scan
gmm = _gmm.gmm


def moe_expert_mlp(expert_in: jnp.ndarray, experts: dict, cfg) -> jnp.ndarray:
    """SwiGLU expert FFN via grouped matmuls.  expert_in [(G,)E,C,D]."""
    squeeze = expert_in.ndim == 3
    if squeeze:
        expert_in = expert_in[None]
    G, E, C, D = expert_in.shape
    x = expert_in.reshape(G * E, C, D)
    w_gate = experts["gate"].astype(x.dtype)
    w_up = experts["up"].astype(x.dtype)
    w_down = experts["down"].astype(x.dtype)
    h = jax.nn.silu(gmm(x, w_gate)) * gmm(x, w_up)
    out = gmm(h, w_down)
    out = out.reshape(G, E, C, D)
    return out[0] if squeeze else out
