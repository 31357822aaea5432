"""Chunked selective-scan (SSD) Pallas TPU kernel.

TPU adaptation of Mamba2's GPU scan: instead of warp-parallel prefix scans,
the sequence is tiled into chunks; each grid step processes one chunk with
dense MXU matmuls (intra-chunk quadratic term + state in/out projections)
and carries the [P, N] SSM state in VMEM scratch across the sequentially-
executed chunk axis.

grid = (batch, heads, chunks) — chunks innermost (sequential carry).

Layout: the TPU tiles a block's last two dimensions by (8, 128) unless a
dimension is whole, so the head axis is moved out of them.  x and y are
blocked as [B, H, S, P] -> (1, 1, chunk, P); dt comes twice, as a column
[B, H, S, 1] and as a row [B, H, 1, S], because the kernel needs the
per-step log-decay both along the chunk's rows and along its columns; the
per-head A sits whole in SMEM and is read as a scalar.  The chunk is the
lane dimension of dt's row block, so it is a multiple of 128 or all of S.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _mamba_kernel(
    x_ref,       # (1, 1, Q, P)
    dtc_ref,     # (1, 1, Q, 1)  dt as a column
    dtr_ref,     # (1, 1, 1, Q)  dt as a row
    a_ref,       # (H,) in SMEM
    b_ref,       # (1, Q, N)
    c_ref,       # (1, Q, N)
    y_ref,       # (1, 1, Q, P) out
    h_ref,       # scratch: (P, N) f32 carried state
    *,
    chunk: int,
):
    jc = pl.program_id(2)

    @pl.when(jc == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[pl.program_id(1)]                      # scalar (negative)
    x = x_ref[0, 0].astype(jnp.float32)              # [Q, P]
    dt_col = dtc_ref[0, 0].astype(jnp.float32)       # [Q, 1]
    dt_row = dtr_ref[0, 0].astype(jnp.float32)       # [1, Q]
    Bm = b_ref[0].astype(jnp.float32)                # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                # [Q, N]

    # inclusive cumulative log-decay, as a column and as a row:
    # cum[t] = sum_{s <= t} dt[s] * A
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = t_idx >= s_idx                            # [t, s] lower-triangular
    a_col = dt_col * A                               # [Q, 1]
    a_row = dt_row * A                               # [1, Q]
    cum_col = jnp.sum(jnp.where(mask, a_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(t_idx <= s_idx, a_col, 0.0), axis=0,
                      keepdims=True)
    a_total = jnp.sum(a_row, axis=1, keepdims=True)  # [1, 1]

    # intra-chunk quadratic term
    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                # [Q,Q] C_t·B_s
    decay = jnp.exp(jnp.where(mask, cum_col - cum_row, -jnp.inf))
    W = CB * decay * dt_row                          # dt applied at source s
    y = jax.lax.dot_general(
        W, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                # [Q,P]

    # inter-chunk contribution from the carried state
    h = h_ref[...]                                   # [P,N]
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                # [Q,P]

    # state update: h' = exp(a_total) h + sum_s w_s x_s ⊗ B_s
    w_state = jnp.exp(a_total - cum_col) * dt_col    # [Q,1]
    xw = x * w_state                                 # [Q,P]
    h_ref[...] = jnp.exp(a_total) * h + jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                # [P,N]

    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(
    xh: jnp.ndarray,   # [B, S, H, P]
    dt: jnp.ndarray,   # [B, S, H] (softplus'd)
    A: jnp.ndarray,    # [H] (negative)
    Bm: jnp.ndarray,   # [B, S, N]
    Cm: jnp.ndarray,   # [B, S, N]
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} must tile by chunk={chunk}")
    nc = S // chunk

    x_t = xh.transpose(0, 2, 1, 3)                   # [B,H,S,P]
    dt_t = dt.transpose(0, 2, 1)                     # [B,H,S]
    kernel = functools.partial(_mamba_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x_t, dt_t[..., None], dt_t[:, :, None, :], A.astype(jnp.float32), Bm, Cm)
    return y.transpose(0, 2, 1, 3)
