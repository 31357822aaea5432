"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps + properties.

Kernels run in interpret mode on CPU, asked for explicitly: ``interpret=True``
on a kernel, or ``pltpu.force_tpu_interpret_mode()`` around a model path that
reaches the kernels through ``repro.kernels.ops`` (which never interprets on
its own).  tests/kernels/test_tpu_compile.py compiles them for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.testing import hypothesis_shim

# real hypothesis when installed; deterministic seeded sweep otherwise
given, settings, st = hypothesis_shim()
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import gmm

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,K,D,causal,window,softcap",
    [
        (2, 128, 4, 2, 64, True, None, None),
        (1, 256, 4, 4, 64, True, None, None),     # MHA
        (2, 128, 4, 1, 32, True, None, None),     # MQA
        (2, 128, 4, 2, 64, False, None, None),    # bidirectional
        (1, 256, 2, 2, 32, True, 64, None),       # sliding window
        (1, 128, 2, 2, 64, True, None, 30.0),     # logit softcap
        (1, 64, 8, 2, 128, True, None, None),     # head_dim 128
    ],
)
def test_flash_attention_matches_ref(B, S, H, K, D, causal, window, softcap,
                                     dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, D)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          logit_softcap=softcap, block_q=64, block_k=64,
                          interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal, window=window,
                                 logit_softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        **TOL[dtype],
    )


def test_flash_attention_block_shape_independence():
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    outs = [
        flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        for bq, bk in [(64, 64), (128, 128), (256, 64), (64, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(
    s_blocks=st.integers(1, 4),
    heads=st.sampled_from([(2, 1), (4, 2), (4, 4)]),
    d=st.sampled_from([32, 64]),
    causal=st.booleans(),
)
def test_flash_attention_property(s_blocks, heads, d, causal):
    H, K = heads
    S = 64 * s_blocks
    ks = jax.random.split(jax.random.PRNGKey(s_blocks * 7 + d), 3)
    q = jax.random.normal(ks[0], (1, S, H, d))
    k = jax.random.normal(ks[1], (1, S, K, d))
    v = jax.random.normal(ks[2], (1, S, K, d))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, expected, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk",
    [
        (2, 64, 2, 8, 16, 16),
        (1, 128, 4, 16, 8, 32),
        (2, 96, 1, 8, 8, 32),
        (1, 64, 2, 64, 64, 64),   # realistic head/state dims
    ],
)
def test_mamba_scan_matches_ref(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xh = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (B, S, N)).astype(dtype)
    out = mamba_scan(xh, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    expected, _ = ref.mamba_scan_ref(xh, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        **TOL[dtype],
    )


def test_mamba_scan_chunk_independence():
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    B, S, H, P, N = 1, 128, 2, 8, 8
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    outs = [mamba_scan(xh, dt, A, Bm, Cm, chunk=c, interpret=True)
            for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "E,C,D,F,blocks",
    [
        (4, 64, 32, 48, (32, 16, 16)),
        (2, 128, 64, 64, (64, 64, 64)),
        (8, 16, 128, 32, (16, 32, 64)),
    ],
)
def test_gmm_matches_ref(E, C, D, F, blocks, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (E, C, D)).astype(dtype)
    w = jax.random.normal(ks[1], (E, D, F)).astype(dtype)
    bc, bf, bd = blocks
    out = gmm(x, w, block_c=bc, block_f=bf, block_d=bd, interpret=True)
    expected = ref.gmm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        **TOL[dtype],
    )


def test_moe_expert_mlp_matches_ref():
    from repro import configs

    cfg = configs.get("mixtral-8x7b", smoke=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    G, E, C, D, F = 2, cfg.moe.n_experts, 16, cfg.d_model, cfg.moe.d_ff
    x = jax.random.normal(ks[0], (G, E, C, D))
    experts = {
        "gate": jax.random.normal(ks[1], (E, D, F)) * 0.1,
        "up": jax.random.normal(ks[2], (E, D, F)) * 0.1,
        "down": jax.random.normal(ks[3], (E, F, D)) * 0.1,
    }
    with pltpu.force_tpu_interpret_mode():
        out = ops.moe_expert_mlp(x, experts, cfg)
    expected = ref.expert_mlp_ref(x, experts)
    np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-4)


def test_moe_layer_with_gmm_matches_einsum_path():
    """apply_moe(use_gmm=True) == apply_moe(use_gmm=False)."""
    from repro import configs
    from repro.models import moe as moe_mod
    from repro.models.layers import materialize

    cfg = configs.get("mixtral-8x7b", smoke=True)
    spec = moe_mod.init_moe(cfg)
    params, _ = materialize(jax.random.PRNGKey(0), spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    out1, aux1 = moe_mod.apply_moe(params, cfg, x, use_gmm=False)
    with pltpu.force_tpu_interpret_mode():
        out2, aux2 = moe_mod.apply_moe(params, cfg, x, use_gmm=True)
    np.testing.assert_allclose(out1, out2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(aux1, aux2, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# ops: no hidden interpret fallback, TPU-legal blocks
# ---------------------------------------------------------------------------

def test_ops_never_interpret_on_their_own():
    """Off the TPU a kernel call fails unless interpretation was asked for."""
    x, w = jnp.ones((2, 16, 32)), jnp.ones((2, 32, 16))
    with pytest.raises(ValueError, match="interpret"):
        ops.gmm(x, w)
    with pltpu.force_tpu_interpret_mode():
        np.testing.assert_allclose(ops.gmm(x, w), ref.gmm_ref(x, w))


@pytest.mark.parametrize("n,preferred,expected", [
    (2048, 128, 128),   # tiles at the preferred block
    (100, 128, 100),    # shorter than a block: one whole-sequence block
    (200, 128, 8),      # halves down to one vreg of sublanes
    (384, 256, 128),
])
def test_seq_block_is_tpu_legal(n, preferred, expected):
    b = ops._seq_block(n, preferred)
    assert b == expected and n % b == 0 and (b == n or b % 8 == 0)


def test_seq_block_refuses_untileable_length():
    with pytest.raises(ValueError, match="pad it"):
        ops._seq_block(1030, 128)   # 1030 = 2 * 515: no block of 8..128 rows
