"""The latent-attention MoE stack (moonlight, the DeepSeek-V3 block) against
its plain reference (``benchmarks/chip/references/mla_moe_lm.py``), at a
small size in float32 on the CPU, on the weights the benchmark makes.

Latent attention: the program's forward, its prefill then decode through
the latent cache, and its absorbed decode against the expanded path.  The
expert-parallel layer: the shares of the experts add up to the uncut
layer and no assignment is dropped when every token picks one expert, on
the dense path (a few tokens) and the ragged one, which agree; and the
correction bias moves the choice but never the weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.references import mla_moe_lm as ref
from repro import configs
from repro.configs.base import ModelConfig
from repro.models import mla, moe
from repro.models.model import (Model, active_params_analytic,
                                count_params_analytic, param_shapes)
from repro.serve.engine import ServeEngine

SEED = 2**33 + 17
TOL = dict(rtol=2e-4, atol=2e-4)

#: one chip's share (4 of 16 experts, from expert 4) of a tiny model with
#: every kind of layer: a leading dense layer, then two expert layers
TINY = dict(
    arch="moonlight-tiny", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab_size=128, rope_theta=50_000.0,
    norm_type="rmsnorm", norm_eps=1e-5, mlp_type="swiglu", n_dense_layers=1,
    mla=dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16),
    moe=dict(n_experts=4, top_k=3, d_ff=32, router_experts=16, first_expert=4,
             shared_d_ff=48, score_func="sigmoid", norm_topk=True,
             routed_scale=2.446),
    param_dtype="float32", compute_dtype="float32",
)


def _tokens(b, s, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (b, s), dtype=np.int32))


@pytest.fixture(autouse=True)
def small_query_blocks(monkeypatch):
    """Blocks of 8 queries, so that the tests' prompts take several."""
    monkeypatch.setattr(mla, "QUERY_BLOCK", 8)


@pytest.fixture(scope="module")
def case():
    model = Model(ModelConfig(**TINY))
    return model, ref.init_params(TINY, SEED)


def test_reference_weights_have_the_programs_layout(case):
    model, params = case
    want = jax.tree_util.tree_map(tuple, param_shapes(model.cfg),
                                  is_leaf=lambda x: isinstance(x, tuple))
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == want


def test_forward_matches_the_reference_in_float32(case):
    model, params = case
    tokens = _tokens(2, 24)
    with jax.default_matmul_precision("highest"):
        got, aux = model.forward(params, {"tokens": tokens})
    want = ref.logits(TINY, params, tokens, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    assert float(aux) == 0.0


def test_prefill_then_latent_decode_matches_the_reference(case):
    model, params = case
    b, s, k = 2, 20, 9
    tokens = _tokens(b, s, seed=1)
    want = np.asarray(ref.logits(TINY, params, tokens, 0))
    with jax.default_matmul_precision("highest"):
        logits, cache = model.prefill(params, {"tokens": tokens[:, :k]}, s)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, k - 1],
                                   **TOL)
        assert set(cache) == {"dense_blocks", "blocks", "moe_counts"}
        assert cache["blocks"]["ckv"].shape == (2, b, s, 32)
        assert cache["dense_blocks"]["kpe"].shape == (1, b, s, 8)
        for t in range(k, s):
            logits, cache = model.decode_step(
                params, tokens[:, t:t + 1], cache, jnp.asarray(t, jnp.int32))
            np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t],
                                       **TOL)


def test_absorbed_decode_matches_the_expanded_path_on_the_same_cache(case):
    model, params = case
    cfg = model.cfg
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["attn"])
    b, s = 2, 13
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        expanded, cache = mla.apply_mla(p, cfg, x, jnp.arange(s), max_len=s)
        # the last position again, absorbed, over the cache the expanded
        # path wrote (which it rewrites with the same latent)
        last = jnp.full((b, 1), s - 1, jnp.int32)
        absorbed, again = mla.apply_mla(p, cfg, x[:, -1:], last, cache=cache,
                                        position=s - 1)
    np.testing.assert_allclose(np.asarray(absorbed[:, 0]),
                               np.asarray(expanded[:, -1]), **TOL)
    np.testing.assert_allclose(np.asarray(again["ckv"]),
                               np.asarray(cache["ckv"]), rtol=1e-6, atol=1e-6)


def test_rope_pairs_rotates_consecutive_pairs_as_published():
    """DeepSeek-V3's interleaved RoPE: de-interleave the pairs, rotate the
    halves, and the dot products of queries and keys are those of
    rotating each pair (2i, 2i+1) in place."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    pos = jnp.arange(5)
    got = mla.rope_pairs(x, pos, 50_000.0)
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    freqs = 1.0 / 50_000.0 ** (jnp.arange(0, 8, 2) / 8)
    ang = jnp.concatenate([pos[:, None] * freqs] * 2, -1)[:, None, :]
    rotate_half = jnp.concatenate([-halves[..., 4:], halves[..., :4]], -1)
    published = halves * jnp.cos(ang) + rotate_half * jnp.sin(ang)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([got[..., 0::2], got[..., 1::2]], -1)),
        np.asarray(published), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.rope_pairs(x, 50_000.0)),
                               np.asarray(got), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ expert layer

def _layer_cfg(**moe_kw):
    moe_cfg = dict(TINY["moe"], **moe_kw)
    return ModelConfig(**dict(TINY, moe=moe_cfg)), dict(TINY, moe=moe_cfg)


def _moe_params(cfg: dict, seed: int):
    """One expert layer's weights, from the reference's tree."""
    full = ref.init_params(cfg, seed)
    return jax.tree_util.tree_map(lambda a: a[0], full["blocks"]["moe"])


#: a call of 14 or 64 tokens computes its held experts densely, one of 320
#: (over ``moe.DENSE_TOKENS``) with ragged matmuls
RAGGED = (4, 80, 0)


@pytest.mark.parametrize("b,s,dense", [(2, 7, 1), RAGGED],
                         ids=["dense", "ragged"])
def test_expert_shares_add_up_to_the_uncut_layer(b, s, dense):
    """Eight chips each hold 8 of 64 experts: the parts the eight shares
    give, with the shared experts (which every chip computes) counted
    once, add up to what the uncut reference gives for the whole layer."""
    _, uncut_dict = _layer_cfg(n_experts=64, router_experts=64, top_k=6,
                               first_expert=0)
    uncut = _moe_params(uncut_dict, SEED)
    x = jax.random.normal(jax.random.PRNGKey(5), (b, s, TINY["d_model"]))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(uncut_dict, uncut, x, ref.exact)
        total, counts = 0.0, 0
        for chip in range(8):
            cfg, _ = _layer_cfg(n_experts=8, router_experts=64, top_k=6,
                                first_expert=8 * chip)
            held = slice(8 * chip, 8 * chip + 8)
            share = dict(uncut, experts=jax.tree_util.tree_map(
                lambda a: a[held], uncut["experts"]))
            out, c = moe.apply_moe_ep(share, cfg, x)
            total = total + out
            counts += int(c[0])
            assert int(c[2]) == dense
        shared = ref.swiglu(uncut["shared"], x, ref.exact)
    np.testing.assert_allclose(np.asarray(total - 7 * shared),
                               np.asarray(want), **TOL)
    assert counts == b * s * 6      # every (token, expert) pair, once


@pytest.mark.parametrize("b,s,dense", [(4, 16, 1), RAGGED],
                         ids=["dense", "ragged"])
def test_no_assignment_is_dropped_when_every_token_picks_one_expert(b, s,
                                                                    dense):
    cfg, cfg_dict = _layer_cfg()
    params = _moe_params(cfg_dict, SEED + 1)
    # a bias that makes held expert 5 (router index) every token's choice
    params["router"]["bias"] = params["router"]["bias"].at[5].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (b, s, TINY["d_model"]))
    with jax.default_matmul_precision("highest"):
        idx, _ = moe.route(params, cfg, x.reshape(-1, TINY["d_model"]))
        assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
        got, counts = moe.apply_moe_ep(params, cfg, x)
        want = ref.experts(cfg_dict, params, x, ref.exact)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    held = (idx >= 4) & (idx < 8)
    assert int(counts[0]) == int(jnp.sum(held))
    assert int(counts[1]) == len(np.unique(np.asarray(idx)[np.asarray(held)]))
    assert int(counts[2]) == dense


def test_both_paths_give_the_same_experts_output_and_sizes():
    """The dense and the ragged path on one set of tokens, some routed to
    no held expert: the same output, and the same pairs per held expert."""
    cfg, cfg_dict = _layer_cfg()
    params = _moe_params(cfg_dict, SEED + 3)
    x = jax.random.normal(jax.random.PRNGKey(8), (48, TINY["d_model"]))
    with jax.default_matmul_precision("highest"):
        idx, weights = moe.route(params, cfg, x)
        dense, dense_sizes = moe._held_experts(params, cfg, x, idx, weights,
                                               dense=True)
        ragged, ragged_sizes = moe._held_experts(params, cfg, x, idx,
                                                 weights, dense=False)
    held = np.asarray((idx >= 4) & (idx < 8))
    assert 0 < held.any(axis=1).sum() < len(held)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ragged), **TOL)
    np.testing.assert_array_equal(np.asarray(dense_sizes),
                                  np.asarray(ragged_sizes))
    np.testing.assert_array_equal(
        np.asarray(dense_sizes),
        [(np.asarray(idx) == 4 + j).sum() for j in range(4)])


def test_the_correction_bias_moves_the_choice_not_the_weights():
    cfg, cfg_dict = _layer_cfg()
    params = _moe_params(cfg_dict, SEED + 2)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, TINY["d_model"]))
    unbiased = dict(params, router=dict(params["router"],
                    bias=jnp.zeros_like(params["router"]["bias"])))
    idx, w = moe.route(params, cfg, x)
    idx0, _ = moe.route(unbiased, cfg, x)
    assert bool(jnp.any(jnp.sort(idx, -1) != jnp.sort(idx0, -1)))
    scores = jax.nn.sigmoid(x @ params["router"]["w"])
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)
                                  * 2.446), rtol=1e-5)


def test_the_engine_pulls_the_expert_counter_once_per_call(case):
    model, params = case
    engine = ServeEngine(model, params, max_len=16)
    out = engine.generate(np.asarray(_tokens(2, 8)), max_new_tokens=5)
    stats = engine.stats
    assert out["tokens"].shape == (2, 5)
    assert stats["moe_decode_steps"] == 4
    # two expert layers, two rows, three experts each: at most 12 pairs on
    # the four held experts a step, and at most 8 held experts hit
    assert 0 < stats["moe_routed_pairs"] <= 4 * 12
    assert 0 < stats["moe_experts_hit"] <= min(4 * 8,
                                               stats["moe_routed_pairs"])
    # a decode step's two rows take the dense path in both expert layers
    assert stats["moe_dense_calls"] == 2 * 4


def test_the_registry_config_counts_the_published_model():
    cfg = configs.get("moonlight-16b-a3b")
    assert count_params_analytic(cfg) == pytest.approx(15.96e9, rel=2e-3)
    assert active_params_analytic(cfg) == pytest.approx(2.91e9, rel=5e-3)
    smoke = Model(configs.get("moonlight-16b-a3b", smoke=True))
    params = jax.jit(smoke.init_fn)(jax.random.PRNGKey(0))
    logits, cache = smoke.prefill(params, {"tokens": _tokens(1, 6)}, 8)
    assert logits.shape == (1, 1, smoke.cfg.vocab_size)
    assert int(cache["moe_counts"].sum()) == 0
