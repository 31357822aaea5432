"""The serving weight view (``Model.serving_params``): the weights cast to
the compute dtype once, where every program casts them at each use.

Served tokens and logits from the view are bitwise those of the programs
run on the float32 tree, for every family the engine serves; the view's
programs hold no cast of a weight; the engine builds one view per weight
assignment and keeps ``params`` as assigned.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.model import Model
from repro.serve.engine import ServeEngine

B, S, NEW = 2, 12, 4
BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)

#: one arch per family the engine serves, with leaves (dict keys from the
#: root) and their dtype in the view: bf16 where ``layers.cast_at_use``
#: holds, else the float32 they were assigned in
FAMILIES = {
    "internlm2-1.8b": {  # dense, GQA
        ("embed", "table"): BF16, ("lm_head", "w"): BF16,
        ("blocks", "attn", "wq", "w"): BF16,
        ("blocks", "mlp", "down", "w"): BF16,
        ("blocks", "attn_norm", "scale"): F32, ("final_norm", "scale"): F32,
    },
    "mixtral-8x7b": {  # MoE
        ("blocks", "moe", "experts", "gate"): BF16,
        ("blocks", "moe", "experts", "down"): BF16,
        ("blocks", "moe", "router", "w"): F32,
        ("blocks", "mlp_norm", "scale"): F32,
    },
    "zamba2-7b": {  # hybrid: Mamba2 + shared attention
        ("groups", "mamba", "in_proj", "w"): BF16,
        ("shared", "mlp", "up", "w"): BF16,
        ("groups", "mamba", "A_log"): F32, ("groups", "mamba", "dt_bias"): F32,
        ("groups", "norm", "scale"): F32,
    },
    "xlstm-1.3b": {  # SSM
        ("super", "mlstm", "up", "w"): BF16,
        ("super", "slstm", "ffn", "up", "w"): BF16,
        ("super", "slstm", "r_z"): F32, ("super", "mlstm", "norm", "scale"): F32,
    },
    "whisper-medium": {  # encoder-decoder
        ("decoder", "cross_attn", "wq", "w"): BF16,
        ("encoder", "mlp", "up", "b"): BF16,
        ("encoder", "attn_norm", "bias"): F32, ("pos_embed",): F32,
    },
    "internvl2-2b": {  # VLM
        ("embed", "table"): BF16, ("blocks", "attn", "wo", "w"): BF16,
        ("blocks", "attn_norm", "scale"): F32,
    },
}


def _engine_case(arch):
    cfg = configs.get(arch, smoke=True).replace(compute_dtype="bfloat16")
    model = Model(cfg)
    params = jax.jit(model.init_fn)(jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    prompts = np.asarray(jax.random.randint(keys[0], (B, S), 0, cfg.vocab_size))
    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = np.asarray(
            jax.random.normal(keys[1], (B, 16, cfg.d_model), jnp.float32))
    if cfg.family == "vlm":
        extra["pixel_embeds"] = np.asarray(jax.random.normal(
            keys[1], (B, cfg.n_image_tokens, cfg.d_model), jnp.float32))
    return model, params, prompts, extra


def _keys(path):
    return tuple(k.key for k in path)


@pytest.mark.parametrize("arch", list(FAMILIES))
def test_view_serves_bitwise_what_the_float32_tree_serves(arch):
    model, params, prompts, extra = _engine_case(arch)
    max_len = S + NEW
    engine = ServeEngine(model, params, max_len=max_len)
    out = engine.generate(prompts, max_new_tokens=NEW, **extra)

    # the programs as they ran before the view: jitted, on the f32 tree
    batch = {"tokens": jnp.asarray(prompts, jnp.int32),
             **{k: jnp.asarray(v) for k, v in extra.items()}}
    logits, cache = jax.jit(model.prefill, static_argnums=2)(
        params, batch, max_len)
    kept, tokens = [logits], []
    decode = jax.jit(model.decode_step)
    for step in range(NEW):
        tokens.append(np.asarray(jnp.argmax(logits[:, -1], -1)))
        if step < NEW - 1:
            logits, cache = decode(params, jnp.asarray(tokens[-1][:, None]),
                                   cache, jnp.asarray(S + step, jnp.int32))
            if step == 0:
                kept.append(logits)

    np.testing.assert_array_equal(out["tokens"], np.stack(tokens, axis=1))
    assert len(out["logits"]) == len(kept) == 2
    for got, want in zip(out["logits"], kept):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                      np.asarray(want.astype(jnp.float32)))

    view = {_keys(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(engine.serving_params)[0]}
    f32 = {_keys(p): v for p, v in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    assert view.keys() == f32.keys()
    for path, dtype in FAMILIES[arch].items():
        assert view[path].dtype == dtype, path
    for path, leaf in view.items():
        assert leaf.shape == f32[path].shape
        if leaf.dtype == F32:  # left as it is: the very array assigned
            assert leaf is f32[path], path
        else:
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(f32[path].astype(leaf.dtype)))


#: ``stablehlo.convert`` from f32 to bf16 of one operand, with its shape
CONVERT = re.compile(r"stablehlo\.convert\S* %\S+ : \(tensor<((?:\d+x)*)f32>\)"
                     r" -> tensor<\1bf16>")


def _weight_casts(lowered_text: str, weight_shapes: set) -> list:
    shapes = [tuple(int(d) for d in m.group(1).split("x") if d)
              for m in CONVERT.finditer(lowered_text)]
    return [s for s in shapes if s in weight_shapes]


def test_view_programs_hold_no_cast_of_a_weight():
    model, params, prompts, _ = _engine_case("internlm2-1.8b")
    view = model.serving_params(params)
    # a stacked leaf [L, ...] is cast per layer inside the scan too
    weight_shapes = set()
    for p, v in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(view)):
        if v.dtype == BF16:
            weight_shapes |= {p.shape, p.shape[1:]}
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    max_len = S + NEW

    def prefill(params, batch):
        return model.prefill(params, batch, max_len)

    jit_prefill, jit_decode = jax.jit(prefill), jax.jit(model.decode_step)
    _, cache = jit_prefill(view, batch)
    token = jnp.zeros((B, 1), jnp.int32)
    position = jnp.asarray(S, jnp.int32)

    def casts(weights):
        return [_weight_casts(jit_prefill.lower(weights, batch).as_text(),
                              weight_shapes),
                _weight_casts(jit_decode.lower(weights, token, cache,
                                               position).as_text(),
                              weight_shapes)]

    assert all(casts(params))  # on the f32 tree each program casts weights
    assert casts(view) == [[], []]


def test_one_view_per_weight_assignment_and_params_kept_as_assigned():
    model, params, prompts, _ = _engine_case("internlm2-1.8b")
    engine = ServeEngine(model, params, max_len=S + NEW)
    cast = [v for v in jax.tree_util.tree_leaves(engine.serving_params)
            if v.dtype == BF16]
    assert engine.stats["weight_views"] == 1
    assert engine.stats["weight_view_bytes"] == sum(v.nbytes for v in cast) > 0
    first = engine.generate(prompts, max_new_tokens=NEW)["tokens"]
    for _ in range(2):
        engine.generate(prompts, max_new_tokens=NEW)
    assert engine.stats["weight_views"] == 1
    # params stay the f32 tree the caller gave, which callers free or read
    assert engine.params is params
    assert all(leaf.dtype == F32 for leaf in jax.tree_util.tree_leaves(
        engine.params))

    other = jax.jit(model.init_fn)(jax.random.PRNGKey(7))
    engine.params = other
    assert engine.stats["weight_views"] == 2
    assert engine.params is other
    np.testing.assert_array_equal(
        np.asarray(engine.serving_params["embed"]["table"]),
        np.asarray(other["embed"]["table"].astype(BF16)))
    second = engine.generate(prompts, max_new_tokens=NEW)["tokens"]
    assert not np.array_equal(first, second)
    assert engine.stats["weight_views"] == 2
