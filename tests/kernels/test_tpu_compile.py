"""The main path's Pallas kernels compile for a TPU v5e at real widths,
and the moonlight decode step reads its held experts in place.

Interpret mode checks what a kernel computes; only the TPU compiler checks
that its block layout, tiling and VMEM use are legal.  These tests compile
each kernel through ``repro.kernels.ops`` for a *described* v5e chip (the
compiler is installed; no chip is attached) and look for the Mosaic kernel
(``tpu_custom_call``) in the compiled program.  Only the TPU compiler, too,
shows which slices of the layer scan's stacked weights it fuses into their
users and which it copies out first.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles,
since an entry written for a described chip cannot be read back without one.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.model import Model

MOONLIGHT = (Path(__file__).parents[2] / "benchmarks" / "chip" / "configs"
             / "mla_moe" / "moonlight-16b-a3b-ep8.json")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32

# internlm2-1.8b attention: 16 query / 8 KV heads of 128, S=2048
QKV = [((1, 2048, 16, 128), BF16), ((1, 2048, 8, 128), BF16),
       ((1, 2048, 8, 128), BF16)]


@pytest.mark.parametrize("window", [None, 512], ids=["causal", "window512"])
def test_flash_attention_compiles_for_v5e(one_chip, window):
    text = _compile_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            window=window),
        QKV, one_chip)
    assert "tpu_custom_call" in text


def test_gmm_compiles_for_v5e(one_chip):
    # mixtral-8x7b experts: d_model 4096, expert d_ff 14336, 512 slots each
    text = _compile_text(
        ops.gmm, [((8, 512, 4096), BF16), ((8, 4096, 14336), BF16)],
        one_chip)
    assert "tpu_custom_call" in text


def test_mamba_scan_compiles_for_v5e(one_chip):
    # zamba2-7b SSM: 112 heads of P=64, state N=64, chunk 128, S=2048
    text = _compile_text(
        lambda x, dt, a, b, c: ops.mamba_scan(x, dt, a, b, c, chunk=128),
        [((1, 2048, 112, 64), BF16), ((1, 2048, 112), F32), ((112,), F32),
         ((1, 2048, 64), BF16), ((1, 2048, 64), BF16)],
        one_chip)
    assert "tpu_custom_call" in text


def _unfused(text: str, shape: str) -> list[str]:
    """Instructions of type ``shape`` (``bf16[8,2048,1408]``) that stand
    alone in the program, outside every fused computation: each is an
    array written to memory."""
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif comp not in fused and re.search(
                r"= " + re.escape(shape) + r"\{", line):
            out.append(line.split("=")[0].strip())
    return out


def test_moonlight_decode_reads_the_held_experts_in_place(one_chip):
    """The cell's expert layer at full width, two of its layers and a
    small vocabulary: a decode step of 32 rows computes its 8 held
    experts with dots that read the scan's slice of the stacked weights,
    so no ragged kernel and no copy of a layer's experts stands alone;
    a prefill of 2 x 160 tokens keeps its ragged matmuls."""
    cfg = json.loads(MOONLIGHT.read_text())["model"]
    model = Model(ModelConfig(**dict(cfg, n_layers=3, vocab_size=2048)))
    params = jax.eval_shape(model.serving_params,
                            jax.eval_shape(model.init_fn,
                                           jax.random.PRNGKey(0)))

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def prefill(p, tokens):
        return model.prefill(p, {"tokens": tokens}, 192)

    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    cache = jax.eval_shape(prefill, params, jnp.zeros((32, 8), jnp.int32))[1]
    position = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    decode = jax.jit(model.decode_step).lower(
        placed(params), tokens, placed(cache), position).compile().as_text()
    assert "ragged" not in decode
    for shape in ("bf16[8,2048,1408]", "bf16[8,1408,2048]"):
        assert shape in decode                  # read, inside the dots
        assert _unfused(decode, shape) == []
    prompts = jax.ShapeDtypeStruct((2, 160), jnp.int32, sharding=one_chip)
    assert "ragged" in jax.jit(prefill).lower(
        placed(params), prompts).compile().as_text()
