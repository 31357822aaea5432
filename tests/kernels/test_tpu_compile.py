"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode checks what a kernel computes; only the TPU compiler checks
that its block layout, tiling and VMEM use are legal.  These tests compile
each kernel through ``repro.kernels.ops`` for a *described* v5e chip (the
compiler is installed; no chip is attached) and look for the Mosaic kernel
(``tpu_custom_call``) in the compiled program.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles,
since an entry written for a described chip cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


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
