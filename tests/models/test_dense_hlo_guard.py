"""The dense path lowers as it did before latent attention, leading dense
layers and the expert-parallel MoE layer came in beside it.

internlm2's and phi3's prefill, decode step and train step, at a tiny
size, lower to StableHLO whose SHA-256 is recorded in
``dense_hlo_fingerprints.json``: the fingerprints of the tree before those
changes (commit 877fb56).  Record them again only on purpose, for a change
that is meant to alter the dense programs:

    PYTHONPATH=src python tests/models/test_dense_hlo_guard.py --record
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.configs.base import TrainConfig
from repro.models.model import Model
from repro.train.loop import init_state, make_train_step

RECORD = Path(__file__).with_name("dense_hlo_fingerprints.json")
ARCHS = ("internlm2-1.8b", "phi3-mini-3.8b")
PROGRAMS = ("prefill", "decode", "train_step")
B, S, MAX_LEN = 2, 8, 12


def _lowered(arch: str, program: str):
    model = Model(configs.get(arch, smoke=True))
    cfg = model.cfg
    if program == "train_step":
        state = jax.eval_shape(
            lambda k: init_state(model, k)[0], jax.random.PRNGKey(0))
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        return jax.jit(make_train_step(model, TrainConfig())).lower(
            state, batch)
    params = jax.eval_shape(model.init_fn, jax.random.PRNGKey(0))
    if program == "prefill":
        def prefill(params, batch):
            return model.prefill(params, batch, MAX_LEN)

        return jax.jit(prefill).lower(
            params, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)})
    cache = jax.eval_shape(lambda: model.init_cache(B, MAX_LEN))
    return jax.jit(model.decode_step).lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache,
        jax.ShapeDtypeStruct((), jnp.int32))


def fingerprint(arch: str, program: str) -> str:
    text = _lowered(arch, program).as_text()
    # source locations would tie the fingerprint to line numbers
    assert "loc(" not in text
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("arch", ARCHS)
def test_dense_program_lowers_as_before(arch, program):
    want = json.loads(RECORD.read_text())["sha256"][arch][program]
    assert fingerprint(arch, program) == want


if __name__ == "__main__" and "--record" in sys.argv:
    RECORD.write_text(json.dumps({
        "about": "SHA-256 of the StableHLO text of each program at the "
                 "tiny SMOKE sizes (batch 2, 8 prompt tokens, cache 12), "
                 f"jax {jax.__version__}",
        "sha256": {a: {p: fingerprint(a, p) for p in PROGRAMS}
                   for a in ARCHS},
    }, indent=1) + "\n")
