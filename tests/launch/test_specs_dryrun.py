"""Dry-run machinery on a 1x1 mesh with smoke configs: specs build, steps
lower + compile, collective parsing and roofline math run end-to-end."""

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.configs.base import ShapeConfig, TrainConfig
from repro.launch import hlo as hlo_mod
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_mesh
from repro.launch.roofline import analyze, model_flops
from repro.models.model import Model
from repro.parallel.sharding import PARAM_RULES, use_rules
from repro.train.loop import make_train_step

TINY_TRAIN = ShapeConfig("train_4k", "train", seq_len=32, global_batch=4)
TINY_PREFILL = ShapeConfig("prefill_32k", "prefill", seq_len=32, global_batch=2)
TINY_DECODE = ShapeConfig("decode_32k", "decode", seq_len=32, global_batch=2)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mixtral-8x7b",
                                  "xlstm-1.3b", "zamba2-7b",
                                  "whisper-medium", "internvl2-2b",
                                  "moonlight-16b-a3b"])
def test_train_cell_lowers_and_compiles(arch):
    cfg = configs.get(arch, smoke=True)
    mesh = _mesh()
    model = Model(cfg)
    specs = {
        "state": specs_mod.state_specs(cfg, mesh),
        "batch": specs_mod.batch_specs(cfg, TINY_TRAIN, mesh),
    }
    step = make_train_step(model, TrainConfig())
    rules = specs_mod.act_rules_for(cfg, TINY_TRAIN, mesh)

    def fn(state, batch):
        with use_rules(PARAM_RULES, rules, mesh):
            return step(state, batch)

    with mesh:
        lowered = jax.jit(fn).lower(specs["state"], specs["batch"])
        compiled = lowered.compile()
    assert compiled.cost_analysis().get("flops", 0) > 0
    text = compiled.as_text()
    stats = hlo_mod.analyze_collectives(text)
    assert "_total" in stats


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "whisper-medium",
                                  "moonlight-16b-a3b"])
def test_decode_cell_lowers_and_compiles(arch):
    cfg = configs.get(arch, smoke=True)
    mesh = _mesh()
    model = Model(cfg)
    specs = specs_mod.decode_specs(cfg, TINY_DECODE, mesh)
    rules = specs_mod.act_rules_for(cfg, TINY_DECODE, mesh)

    def fn(params, tokens, cache, position):
        with use_rules(PARAM_RULES, rules, mesh):
            return model.decode_step(params, tokens, cache, position)

    with mesh:
        compiled = jax.jit(fn).lower(
            specs["params"], specs["tokens_new"], specs["cache"],
            specs["position"],
        ).compile()
    assert compiled.cost_analysis() is not None


def test_prefill_cell_lowers(arch="internlm2-1.8b"):
    cfg = configs.get(arch, smoke=True)
    mesh = _mesh()
    model = Model(cfg)
    rules = specs_mod.act_rules_for(cfg, TINY_PREFILL, mesh)

    def fn(params, batch):
        with use_rules(PARAM_RULES, rules, mesh):
            return model.prefill(params, batch, TINY_PREFILL.seq_len)

    with mesh:
        compiled = jax.jit(fn).lower(
            specs_mod.param_specs(cfg, mesh, dtype=jnp.bfloat16),
            specs_mod.batch_specs(cfg, TINY_PREFILL, mesh),
        ).compile()
    assert compiled.cost_analysis() is not None


def test_hlo_collective_parser():
    text = """
  %p = f32[128,64]{1,0} parameter(0)
  %ag = f32[256,64]{1,0} all-gather(%p), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[128,64]{1,0} all-reduce(%p), to_apply=%add
  %rs.1 = f32[64,64]{1,0} reduce-scatter(f32[128,64]{1,0} %ar), dimensions={0}
"""
    stats = hlo_mod.analyze_collectives(text)
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["result_bytes"] == 256 * 64 * 4
    assert stats["all-gather"]["operand_bytes"] == 128 * 64 * 4
    assert stats["all-reduce"]["operand_bytes"] == 128 * 64 * 4
    assert stats["reduce-scatter"]["operand_bytes"] == 128 * 64 * 4
    # wire estimate: ar 2x operand + ag result + rs operand
    expected = 2 * 128 * 64 * 4 + 256 * 64 * 4 + 128 * 64 * 4
    assert stats["_total"]["wire_bytes_per_device"] == expected


def test_roofline_analyze_math():
    record = {
        "arch": "x", "shape": "train_4k", "mesh": "single", "chips": 256,
        "device_kind": "TPU v5 lite",
        "kind": "train", "seq_len": 4096, "global_batch": 256,
        "params_total": 2_000_000_000, "params_active": 1_000_000_000,
        "status": "ok",
        "cost": {"flops": 197e12, "bytes accessed": 819e9},
        "collectives": {"_total": {"wire_bytes_per_device": 50e9}},
        "memory": {},
    }
    row = analyze(record)
    assert row["compute_s"] == pytest.approx(1.0)
    assert row["memory_s"] == pytest.approx(1.0)
    assert row["collective_s"] == pytest.approx(1.0)
    # MODEL_FLOPS uses ACTIVE params (MoE correction)
    assert row["model_flops"] == 6.0 * 1e9 * 256 * 4096
    assert 0 < row["roofline_fraction"] <= 1.0


def test_roofline_refuses_unknown_device_kind():
    record = {"arch": "x", "chips": 1, "kind": "decode", "global_batch": 1,
              "params_active": 1, "device_kind": "cpu"}
    with pytest.raises(KeyError, match="no published peaks"):
        analyze(record)


def test_model_flops_kinds():
    base = {"params_active": 1e9, "global_batch": 8, "seq_len": 100}
    assert model_flops({**base, "kind": "train"}) == 6e9 * 800
    assert model_flops({**base, "kind": "prefill"}) == 2e9 * 800
    assert model_flops({**base, "kind": "decode"}) == 2e9 * 8


def test_long_500k_rules_shard_kv_seq():
    import numpy as np
    from types import SimpleNamespace

    cfg = configs.get("zamba2-7b", smoke=True)
    # production-mesh stand-in (the test process has one real device)
    mesh = SimpleNamespace(axis_names=("data", "model"),
                           devices=np.empty((16, 16)))
    long_shape = ShapeConfig("long_500k", "decode", 1024, 1)
    rules = specs_mod.act_rules_for(cfg, long_shape, mesh)
    # batch=1 < 16 data shards -> KV/sequence parallelism kicks in
    assert rules.rules["kv_seq"] == ("pod", "data")
    big_train = ShapeConfig("train_4k", "train", 4096, 256)
    train_rules = specs_mod.act_rules_for(cfg, big_train, mesh)
    assert train_rules.rules["kv_seq"] is None
