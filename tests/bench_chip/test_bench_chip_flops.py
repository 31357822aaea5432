"""The chip benchmark's analytic counts, against the program's parameter
count and a brute-force causal attention count."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip import flops

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / "configs"


def model(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name", sorted(p.stem for p in CONFIGS.glob("*.json")))
def test_parameter_counts_match_the_program(name):
    from repro.configs.base import ModelConfig
    from repro.models.model import count_params_analytic

    cfg = model(name)
    assert flops.total_params(cfg) == count_params_analytic(ModelConfig(**cfg))
    # the embedding table is gathered, not multiplied
    assert flops.matmul_params(cfg) == (flops.total_params(cfg)
                                        - cfg["vocab_size"] * cfg["d_model"]
                                        - (2 * cfg["n_layers"] + 1)
                                        * cfg["d_model"])


def brute_attention(cfg, first, count):
    per_key = 4 * cfg["n_layers"] * cfg["n_heads"] * flops.head_dim(cfg)
    return sum(per_key * (first + i + 1) for i in range(count))


@pytest.mark.parametrize("first,count", [(0, 1), (0, 128), (300, 1), (17, 40)])
def test_attention_is_counted_causal(first, count):
    cfg = model("internlm2-1.8b")
    assert flops.attention_flops(cfg, first, count) == brute_attention(
        cfg, first, count)


def test_serving_and_training_counts():
    cfg = model("internlm2-1.8b")
    n = flops.matmul_params(cfg)
    assert n == 1_699_479_552
    # the prefill projects only the last position to the vocabulary
    body = 2 * (n - flops.head_params(cfg)) * 128
    assert flops.prefill_flops(cfg, 8, 128) == 8 * (
        body + 2 * flops.head_params(cfg) + brute_attention(cfg, 0, 128))
    assert flops.decode_flops(cfg, 8, 200) == 8 * (
        2 * n + brute_attention(cfg, 200, 1))
    assert flops.generate_flops(cfg, 8, 128, 3) == (
        flops.prefill_flops(cfg, 8, 128) + flops.decode_flops(cfg, 8, 128)
        + flops.decode_flops(cfg, 8, 129))
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(
        6 * n + 3 * brute_attention(cfg, 0, 1024) / 1024)
    kv = 2 * 24 * 8 * 101 * 8 * 128 * 2
    assert flops.decode_least_bytes(cfg, 8, 100) == 2 * n + kv
