"""The latent-attention MoE serving cell (``moonlight-serve-decode``) at a
size a CPU holds, and the counts of its per-layer metrics.

The harness drives FlowsService -> ComputeProvider -> ServeEngine over the
latent-attention stack with one chip's share of the experts, and the
comparison with ``references/mla_moe_lm.py`` decides ``correct``: a sound
run is correct, an altered served token is not, and the fp8 control reads
above the cell's limit."""

from __future__ import annotations

import copy
import time

import jax
import pytest

from benchmarks.chip import faults, flops_mla_moe, harness, serving

SEED = 2**33 + 303
#: the cell's model at a CPU's size, in the cell's own dtypes: one leading
#: dense layer and two expert layers holding 4 of 16 experts, from expert 4
TINY = dict(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=512,
    mla=dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16),
    moe=dict(n_experts=4, top_k=6, d_ff=32, router_experts=16,
             first_expert=4, shared_d_ff=64, score_func="sigmoid",
             norm_topk=True, routed_scale=2.446),
)


def tiny_cell():
    """The committed cell with its model and prompts cut to a CPU's size;
    its kind, limits and metrics are the cell's own."""
    cell = harness.find_cell("moonlight-serve-decode")
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY)
    cell.traffic = dict(cell.traffic, batch=8, max_new_tokens=16, max_len=80,
                        prompt_buckets={"16": 0.5, "32": 0.3, "64": 0.2})
    return cell


@pytest.fixture(scope="module")
def decode_run():
    cell = tiny_cell()
    return cell, harness.run_cell(cell, SEED, 1.0, False, time.time())


def test_a_sound_run_is_correct_and_counts_the_held_experts(decode_run):
    cell, run = decode_run
    assert run.correct, run.checks
    assert run.failed == 0 and run.attempted >= 4
    gap, limit = run.checks["logit_gap"]
    assert 0 <= gap <= limit
    line = harness.result_line(cell, run, False, jax.devices())
    assert set(line["metrics"]) == {"setup_s", "output_tokens_per_s"}
    stats = run.data["engine_stats"]
    assert stats["moe_decode_steps"] == 15 * stats["batches"]
    share = flops_mla_moe.hit_share(stats, cell.model)
    assert 0 < share <= 1


def test_the_control_reads_far_above_the_program(decode_run):
    """The fp8 control, read the same way at the same positions, reads
    above the cell's own limit and far above the program."""
    cell, run = decode_run
    gap, limit = run.checks["logit_gap"]
    control = serving.control_gap(cell, SEED, run)
    assert control > limit
    assert control > 3 * max(gap, 1e-3)


def test_an_altered_token_makes_the_run_incorrect(monkeypatch):
    faults.plant("altered_token", monkeypatch)
    run = harness.run_cell(tiny_cell(), SEED, 1.0, False, time.time())
    assert run.failed == 0
    assert not run.correct
    gap, limit = run.checks["logit_gap"]
    assert gap > limit


# ------------------------------------------------------------------ counts

def _model():
    return harness.find_cell("moonlight-serve-decode").model


def test_the_parameter_count_matches_the_program():
    """The dense counts (``flops.py``, checked against every configuration
    directly in ``configs/``) would misread this one; its own module's
    total is the program's."""
    from repro.configs.base import ModelConfig
    from repro.models.model import count_params_analytic

    cfg = _model()
    assert flops_mla_moe.total_params(cfg) == count_params_analytic(
        ModelConfig(**cfg)) == 3_364_615_296


def test_the_counts_agree_with_a_hand_count():
    cfg = _model()
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    expert = 3 * 2048 * 1408
    fixed = 2048 * 64 + 3 * 2048 * 2816          # router, shared experts
    dense, head = 3 * 2048 * 11264, 2048 * 163840
    assert flops_mla_moe.mla_params(cfg) == mla == 13_762_560
    assert flops_mla_moe.expert_params(cfg) == expert
    assert flops_mla_moe.routed_per_token(cfg) == 6 * 8 / 64
    body = 27 * mla + dense + 26 * (fixed + 0.75 * expert)
    assert flops_mla_moe.body_params(cfg, 0.75) == body
    # a prefill of one 4-token prompt: the body for every token, the head
    # for the last, expanded attention over 1 + 2 + 3 + 4 keys
    attn = 2 * 27 * 16 * (128 + 64 + 128) * 10
    assert flops_mla_moe.prefill_flops(cfg, 1, 4) == 2 * body * 4 \
        + 2 * head + attn
    # a decode step at position 9 reads 10 keys absorbed
    assert flops_mla_moe.decode_flops(cfg, 2, 9) == 2 * (
        2 * (body + head) + 2 * 27 * 16 * (2 * 512 + 64) * 10)
    # least bytes: each weight once (held experts at their hit share) and
    # the latent cache of every layer up to the position, in bf16
    cache = 27 * 32 * 1001 * 576 * 2
    assert flops_mla_moe.decode_least_bytes(cfg, 32, 1000, 0.5) == (
        27 * mla + 26 * (fixed + 0.5 * 8 * expert) + dense + head) * 2 + cache
    assert flops_mla_moe.mla_least_bytes(cfg, 32, 1000) == 27 * mla * 2 \
        + cache
    total = (flops_mla_moe.mla_least_bytes(cfg, 32, 1000)
             + flops_mla_moe.moe_least_bytes(cfg, 0.5) + (dense + head) * 2)
    assert total == flops_mla_moe.decode_least_bytes(cfg, 32, 1000, 0.5)
    stats = {"moe_decode_steps": 10, "moe_experts_hit": 26 * 8 * 10 // 4}
    assert flops_mla_moe.hit_share(stats, cfg) == 0.25
