"""moonlight-16b-a3b [moe] — hf:moonshotai/Moonlight-16B-A3B (the DeepSeek-V3
block, arXiv:2412.19437; MLA from DeepSeek-V2, arXiv:2405.04434).

27L d_model=2048 16H vocab=163840, untied; multi-head latent attention
(no query latent, kv latent 512, 128 + 64 rotary query/key dims, 128 value
dims); layer 0 a dense SwiGLU of 11264, then 26 MoE layers: 64 routed
experts of 1408, top-6, sigmoid scores with a selection-only correction
bias, top-6 weights renormalised and scaled by 2.446, plus 2 shared
experts (one SwiGLU of 2816).  RoPE theta 5e4, RMSNorm eps 1e-5.
~15.96 B parameters, ~2.9 B active per token.
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    arch="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50_000.0,
    norm_type="rmsnorm",
    norm_eps=1e-5,
    mlp_type="swiglu",
    n_dense_layers=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff=1408, router_experts=64,
                  shared_d_ff=2816, score_func="sigmoid", norm_topk=True,
                  routed_scale=2.446),
)

SMOKE = CONFIG.replace(
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=MoEConfig(n_experts=8, top_k=3, d_ff=32, router_experts=8,
                  shared_d_ff=64, score_func="sigmoid", norm_topk=True,
                  routed_scale=2.446),
    remat_policy="none",
)
