"""Model / shape / mesh configuration schema and the architecture registry.

One module per assigned architecture lives next to this file; each exposes
``CONFIG`` (the exact published configuration) and ``SMOKE`` (a reduced
same-family configuration for CPU smoke tests).  ``repro.configs.get(arch)``
resolves ids like ``"phi3-mini-3.8b"``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class MoEConfig:
    """Expert layer.  The GQA stack (mixtral, qwen3) runs the capacity
    layer; the latent-attention stack (``mla`` set) runs the dropless
    expert-parallel layer, which reads the fields after ``aux_loss_weight``.
    """

    n_experts: int = 8       # experts held here (all of them, unless shared out)
    top_k: int = 2
    d_ff: int = 0  # per-expert hidden size
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # dropless expert-parallel layer
    router_experts: int = 0  # experts the router scores; 0: n_experts
    first_expert: int = 0    # router index of the first expert held here
    shared_d_ff: int = 0     # shared experts as one SwiGLU of this width; 0: none
    score_func: str = "softmax"   # softmax | sigmoid
    norm_topk: bool = True   # the top-k weights renormalised to sum to 1
    routed_scale: float = 1.0     # the routed experts' output scale

    @property
    def n_routed(self) -> int:
        return self.router_experts or self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): a low-rank latent of
    keys and values, cached in place of them, beside one rotary key head
    shared by every query head."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 block parameters (zamba2)."""

    state_dim: int = 64
    head_dim: int = 64       # P
    n_heads: int = 0         # derived: d_inner // head_dim if 0
    expand: int = 2          # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 128
    n_groups: int = 1        # B/C groups


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block stack parameters."""

    slstm_every: int = 8     # one sLSTM per this many blocks (7:1 -> 8)
    conv_width: int = 4
    chunk_size: int = 64
    proj_factor: float = 2.0  # mLSTM up-projection
    qk_factor: float = 0.25   # q/k head dim as a fraction of v head dim


@dataclass(frozen=True)
class ZambaConfig:
    shared_period: int = 6   # apply the shared attention block every N layers
    lora_rank: int = 128     # per-application LoRA on the shared block


@dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str              # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0        # 0 -> d_model // n_heads
    # attention
    rope_theta: float = 10_000.0
    sliding_window: int | None = None
    attn_logit_softcap: float | None = None
    use_qkv_bias: bool = False
    use_bias: bool = False   # dense/MLP bias (starcoder2, whisper)
    # block structure
    norm_type: str = "rmsnorm"        # rmsnorm | layernorm
    norm_eps: float = 1e-5
    mlp_type: str = "swiglu"          # swiglu | gelu
    parallel_residual: bool = False   # command-r style
    tie_embeddings: bool = False
    # sub-configs
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    n_dense_layers: int = 0  # leading d_ff-wide SwiGLU layers (latent stack)
    ssm: SSMConfig | None = None
    xlstm: XLSTMConfig | None = None
    zamba: ZambaConfig | None = None
    # enc-dec (whisper)
    n_encoder_layers: int = 0
    max_target_positions: int = 0     # decoder positions (whisper: 448)
    # vlm
    n_image_tokens: int = 0           # stub patch-embedding positions
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # remat: "none" | "full" | "dots"
    remat_policy: str = "full"
    # attention impl: "xla" | "flash" (flash = Pallas kernel, TPU target)
    attention_impl: str = "xla"
    # unroll layer stacks instead of lax.scan — used by the dry-run so that
    # HLO cost analysis (which counts while-loop bodies once) sees the full
    # per-layer FLOPs/bytes; training keeps scan for compact HLO
    unroll_layers: bool = False

    def __post_init__(self):
        # a configuration read from JSON gives its sub-sections as dicts
        for name, kind in _SUBCONFIGS.items():
            value = getattr(self, name)
            if isinstance(value, dict):
                object.__setattr__(self, name, kind(**value))

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Approximate parameter count (for roofline MODEL_FLOPS)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)


_SUBCONFIGS = {"moe": MoEConfig, "mla": MLAConfig, "ssm": SSMConfig,
               "xlstm": XLSTMConfig, "zamba": ZambaConfig}


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str                 # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                 # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}

#: archs with sub-quadratic attention paths run long_500k (see DESIGN.md)
LONG_CONTEXT_ARCHS = {"xlstm-1.3b", "zamba2-7b", "mixtral-8x7b"}


def shapes_for(arch: str) -> list[ShapeConfig]:
    out = [SHAPES["train_4k"], SHAPES["prefill_32k"], SHAPES["decode_32k"]]
    if arch in LONG_CONTEXT_ARCHS:
        out.append(SHAPES["long_500k"])
    return out


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    warmup_steps: int = 100
    total_steps: int = 10_000
    max_grad_norm: float = 1.0
    microbatches: int = 1     # gradient accumulation
    # distributed-optimization tricks
    grad_compression: str = "none"   # none | bf16 | int8
    seed: int = 0


ARCH_IDS = [
    "phi3-mini-3.8b",
    "command-r-35b",
    "starcoder2-15b",
    "internlm2-1.8b",
    "mixtral-8x7b",
    "qwen3-moe-235b-a22b",
    "xlstm-1.3b",
    "zamba2-7b",
    "whisper-medium",
    "internvl2-2b",
    "moonlight-16b-a3b",
]

_MODULES = {
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "command-r-35b": "command_r_35b",
    "starcoder2-15b": "starcoder2_15b",
    "internlm2-1.8b": "internlm2_1_8b",
    "mixtral-8x7b": "mixtral_8x7b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "xlstm-1.3b": "xlstm_1_3b",
    "zamba2-7b": "zamba2_7b",
    "whisper-medium": "whisper_medium",
    "internvl2-2b": "internvl2_2b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def get(arch: str, smoke: bool = False) -> ModelConfig:
    """Resolve an architecture id to its (full or smoke) ModelConfig."""
    import importlib

    if arch not in _MODULES:
        raise KeyError(f"unknown architecture {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.SMOKE if smoke else mod.CONFIG
