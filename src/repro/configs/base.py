"""Architecture config schema + registry.

One file per assigned architecture lives in this package; each exposes
``CONFIG`` (the exact published shape) and ``smoke()`` (a reduced same-family
config for CPU tests).  ``repro.configs.get(name)`` looks either up.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                # dense | moe | ssm | hybrid | audio | vlm
                               # | mla_moe
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None

    # Attention details.
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0

    # MoE (d_ff above is the per-expert hidden dim when num_experts > 0).
    num_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25

    # Multi-head latent attention (DeepSeek-V2/V3): queries and keys/values
    # pass through low-rank latents; each head's query/key is a no-RoPE part
    # plus a decoupled RoPE part shared across heads.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Fine-grained MoE: always-on shared experts beside the routed ones, the
    # first ``first_k_dense_replace`` layers dense with a ``dense_d_ff``
    # (intermediate_size) SwiGLU MLP, and sigmoid routing limited to the
    # best ``topk_group`` of ``n_group`` expert groups.
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0
    dense_d_ff: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0

    # SSM (Mamba2 / SSD).
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128

    # Hybrid (zamba2): one *shared* attention block invoked every
    # ``shared_attn_period`` SSM layers.
    shared_attn_period: int = 0

    # Encoder-decoder (whisper): ``num_layers`` is the decoder depth.
    encoder_layers: int = 0
    encoder_seq: int = 1500     # whisper-small: 30 s -> 1500 frames

    # VLM: one cross-attention layer every ``cross_attn_period`` layers
    # (counted within num_layers) attending to ``vision_seq`` patch embeds.
    cross_attn_period: int = 0
    vision_seq: int = 1601      # (448/14)^2 + cls for Llama-3.2-Vision

    mlp_act: str = "swiglu"     # swiglu | gelu
    tie_embeddings: bool = False
    norm_eps: float = 1e-6

    # Numerics.
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """True iff decode cost/state is sub-linear in context (SSM/hybrid).

        Pure full-attention archs skip the long_500k shape (DESIGN.md
        SArch-applicability)."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, f, hd = self.d_model, self.d_ff, self.hd()
        qkv = d * hd * (self.num_heads + 2 * self.num_kv_heads) + hd * \
            self.num_heads * d
        if self.mlp_act == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        if self.num_experts:
            mlp = self.num_experts * 3 * d * f + d * self.num_experts
        if self.family == "mla_moe":
            H = self.num_heads
            attn = (d * self.q_lora_rank
                    + self.q_lora_rank * H * (self.qk_nope_head_dim
                                              + self.qk_rope_head_dim)
                    + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                    + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                               + self.v_head_dim)
                    + H * self.v_head_dim * d)
            moe = (self.num_experts + self.n_shared_experts) * 3 * d * f \
                + d * self.num_experts
            k = self.first_k_dense_replace
            return int(self.num_layers * attn + k * 3 * d * self.dense_d_ff
                       + (self.num_layers - k) * moe
                       + 2 * self.vocab_size * d)
        if self.family == "ssm":
            di = self.ssm_expand * d
            blk = d * (2 * di + 2 * self.ssm_state) + di * d
        elif self.family == "hybrid":
            di = self.ssm_expand * d
            blk = d * (2 * di + 2 * self.ssm_state) + di * d + mlp // 4
        else:
            blk = qkv + mlp
        n = self.num_layers * blk + 2 * self.vocab_size * d
        if self.encoder_layers:
            n += self.encoder_layers * (qkv + mlp)
        return int(n)


# Input shape grid (the brief's per-arch shape set).
@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # "train" | "prefill" | "decode"


SHAPES: Tuple[InputShape, ...] = (
    InputShape("train_4k", 4096, 256, "train"),
    InputShape("prefill_32k", 32768, 32, "prefill"),
    InputShape("decode_32k", 32768, 128, "decode"),
    InputShape("long_500k", 524288, 1, "decode"),
)


def get_shape(name: str) -> InputShape:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


ARCH_IDS: List[str] = [
    "zamba2_1p2b",
    "phi3p5_moe_42b",
    "qwen3_moe_235b",
    "whisper_small",
    "qwen3_32b",
    "qwen1p5_0p5b",
    "starcoder2_3b",
    "qwen2p5_3b",
    "mamba2_130m",
    "llama3p2_vision_90b",
]

# CLI-friendly aliases (the brief's ids).
ALIASES = {
    "zamba2-1.2b": "zamba2_1p2b",
    "phi3.5-moe-42b-a6.6b": "phi3p5_moe_42b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "whisper-small": "whisper_small",
    "qwen3-32b": "qwen3_32b",
    "qwen1.5-0.5b": "qwen1p5_0p5b",
    "starcoder2-3b": "starcoder2_3b",
    "qwen2.5-3b": "qwen2p5_3b",
    "mamba2-130m": "mamba2_130m",
    "llama-3.2-vision-90b": "llama3p2_vision_90b",
}


def canonical(name: str) -> str:
    return ALIASES.get(name, name.replace("-", "_").replace(".", "p"))


def get(name: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.CONFIG


def get_smoke(name: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.smoke()


def all_configs() -> List[ArchConfig]:
    return [get(a) for a in ARCH_IDS]
