"""Lower the assigned architecture configs to cost-model workloads.

This is how the paper's technique becomes a first-class feature for the
model zoo: every ArchConfig lowers to the per-layer (CONV/GEMM) descriptor
list the ConfuciuX Env consumes, so ``launch/search.py --arch qwen3-32b``
searches accelerator resource assignments for serving/training that model.

Lowering conventions (per-layer GEMMs for one forward pass over ``tokens``
token positions):
  * attention: QKV / output projections as GEMMs; score and context batched
    GEMMs folded via ``repeat=heads``.
  * MoE: router GEMM + expert-bank GEMMs with M = tokens * top_k (the routed
    token-slots) and ``repeat=1`` per layer group -- each expert instance is
    one hardware partition in LP.
  * Mamba2/SSD: in/out projections + conv (as CONV descriptor) + the SSD
    intra-chunk matmuls as seq x seq GEMMs per chunk.
  * identical consecutive layers collapse into one entry with ``repeat=L``
    so RL episode lengths stay tractable for 90+ layer models (layers.py).

The ``mla_moe`` family (DeepSeek-V3) always lowers one decode step:
``tokens`` requests, each one new token against its own ``ctx``-position
latent cache, through the absorbed MLA path decode engines run (every head
shares one ``kv_lora + rope``-wide cache row):

  q_a, q_b, kv_a, o_proj      GEMM, M = tokens, repeat L
  q_absorb, v_absorb          BMM, one instance per head (X = heads)
  score, ctx                  BMM, one instance per request (X = tokens):
                              (heads x ctx) against that request's cache
  dense up/down               GEMM, the first ``first_k_dense_replace`` layers
  router, shared up/down      GEMM, the other layers
  exp_up, exp_down            EXPERTS, X = routed experts, each seeing
                              M = ceil(tokens * top_k / experts) tokens
                              (uniform routing)
  unembed                     GEMM

Norms, RoPE, softmax and the routing's top-k cost no MACs here (the
descriptor counts MACs only), and the multi-token-prediction layer is left
out.  ``repro.costmodel.deepseek_v3_ref`` is the plain forward these rows
are checked against.
"""
from __future__ import annotations

import math
from typing import List

from repro import configs
from repro.configs.base import ArchConfig
from repro.costmodel.layers import LayerSpec


def _attn_layers(cfg: ArchConfig, tokens: int, ctx: int, repeat: int,
                 prefix: str) -> List[LayerSpec]:
    d, hd, H, Kv = cfg.d_model, cfg.hd(), cfg.num_heads, cfg.num_kv_heads
    return [
        LayerSpec.gemm(tokens, (H + 2 * Kv) * hd, d, repeat=repeat,
                       name=f"{prefix}.qkv"),
        LayerSpec.gemm(tokens, ctx, hd, repeat=repeat * H,
                       name=f"{prefix}.score"),
        LayerSpec.gemm(tokens, hd, ctx, repeat=repeat * H,
                       name=f"{prefix}.ctx"),
        LayerSpec.gemm(tokens, d, H * hd, repeat=repeat,
                       name=f"{prefix}.out"),
    ]


def _ffn_layers(cfg: ArchConfig, tokens: int, repeat: int,
                prefix: str) -> List[LayerSpec]:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.num_experts:
        routed = tokens * cfg.experts_per_token
        out = [LayerSpec.gemm(tokens, cfg.num_experts, d, repeat=repeat,
                              name=f"{prefix}.router")]
        n_mats = 3 if cfg.mlp_act == "swiglu" else 2
        out.append(LayerSpec.gemm(routed, f * (n_mats - 1), d, repeat=repeat,
                                  name=f"{prefix}.experts_up"))
        out.append(LayerSpec.gemm(routed, d, f, repeat=repeat,
                                  name=f"{prefix}.experts_down"))
        return out
    if cfg.mlp_act == "swiglu":
        return [LayerSpec.gemm(tokens, 2 * f, d, repeat=repeat,
                               name=f"{prefix}.up_gate"),
                LayerSpec.gemm(tokens, d, f, repeat=repeat,
                               name=f"{prefix}.down")]
    return [LayerSpec.gemm(tokens, f, d, repeat=repeat,
                           name=f"{prefix}.up"),
            LayerSpec.gemm(tokens, d, f, repeat=repeat,
                           name=f"{prefix}.down")]


def _mamba_layers(cfg: ArchConfig, tokens: int, repeat: int,
                  prefix: str) -> List[LayerSpec]:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    H = di // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    S = cfg.ssm_state
    Q = min(cfg.ssm_chunk, tokens)
    nc = max(tokens // Q, 1)
    return [
        LayerSpec.gemm(tokens, 2 * di + 2 * S + H, d, repeat=repeat,
                       name=f"{prefix}.in_proj"),
        LayerSpec.conv(di + 2 * S, 1, tokens + 3, 1, 4, 1, repeat=repeat,
                       name=f"{prefix}.conv1d"),
        # SSD intra-chunk: (Q x Q) score and mix matmuls per chunk.
        LayerSpec.gemm(Q, Q, S, repeat=repeat * nc,
                       name=f"{prefix}.ssd_cb"),
        LayerSpec.gemm(Q, H * P, Q, repeat=repeat * nc,
                       name=f"{prefix}.ssd_mix"),
        LayerSpec.gemm(tokens, d, di, repeat=repeat,
                       name=f"{prefix}.out_proj"),
    ]


def _mla_moe_decode(cfg: ArchConfig, batch: int,
                    ctx: int) -> List[LayerSpec]:
    """One decode step of an MLA + fine-grained-MoE model, up to the
    unembedding (module docstring)."""
    d, H, L = cfg.d_model, cfg.num_heads, cfg.num_layers
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lat = cfg.kv_lora_rank
    k_dense = cfg.first_k_dense_replace
    L_moe = L - k_dense
    f, E = cfg.d_ff, cfg.num_experts
    shared = cfg.n_shared_experts * f
    per_expert = math.ceil(batch * cfg.experts_per_token / E)
    return [
        LayerSpec.gemm(batch, cfg.q_lora_rank, d, repeat=L, name="q_a"),
        LayerSpec.gemm(batch, H * (nope + rope), cfg.q_lora_rank, repeat=L,
                       name="q_b"),
        LayerSpec.gemm(batch, lat + rope, d, repeat=L, name="kv_a"),
        LayerSpec.bmm(batch, lat, nope, H, repeat=L, name="q_absorb"),
        LayerSpec.bmm(H, ctx, lat + rope, batch, repeat=L, name="score"),
        LayerSpec.bmm(H, lat, ctx, batch, repeat=L, name="ctx"),
        LayerSpec.bmm(batch, v, lat, H, repeat=L, name="v_absorb"),
        LayerSpec.gemm(batch, d, H * v, repeat=L, name="o_proj"),
        LayerSpec.gemm(batch, 2 * cfg.dense_d_ff, d, repeat=k_dense,
                       name="dense_up"),
        LayerSpec.gemm(batch, d, cfg.dense_d_ff, repeat=k_dense,
                       name="dense_down"),
        LayerSpec.gemm(batch, E, d, repeat=L_moe, name="router"),
        LayerSpec.gemm(batch, 2 * shared, d, repeat=L_moe,
                       name="shared_up"),
        LayerSpec.gemm(batch, d, shared, repeat=L_moe, name="shared_down"),
        LayerSpec.experts(per_expert, 2 * f, d, E, repeat=L_moe,
                          name="exp_up"),
        LayerSpec.experts(per_expert, d, f, E, repeat=L_moe,
                          name="exp_down"),
    ]


def lower_arch(name: str, tokens: int = 1024, ctx: int = None,
               include_unembed: bool = True) -> List[LayerSpec]:
    """Lower an architecture to its serving workload (see
    :func:`lower_config`)."""
    return lower_config(configs.get(name), tokens, ctx, include_unembed)


def lower_config(cfg: ArchConfig, tokens: int = 1024, ctx: int = None,
                 include_unembed: bool = True) -> List[LayerSpec]:
    """Lower a config to its serving workload at ``tokens`` positions.

    ctx: attention context length (defaults to tokens -- self-attention over
    the processed window).  The ``mla_moe`` family lowers one decode step
    instead: ``tokens`` requests, each against ``ctx`` cached positions.
    """
    ctx = ctx or tokens
    out: List[LayerSpec] = []
    fam = cfg.family
    L = cfg.num_layers
    if fam in ("dense", "moe"):
        out += _attn_layers(cfg, tokens, ctx, L, "blk")
        out += _ffn_layers(cfg, tokens, L, "blk")
    elif fam == "ssm":
        out += _mamba_layers(cfg, tokens, L, "blk")
    elif fam == "hybrid":
        sites = L // cfg.shared_attn_period
        out += _mamba_layers(cfg, tokens, L, "ssm")
        out += _attn_layers(cfg, tokens, ctx, sites, "shared")
        out += _ffn_layers(cfg, tokens, sites, "shared")
    elif fam == "audio":
        Se = cfg.encoder_seq
        out += _attn_layers(cfg, Se, Se, cfg.encoder_layers, "enc")
        out += _ffn_layers(cfg, Se, cfg.encoder_layers, "enc")
        out += _attn_layers(cfg, tokens, ctx, L, "dec.self")
        out += _attn_layers(cfg, tokens, Se, L, "dec.cross")
        out += _ffn_layers(cfg, tokens, L, "dec")
    elif fam == "mla_moe":
        out += _mla_moe_decode(cfg, tokens, ctx)
    elif fam == "vlm":
        n_cross = L // cfg.cross_attn_period
        n_self = L - n_cross
        out += _attn_layers(cfg, tokens, ctx, n_self, "self")
        out += _ffn_layers(cfg, tokens, n_self, "self")
        out += _attn_layers(cfg, tokens, cfg.vision_seq, n_cross, "cross")
        out += _ffn_layers(cfg, tokens, n_cross, "cross")
    else:
        raise ValueError(fam)
    if include_unembed:
        out.append(LayerSpec.gemm(tokens, cfg.vocab_size, cfg.d_model,
                                  name="unembed"))
    return out


def arch_names() -> List[str]:
    return list(configs.ARCH_IDS)
