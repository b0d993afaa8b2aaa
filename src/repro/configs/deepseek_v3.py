"""deepseek-v3 [mla_moe]: 61L (first 3 dense) d_model=7168 128H, MLA
(q_lora 1536, kv_lora 512, qk 128+64 rope, v 128), 256 routed experts top-8
+ 1 shared, expert d_ff=2048, dense d_ff=18432, vocab=129280
(hf:deepseek-ai/DeepSeek-V3 config.json; the one MTP layer is left out).

Not in ``ARCH_IDS``: the LM runtime does not build MLA, so this config is
lowered only for the cost model, as one decode step
(``arch_workloads.lower_arch(name, tokens=64, ctx=32768)``, workload
``deepseek_v3_decode``)."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek_v3", family="mla_moe", num_layers=61, d_model=7168,
    num_heads=128, num_kv_heads=128, d_ff=2048, vocab_size=129280,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, num_experts=256,
    experts_per_token=8, n_shared_experts=1, first_k_dense_replace=3,
    dense_d_ff=18432, n_group=8, topk_group=4, routed_scaling_factor=2.5,
    rope_theta=10000.0, mlp_act="swiglu", norm_eps=1e-6)


def smoke() -> ArchConfig:
    return ArchConfig(
        name="deepseek_v3_smoke", family="mla_moe", num_layers=3,
        d_model=64, num_heads=4, num_kv_heads=4, d_ff=16, vocab_size=256,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, num_experts=8,
        experts_per_token=2, n_shared_experts=1, first_k_dense_replace=1,
        dense_d_ff=96, n_group=4, topk_group=2, routed_scaling_factor=2.5,
        rope_theta=10000.0, mlp_act="swiglu", norm_eps=1e-6)
