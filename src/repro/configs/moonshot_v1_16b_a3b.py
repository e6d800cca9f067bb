"""moonshot-v1-16b-a3b [moe] — Moonlight-16B-A3B (DeepSeek-V3 architecture).

27L d_model=2048: latent attention (MLA: 16 heads, kv_lora_rank 512, nope
128 + rope 64 per head for q/k, v 128, no q_lora_rank); layer 0 dense
(SwiGLU 11264), layers 1-26 MoE (64 routed experts of width 1408, top 6,
sigmoid scores with a selection-only bias, top-k renormalized then scaled
by 2.446; 2 shared experts, one SwiGLU of 2816); RMSNorm eps 1e-5; rope
theta 50000, no scaling; untied embeddings, vocab 163840.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

``EP8`` is one chip's share of an eight-chip expert-parallel deployment:
routed experts 0-7 of 64 held (the router still scores all 64); attention,
the shared expert, the dense layer, embedding and head replicated.
"""
import dataclasses

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab=163840,
    norm="rmsnorm", mlp="swiglu", norm_eps=1e-5, rope_theta=50_000.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    first_dense_layers=1, dense_ff=11264,
    n_experts=64, top_k=6, shared_expert_ff=2816,   # 2 shared x 1408
    router_score="sigmoid", routed_scale=2.446,
    capacity_factor=1.25,                           # training only
)

EP8 = dataclasses.replace(CONFIG, name="moonlight-16b-a3b-ep8",
                          experts_held=8, first_expert=0)

SMOKE = ModelConfig(
    name="moonshot-v1-16b-a3b-smoke", family="moe",
    n_layers=3, d_model=96, n_heads=4, n_kv_heads=4,
    d_ff=32, vocab=512, norm="rmsnorm", mlp="swiglu", norm_eps=1e-5,
    rope_theta=50_000.0,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    first_dense_layers=1, dense_ff=128,
    n_experts=8, top_k=2, shared_expert_ff=64,
    router_score="sigmoid", routed_scale=2.446,
    capacity_factor=2.0, tp_target=4,
)
