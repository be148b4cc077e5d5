"""Moonlight-16B-A3B (model_type deepseek_v3): 27L d2048, latent attention
(MLA: 16 heads, kv_lora_rank 512, qk 128 + 64 rope, v 128, no q LoRA),
layer 0 a dense SwiGLU of 11264, then 26 MoE layers of 64 routed experts
(top-6, width 1408, sigmoid scores, normalised, x 2.446) plus 2 shared;
vocab 163840 untied, RoPE theta 5e4.
[hf:moonshotai/Moonlight-16B-A3B]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50_000.0,
    norm="rmsnorm",
    mlp="swiglu",
    tie_embeddings=False,
    num_experts=64,
    experts_per_token=6,
    moe_dispatch="dropless",
    moe_d_ff=1408,
    num_shared_experts=2,
    routed_scaling=2.446,
    first_dense_layers=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    notes="MLA + 64 routed (top-6) and 2 shared experts, 1 leading dense layer",
)
