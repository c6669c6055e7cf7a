"""deepseek-v2-lite — MLA + shared and routed experts
[hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H; multi-head latent attention with no query
compression (kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128); layer 0
dense (SwiGLU 10944), layers 1-26 MoE: 64 routed experts of width 1408,
6 per token (softmax scores, greedy top-6, no renormalisation, scale 1),
2 shared experts; YaRN rotary (factor 40 over 4096 positions); RMSNorm
eps 1e-6; vocab 102400, untied. Routing is dropless, as the model is
served.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    block_pattern=("mla_moe",),
    n_dense_layers=1,
    n_experts=64,
    experts_per_token=6,
    dropless=True,
    moe_d_ff=1408,
    n_shared_experts=2,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    param_dtype="bfloat16",
)
