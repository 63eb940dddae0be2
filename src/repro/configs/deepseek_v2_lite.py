"""deepseek-v2-lite [moe] — multi-head latent attention (no q compression),
YaRN rope, 64 routed experts top-6 + 2 shared, layer 0 dense.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,               # q/k head: nope 128 + rope 64
    d_ff=1408,                  # per-expert intermediate
    vocab_size=102400,
    num_experts=64,
    experts_per_token=6,
    norm_topk_prob=False,
    shared_d_ff=2816,           # 2 shared experts of 1408, as one SwiGLU
    first_dense_layers=1,
    dense_d_ff=10944,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_factor=40.0,
    rope_original_max_len=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    max_seq_len=163840,
    norm_eps=1e-6,
    mlp_activation="swiglu",
)

SMOKE_CONFIG = CONFIG.replace(
    name="deepseek-v2-lite-smoke",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    head_dim=24,
    d_ff=32,
    vocab_size=512,
    num_experts=16,
    experts_per_token=4,
    experts_held=4,
    shared_d_ff=64,
    dense_d_ff=128,
    kv_lora_rank=16,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_original_max_len=64,
    max_seq_len=128,
)
