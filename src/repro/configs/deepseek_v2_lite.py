"""deepseek-v2-lite — multi-head latent attention and fine-grained MoE.

[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]
27L d_model=2048 16H vocab=102400, RMSNorm eps 1e-6, untied head.

Attention is MLA without q compression: a 512-wide latent (normed) and a
64-wide rotary key per position are all the cache holds; q and k carry
128 no-rope + 64 rope dims a head, v 128.  The rotary is YaRN (factor 40
over an original 4096 positions, mscale 0.707 on both), which also
scales the softmax.  Layer 0 has a dense SwiGLU of 10944; layers 1-26
route each token softmax-greedy to 6 of 64 experts of 1408 without
renormalising the gates, beside 2 shared experts.  This config holds all
64 experts, with the capacity dispatch; a chip's share under expert
parallelism sets `moe.held` to its slice (the dropless held layer).
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRN

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=102_400,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_ff=1408,
                  first_k_dense=1, dense_d_ff=10944, norm_topk_prob=False),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    rope_theta=10_000.0,
    norm_eps=1e-6,
)
