"""DeepSeek-V2-Lite — latent attention (MLA) and DeepSeekMoE
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite].

27 layers, d_model 2048, 16 heads.  MLA with no query compression: each
token caches a 512-wide latent (RMS-normed) and one 64-wide rope key
shared by every head; heads have 128 no-rope and 64 rope query/key
dims and 128 value dims.  Yarn rope (factor 40 over 4096 positions).
Layer 0 is a dense SiLU-gated MLP of width 10944; the 26 others hold 64
routed experts of width 1408 (softmax, greedy top-6, gates not
renormalised) and 2 shared experts.  Untied vocabulary of 102400.
"""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    vocab_size=102400,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    rope_factor=40.0,
    rope_original_max_pos=4096,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    d_ff=10944,
    mlp_act="swiglu",
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    num_dense_layers=1,
    norm_topk_prob=False,
    tie_embeddings=False,
    source="arXiv:2405.04434 (DeepSeek-V2), deepseek-ai/DeepSeek-V2-Lite",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke", family="moe", num_layers=3,
        d_model=256, vocab_size=2048, num_heads=4, num_kv_heads=4,
        head_dim=48, kv_lora_rank=64, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, rope_factor=40.0,
        rope_original_max_pos=4096, rope_mscale=0.707,
        rope_mscale_all_dim=0.707, d_ff=512, mlp_act="swiglu",
        num_experts=4, experts_per_token=2, moe_d_ff=64,
        num_shared_experts=2, num_dense_layers=1, norm_topk_prob=False,
        tie_embeddings=False, source=CONFIG.source)
