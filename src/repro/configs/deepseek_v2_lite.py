"""deepseek-v2-lite [moe] — multi-head latent attention over a latent KV
cache (kv_lora_rank 512 + a shared 64-dim RoPE key, YaRN factor 40), a
dense first layer (d_ff 10944), then 64 routed experts of 1408 (softmax
top-6, no renormalisation) and 2 ungated shared experts (one MLP of 2816).
[hf:deepseek-ai/DeepSeek-V2-Lite]"""
from repro.config import ModelConfig, YarnScaling, register

NAME = "deepseek-v2-lite"


def config() -> ModelConfig:
    return ModelConfig(
        name=NAME,
        family="moe",
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,          # qk_nope_head_dim + qk_rope_head_dim
        d_ff=1408,             # per-expert width
        vocab_size=102400,
        mlp_type="moe",
        activation="silu",
        rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=40.0,
                                 original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        shared_expert_d_ff=2816,
        shared_expert_gated=False,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        first_k_dense_replace=1,
        dense_d_ff=10944,
        max_seq_len=163840,
        bpd_k=8,
    )


def smoke_config() -> ModelConfig:
    return config().replace(
        num_layers=2,
        d_model=128,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        d_ff=32,
        dense_d_ff=96,
        vocab_size=256,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        num_experts=4,
        num_experts_per_tok=2,
        shared_expert_d_ff=64,
        bpd_k=4,
        max_seq_len=256,
    )


register(NAME, config, smoke_config)
