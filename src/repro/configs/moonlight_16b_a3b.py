"""Moonlight-16B-A3B [mla_moe]: 27L d_model=2048, multi-head latent
attention (16 heads, no q low rank, kv latent 512 + shared RoPE key 64,
k/v heads 128 + 128, RoPE theta 5e4), layer 0 a dense gated-SiLU MLP of
11264, layers 1-26 MoE: 64 routed experts of 1408, top 6 by sigmoid score
+ correction bias, weights renormalised x 2.446, 2 shared experts; vocab
163840, untied, RMS eps 1e-5 [hf:moonshotai/Moonlight-16B-A3B config.json;
the DeepSeek-V3 block, arXiv:2412.19437].

Served by repro.models.moonlight.ExpertOffload with its routed experts in
host memory; it is not one of the dry-run and training architectures."""

from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="mla_moe",
        n_layers=27, d_model=2048, vocab=163840,
        n_heads=16, n_kv_heads=16, d_ff=11264,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=50_000.0,
        n_experts=64, top_k=6, n_shared_experts=2, d_expert=1408,
        routed_scaling=2.446, first_dense_layers=1,
        mlp="gated_silu", norm="rms", rms_norm_eps=1e-5,
    )


def smoke_config() -> ModelConfig:
    return config().replace(
        name="moonlight-smoke", n_layers=3, d_model=64, vocab=512,
        n_heads=4, n_kv_heads=4, d_ff=96, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_experts=8, top_k=2, d_expert=32, n_shared_experts=2,
    )
