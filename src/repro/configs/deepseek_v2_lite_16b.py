"""deepseek-v2-lite-16b [moe]: 27L d=2048 16H multi-head latent attention
(kv_lora_rank 512, qk 128+64 rope, v 128, no q LoRA), layer 0 dense SwiGLU
10944, layers 1-26 MoE: 64 routed experts of 1408 top-6 (softmax, greedy,
gates not renormalised) + 2 shared experts (2816), vocab=102400 untied,
YaRN RoPE x40 from 4096 positions.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from ._base import MLACfg, ModelConfig, MoECfg, YarnCfg


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, head_dim=192, d_ff=10944, vocab=102400,
        pattern=("mla",) * 27, rope_theta=10000.0, activation="swiglu",
        tie_embeddings=False,
        moe=MoECfg(n_experts=64, top_k=6, d_ff_expert=1408,
                   shared_expert=True, d_ff_shared=2 * 1408,
                   norm_topk_prob=False),
        mla=MLACfg(kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                   v_head_dim=128),
        yarn=YarnCfg(factor=40.0, original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        first_k_dense=1, norm_eps=1e-6, family="moe",
    )


def smoke_config() -> ModelConfig:
    """The same stack at CPU-test widths: one dense layer, then MoE layers
    of 8 experts (top-2, two shared), every expert held."""
    return ModelConfig(
        name="deepseek-v2-lite-smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=24, d_ff=96, vocab=512,
        pattern=("mla",) * 3, rope_theta=10000.0, activation="swiglu",
        tie_embeddings=False,
        moe=MoECfg(n_experts=8, top_k=2, d_ff_expert=32, shared_expert=True,
                   d_ff_shared=2 * 32, norm_topk_prob=False),
        mla=MLACfg(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                   v_head_dim=16),
        yarn=YarnCfg(factor=40.0, original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        first_k_dense=1, norm_eps=1e-6, family="moe",
    )
