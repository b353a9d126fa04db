"""deepseek-v2-lite [moe] — latent attention (MLA: kv latent 512, no q latent,
q/k heads 128 + 64 RoPE, v heads 128, YaRN x40) and fine-grained MoE: 2 shared
+ 64 routed top-6 with unnormalised softmax weights, balanced within each
sequence (weight 0.001); layer 0 is a dense FFN (d_ff 10944)
(arXiv:2405.04434)."""

from .base import ModelConfig, register


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,
        vocab=102_400,
        head_dim_=128,
        pattern=("mla",),
        kv_lora_rank=512,
        qk_rope_dim=64,
        v_head_dim=128,
        rope_theta=10_000.0,
        yarn_factor=40.0,
        yarn_original_len=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        n_experts=64,
        n_shared_experts=2,
        top_k=6,
        moe_d_ff=1408,
        norm_topk_prob=False,
        aux_loss_alpha=0.001,
        seq_aux=True,
        first_dense=1,
        dense_d_ff=10944,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        family="moe",
        n_layers=3,
        d_model=32,
        n_heads=2,
        n_kv_heads=2,
        d_ff=16,
        vocab=128,
        head_dim_=16,
        pattern=("mla",),
        kv_lora_rank=16,
        qk_rope_dim=8,
        v_head_dim=16,
        yarn_factor=40.0,
        yarn_original_len=16,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        n_experts=8,
        n_shared_experts=2,
        top_k=3,
        moe_d_ff=16,
        norm_topk_prob=False,
        aux_loss_alpha=0.001,
        seq_aux=True,
        first_dense=1,
        dense_d_ff=64,
        remat="none",
    )


register("deepseek-v2-lite", config, smoke)
