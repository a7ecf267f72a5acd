"""deepseek-v2-lite — multi-head latent attention and fine-grained MoE
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].

27L d_model=2048 16H MLA (kv_lora 512, no q-LoRA; nope/rope/v 128/64/128,
yarn RoPE x40), a leading dense layer (d_ff 10944), then MoE 64e top-6 of
d_ff 1408 with 2 shared experts and un-renormalised softmax gates;
vocab 102400, untied head.
"""
from repro.configs.base import ModelConfig, register


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        arch_type="moe",
        source="arXiv:2405.04434 (DeepSeek-V2); DeepSeek-V2-Lite config.json",
        num_layers=27,
        d_model=2048,
        vocab_size=102_400,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,            # q/k head width: nope 128 + rope 64
        rope_theta=10_000.0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        yarn_factor=40.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        d_ff=10_944,             # the leading dense layer's MLP
        first_k_dense=1,
        num_experts=64,
        experts_per_token=6,
        moe_d_ff=1408,
        num_shared_experts=2,
        norm_topk_prob=False,
        norm_eps=1e-6,
    )


def smoke() -> ModelConfig:
    """One dense and two MoE layers at small widths; every mechanism on
    (MLA, yarn, shared experts, un-renormalised gates)."""
    from dataclasses import replace

    return replace(
        full(), name="deepseek-v2-lite-smoke", num_layers=3, d_model=128,
        vocab_size=512, num_heads=4, num_kv_heads=4, head_dim=48,
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, d_ff=256, num_experts=4, experts_per_token=2,
        moe_d_ff=64, num_shared_experts=2,
    )


register("deepseek-v2-lite", full, smoke)
