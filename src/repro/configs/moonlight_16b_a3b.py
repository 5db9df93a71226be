"""Moonlight-16B-A3B — DeepSeek-V3 block: latent attention (MLA, no query
LoRA), 64 routed experts top-6 by biased sigmoid scores plus 2 shared
experts, one leading dense layer.
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2502.16982]

RoPE rotates adjacent pairs of the 64 rope dimensions, as DeepSeek-V3's
published code does (``rope_type="interleaved"``).  The routed experts'
balance loss coefficient is not in the config: DeepSeek-V3's 0.0001
(arXiv:2412.19437).
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="mla_moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,           # per-expert FFN width
    vocab_size=163840,
    norm_eps=1e-5,
    rope_theta=50_000.0,
    rope_type="interleaved",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    router="sigmoid",
    routed_scaling_factor=2.446,
    moe_capacity_factor=0.0,
    router_aux_loss_coef=1e-4,
    first_dense_layers=1,
    dense_d_ff=11264,
)


def tiny() -> ModelConfig:
    return ModelConfig(
        name="moonlight-tiny", family="mla_moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=32, vocab_size=256, norm_eps=1e-5,
        rope_theta=50_000.0, rope_type="interleaved", kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=16, num_experts_per_tok=4, num_shared_experts=2,
        router="sigmoid", routed_scaling_factor=2.446,
        moe_capacity_factor=0.0, router_aux_loss_coef=1e-4,
        first_dense_layers=1, dense_d_ff=128, vocab_pad_multiple=8,
    )
