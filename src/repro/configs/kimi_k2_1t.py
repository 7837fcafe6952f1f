"""Kimi-K2 1T-A32B (Kimi-K2-Instruct) — DeepSeek-V3's layer: multi-head latent
attention, one leading dense layer, then 60 layers of 384 sigmoid-routed
experts (top-8) plus one shared expert [hf:moonshotai/Kimi-K2-Instruct
config.json; arXiv:2412.19437 (DeepSeek-V3); verified].
"""
from repro.configs.base import MLAConfig, ModelConfig, QuantConfig, YarnConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=64,
    n_kv_heads=64,
    d_ff=2048,  # moe_intermediate_size: each expert's width
    vocab_size=163840,
    block_pattern=("mla",),
    n_experts=384,
    experts_per_token=8,
    router="sigmoid",
    routed_scaling=2.827,
    n_shared_experts=1,
    first_dense_layers=1,
    dense_d_ff=18432,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=32.0, original_max_position=4096, beta_fast=1.0, beta_slow=1.0,
                    mscale=1.0, mscale_all_dim=1.0),
    rope_theta=50_000.0,
    norm_eps=1e-6,
    embed_scale=False,
    quant=QuantConfig(enabled=True, act_bits=8, weight_bits=8),
    source="[hf:moonshotai/Kimi-K2-Instruct; arXiv:2412.19437; verified]",
)
