"""Mellum2-12B-A2.5B — 28L d_model=2304 32H (GQA kv=4, hd 128), every layer
MoE (64 experts of 896, top-8 renormalised, none shared), a 1,024-token
window on 3 of every 4 layers (layer i full when i % 4 == 3), RoPE theta
500,000 with YaRN (16x over 8,192 positions) on the full layers, RMSNorm
1e-6, untied head, vocab 98304.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]"""
from repro.configs.base import ModelConfig, register_arch

CONFIG = register_arch(
    ModelConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        num_layers=28,
        d_model=2304,
        num_heads=32,
        num_kv_heads=4,
        d_head=128,
        d_ff=0,                 # all layers are MoE
        moe_d_ff=896,
        num_experts=64,
        num_experts_per_tok=8,
        vocab_size=98304,
        act="silu",
        norm="rmsnorm",
        norm_eps=1e-6,
        rope_theta=500000.0,
        sliding_window=1024,
        full_attention_every=4,
        full_attention_offset=3,
        yarn_factor=16.0,
        yarn_original_max_position=8192,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_attention_factor=1.2772588722239782,
        num_function_groups=7,
        source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
    )
)
