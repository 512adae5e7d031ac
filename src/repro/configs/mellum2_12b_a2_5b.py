"""Mellum2-12B-A2.5B — sparse experts in every block (64 of width 896,
top-8, renormalised, no shared expert), three sliding-window layers
(window 1024, plain RoPE) before each full-attention layer (YaRN RoPE).

[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json] The whole model on
one device: every expert held. The published MTP head is not in the config
and is left out; the config gives no q/k norm.
"""
from repro.configs.base import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,           # per-expert ffn width
    vocab_size=98_304,
    num_experts=64,
    top_k=8,
    held_experts=tuple(range(64)),
    sliding_window=1024,
    layer_pattern=("local", "local", "local", "attn"),
    rope_theta=500_000.0,
    rope_yarn=Yarn(factor=16.0, original_max_positions=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782),
    norm_eps=1e-6,
    tie_embeddings=False,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
