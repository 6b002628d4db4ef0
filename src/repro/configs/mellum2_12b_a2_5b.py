"""Mellum2-12B-A2.5B [huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
config.json] — 28 sparse layers of 64 routed experts (width 896, 8 a
token, softmax renormalised over the chosen 8, no shared expert) under
GQA attention (32 q / 4 kv heads of 128); three layers of four attend
within a 1,024 window with RoPE at 5e5, the fourth to every position with
YaRN (factor 16 over 8,192 original positions)."""
from repro.configs.base import MixedAttentionConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    source="huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,            # intermediate_size; no layer is dense
    vocab_size=98304,
    rope_theta=500_000.0,
    sliding_window=1024,
    norm_eps=1e-6,
    moe=MoEConfig(num_experts=64, top_k=8, d_ff_expert=896, layer_period=1),
    mixed_attention=MixedAttentionConfig(
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        full_rope_theta=500_000.0,
        yarn_factor=16.0,
        yarn_original_max_position_embeddings=8192,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_attention_factor=1.2772588722239782),
)
