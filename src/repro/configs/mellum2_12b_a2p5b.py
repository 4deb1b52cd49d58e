"""mellum2-12b-a2.5b [moe] — every layer sparse (64 experts of width 896,
top-8, weights renormalized over the 8, no shared expert), GQA 32/4 heads
of 128, three sliding-window layers (1024) to one full-attention layer,
RoPE theta 5e5 with YaRN on the full layers
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

The published config unties the head (``tie_word_embeddings`` false) and
its model card mentions an MTP head the config has no key for: the model
here ties its head and has no MTP module."""
from repro.models.config import ModelConfig

EXPECTED = dict(n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
                head_dim=128, d_ff=896, vocab=98304, n_experts=64, top_k=8,
                window=1024, rope_theta=500_000.0, yarn_factor=16.0,
                yarn_original_max_pos=8192)

FULL = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, head_dim=128,
    d_ff=896, vocab=98304,
    n_experts=64, top_k=8,
    window=1024, local_global_period=4,
    rope_theta=500_000.0, yarn_factor=16.0, yarn_original_max_pos=8192,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    mlp="silu_gated", norm_eps=1e-6,
    dtype="bfloat16",
)

SMOKE = ModelConfig(
    name="mellum2-smoke", family="moe",
    n_layers=4, d_model=128, n_heads=8, n_kv_heads=2, head_dim=16,
    d_ff=64, vocab=512,
    n_experts=8, top_k=2,
    window=16, local_global_period=4,
    rope_theta=500_000.0, yarn_factor=16.0, yarn_original_max_pos=64,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
    mlp="silu_gated",
    loss_chunk=32, q_chunk=32, kv_chunk=32,
)
