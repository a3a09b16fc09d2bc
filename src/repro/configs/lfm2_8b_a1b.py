"""lfm2-8b-a1b [lfm2]: 24L d_model=2048, 18 gated short-conv blocks (3 taps)
and 6 GQA layers (32 heads, 8 KV heads, head_dim 64, QK norm), 2 leading
dense SwiGLU layers of width 7168, then 32 routed experts of width 1792,
top 4 by sigmoid score, vocab 65536, tied head.
[hf:LiquidAI/LFM2-8B-A1B config.json]

``lfm2-8b-a1b-ep4`` is one chip's share of a four-chip expert-parallel
host, derived from the published configuration by the reduced keys alone:
published layers 0 (conv, dense FFN) and 2-5 (one whole period of the
expert layers: attention, conv, conv, conv), experts 0-7 of the 32, and
rows 0-16,383 of the vocabulary.  Widths, heads, the router's 32 outputs
and its top 4 are as published."""
import dataclasses

from repro.configs.base import ModelConfig

LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv",
)

CONFIG = ModelConfig(
    name="lfm2-8b-a1b", family="lfm2",
    n_layers=24, d_model=2048, vocab=65536, d_ff=7168,
    n_heads=32, n_kv_heads=8, head_dim=64, qk_norm=True, norm_eps=1e-5,
    tie_embeddings=True, rope_theta=1e6, act="silu",
    n_experts=32, moe_top_k=4, d_ff_expert=1792,
    layer_types=LAYER_TYPES, n_dense_layers=2, conv_width=3,
    max_seq=128000,
)

EP4_SHARE = dataclasses.replace(
    CONFIG, name="lfm2-8b-a1b-ep4",
    n_layers=5, layer_types=(LAYER_TYPES[0],) + LAYER_TYPES[2:6],
    n_dense_layers=1, experts_held=(0, 8), vocab=16384,
)

CONFIGS = {c.name: c for c in (CONFIG, EP4_SHARE)}
