"""LFM2-24B-A2B: gated short convolutions beside GQA, 64-expert MoE with
sigmoid scores and a selection bias, top-4, two leading dense layers.
[hf:LiquidAI/LFM2-24B-A2B config.json]

The published model: all 40 layers and all 64 experts of each MoE
layer. One chip's share (the first layers, one expert shard) is cut in
``repro.launch.serve``. head_dim (2048 / 32) and the tied embeddings
follow the family's convention; the config states neither."""
from repro.configs.base import ModelConfig

_PERIOD = ("full_attention", "conv", "conv", "conv")
_TYPES = ("conv", "conv") + _PERIOD * 9 + _PERIOD[:2]

CONFIG = ModelConfig(
    name="lfm2-24b-a2b", family="moe",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=1536, vocab_size=65536, d_ff_dense=11776,
    layer_types=tuple("attn" if t == "full_attention" else t
                      for t in _TYPES),
    conv_width=3, qk_norm=True, rope_theta=1_000_000.0, mlp="swiglu",
    n_experts=64, top_k=4, n_dense_layers=2, router="sigmoid",
    tie_embeddings=True, norm_eps=1e-5,
    source="hf:LiquidAI/LFM2-24B-A2B (config.json)",
)
