"""Mellum2-12B-A2.5B: one chip's share of a four-way expert-parallel
deployment.  [hf: JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

28L, d_model=2304, 32H (GQA kv=4, head_dim 128), vocab=98304 untied,
RMSNorm eps 1e-6.  Period of four layers: three sliding-window layers
(window 1024, default RoPE, theta 500000), then one full-attention layer
(YaRN: factor 16 over 8192 positions, beta_fast 32, beta_slow 1,
attention factor 1.2773).  Every FFN is sparse: 64 SwiGLU experts of
896, top-8 of a softmax router, the 8 gates renormalised, no shared
expert (``intermediate_size`` 7168 is unused).

A v5e-4 host splits the experts four ways and replicates attention; this
chip holds experts 0-15 of every layer and computes their part of each
layer's output (``MoEConfig.held``).  The MTP head is not served.
"""
from repro.config import MoEConfig, ModelConfig, YarnConfig, register

YARN = YarnConfig(factor=16.0, original_max_position=8192, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.2772588722239782)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    d_ff=7168,
    vocab_size=98_304,
    head_dim=128,
    ffn_pattern=("moe",),
    moe=MoEConfig(num_experts=64, top_k=8, d_ff_expert=896,
                  held=tuple(range(16))),
    attn_pattern=("window", "window", "window", "full"),
    sliding_window=1024,
    rope_theta=500_000.0,
    yarn=YARN,
    norm_eps=1e-6,
    source="[hf JetBrains/Mellum2-12B-A2.5B-Instruct; 16 of 64 experts]",
)


def reduced() -> ModelConfig:
    """Two periods, a window of 32, 8 experts top-2 of which 4 held; in
    float32, because at this width a top-2 choice among 8 experts flips
    on bfloat16 rounding and moves the logits more than any bound that
    still tests the mechanism against a float32 reference."""
    return ModelConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        num_layers=8,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        d_ff=128,
        vocab_size=512,
        head_dim=16,
        ffn_pattern=("moe",),
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                      held=(0, 1, 2, 3)),
        attn_pattern=("window", "window", "window", "full"),
        sliding_window=32,
        rope_theta=500_000.0,
        yarn=YARN,
        norm_eps=1e-6,
        dtype="float32",
    )


register(CONFIG, reduced)
