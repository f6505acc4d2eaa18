"""qwen3-next-80b-a3b — Qwen3-Next-80B-A3B. [hf:Qwen/Qwen3-Next-80B-A3B-Instruct]
48L d_model=2048 in periods of 4: three Gated DeltaNet layers (16 key and
32 value heads of 128, conv width 4), then one gated attention layer (16H,
GQA kv=2, head_dim=256, qk-norm, rotary on the first quarter of each head,
sigmoid output gate).  Every layer's FFN is an MoE: 512 experts top-10,
expert d_ff=512, plus a shared expert of d_ff=512 behind a sigmoid gate.
vocab=151936, untied.  The published RMSNorms scale by (1 + w); the
program holds the scale itself.  The multi-token-prediction head is not
built (serving does not use it)."""

from .base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="qwen3-next-80b-a3b",
    family="hybrid",
    num_layers=48,
    d_model=2048,
    num_heads=16,
    num_kv_heads=2,
    head_dim=256,
    d_ff=0,
    vocab_size=151936,
    block_pattern=("gdn", "gdn", "gdn", "attn"),
    moe=MoEConfig(num_experts=512, top_k=10, d_ff=512, held=512,
                  shared_d_ff=512),
    qk_norm=True,
    rope_theta=10_000_000.0,
    rotary_fraction=0.25,
    attn_gate=True,
    gdn_key_heads=16,
    gdn_value_heads=32,
    gdn_key_dim=128,
    gdn_value_dim=128,
    conv_width=4,
    activation="swiglu",
)
