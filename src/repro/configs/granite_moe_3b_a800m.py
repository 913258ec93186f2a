"""Granite-3.0-3B-A800M — 40 experts of width 512, top-8, GQA 24/8 heads
of 64 [https://huggingface.co/ibm-granite/granite-3.0-3b-a800m-base,
model type ``granitemoe``]."""
from .base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    d_ff=512,
    vocab=49408,  # 49155 padded to /256 for TP (std TPU vocab padding)
    head_dim=64,
    attention="full",
    moe=MoEConfig(n_experts=40, top_k=8),
    rope_theta=10000.0,
    act="silu",
)
