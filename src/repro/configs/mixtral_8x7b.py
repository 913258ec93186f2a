"""Mixtral-8x7B — 8-expert top-2 MoE with sliding-window attention
[arXiv:2401.04088; hf]."""
from .base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="mixtral-8x7b",
    family="moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    vocab=32000,
    head_dim=128,
    attention="swa",
    window=4096,
    moe=MoEConfig(n_experts=8, top_k=2),
    rope_theta=1000000.0,
    act="silu",
    subquadratic=True,   # SWA => bounded decode cache
)
