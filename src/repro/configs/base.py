"""Architecture/config system.

One :class:`ModelConfig` per assigned architecture lives in
``repro/configs/<id>.py``; ``repro.configs.get(name)`` resolves them.
``reduced()`` produces the CPU-smoke-test version of any config (same
family/topology, tiny dims).
"""
from __future__ import annotations

import dataclasses
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts. The router always scores all ``n_experts``; a
    model holds the ``experts_here`` of them from ``first_expert`` on (all
    when None), as one chip of an expert-parallel deployment does, and
    computes their part of each layer's result."""

    n_experts: int = 8
    top_k: int = 2
    experts_here: int | None = None
    first_expert: int = 0

    @property
    def held(self) -> int:
        return self.n_experts if self.experts_here is None \
            else self.experts_here


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int | None = None     # default: d_model
    conv_width: int = 4
    block_pattern: tuple[str, ...] = ("rec", "rec", "attn")
    c: float = 8.0                   # RG-LRU decay sharpness


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None

    # attention flavour
    attention: str = "full"          # full | swa | local | mla | none
    window: int = 0                  # swa/local window
    rope_theta: float = 10000.0

    mla: MLAConfig | None = None
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    rglru: RGLRUConfig | None = None

    # enc-dec (whisper)
    n_encoder_layers: int = 0
    encoder_len: int = 1500          # stub audio frontend frames

    # vlm
    n_patches: int = 0               # stub patch-embedding frontend length

    norm_eps: float = 1e-6
    act: str = "silu"
    tie_embeddings: bool = False
    # attention kv-chunk for flash-style scan; also CE token chunking
    attn_chunk: int = 1024
    ce_chunks: int = 8
    remat: bool = True
    # sub-quadratic decode at 500k context?
    subquadratic: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    def param_count(self) -> int:
        """Approximate (exact for dense) parameter count, for roofline math."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        H, KV, hd = self.n_heads, self.n_kv_heads, self.hd
        emb = V * D * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            s = self.ssm
            d_in = s.expand * D
            per = D * (2 * d_in + 2 * s.n_groups * s.d_state + d_in // s.head_dim) \
                + d_in * D + 3 * (d_in // s.head_dim)
            return emb + L * per
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        if self.attention == "mla":
            m = self.mla
            attn = (D * m.q_lora_rank
                    + m.q_lora_rank * H * (m.qk_nope_dim + m.qk_rope_dim)
                    + D * (m.kv_lora_rank + m.qk_rope_dim)
                    + m.kv_lora_rank * H * (m.qk_nope_dim + m.v_head_dim)
                    + H * m.v_head_dim * D)
        ffn = 3 * D * F
        if self.moe:
            ffn = self.moe.held * 3 * D * F + D * self.moe.n_experts
        per = attn + ffn
        if self.family == "hybrid":
            # recurrent layers replace attention with RG-LRU machinery
            pass
        total = emb + L * per
        if self.n_encoder_layers:
            total += self.n_encoder_layers * (attn + ffn)
        return total

    def active_param_count(self) -> int:
        """Per-token active params (MoE: the top_k experts, of which the
        held share on average) for 6*N*D FLOPs."""
        if not self.moe:
            return self.param_count()
        m, D, F, L = self.moe, self.d_model, self.d_ff, self.n_layers
        idle = m.held - m.top_k * m.held / m.n_experts
        return int(self.param_count() - L * idle * 3 * D * F)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    kw: dict = dict(
        n_layers=min(cfg.n_layers, 3 if cfg.family != "hybrid" else 3),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_ff=128,
        vocab=256,
        head_dim=16,
        attn_chunk=32,
        ce_chunks=2,
        window=min(cfg.window, 32) if cfg.window else 0,
        encoder_len=16 if cfg.n_encoder_layers else cfg.encoder_len,
        n_encoder_layers=min(cfg.n_encoder_layers, 2),
        n_patches=8 if cfg.n_patches else 0,
        remat=False,
    )
    if cfg.mla:
        kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                              qk_rope_dim=8, v_head_dim=16)
    if cfg.moe:
        kw["moe"] = MoEConfig(n_experts=min(cfg.moe.n_experts, 4),
                              top_k=min(cfg.moe.top_k, 2))
    if cfg.ssm:
        kw["ssm"] = SSMConfig(d_state=16, expand=2, head_dim=16, chunk=16)
    if cfg.rglru:
        kw["rglru"] = RGLRUConfig(lru_width=64, block_pattern=cfg.rglru.block_pattern)
    return cfg.replace(**kw)
