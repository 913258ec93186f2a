"""Decoder-only LM: dense GQA / sliding-window / MLA / VLM-frontend variants.

Structure: stacked per-layer parameters + ``lax.scan`` over layers (HLO size
independent of depth, per-layer remat policy), flash-chunked attention,
chunked cross-entropy. The same block code serves train (full sequence),
prefill (returns KV cache) and decode (one token against the cache) — the
``mode`` argument selects the attention path.

QAT: every projection goes through ``common.dense`` which applies the
paper's deterministic FP8 fake-quant to weights (per layer-tensor alpha)
and input activations (per layer-site beta).

MLA (minicpm3): prefill/train decompress the latent KV; decode uses the
absorbed form — scores against the (kv_lora + rope) latent cache directly,
so the per-token cost is O(S * (kv_lora + d_rope)) instead of
O(S * H * head_dim).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.qat import QATConfig, alpha_like, beta_init
from . import moe as moe_lib
from .attention import decode_attention, flash_attention, local_block_attention
from .common import (
    COMPUTE_DTYPE,
    activation,
    chunked_ce_loss,
    dense,
    hint,
    logits_head,
    put,
    rms_norm,
    rope,
    winit,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig, L: int) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p: dict = {}
    ks = jax.random.split(key, 8)
    if cfg.attention == "mla":
        m = cfg.mla
        put(p, "wq_a", winit(ks[0], (L, D, m.q_lora_rank)))
        put(p, "wq_b", winit(ks[1], (L, m.q_lora_rank, H * (m.qk_nope_dim + m.qk_rope_dim)),
                             fan_in=m.q_lora_rank))
        put(p, "wkv_a", winit(ks[2], (L, D, m.kv_lora_rank + m.qk_rope_dim)))
        put(p, "wkv_b", winit(ks[3], (L, m.kv_lora_rank, H * (m.qk_nope_dim + m.v_head_dim)),
                              fan_in=m.kv_lora_rank))
        put(p, "wo", winit(ks[4], (L, H * m.v_head_dim, D), fan_in=H * m.v_head_dim))
    else:
        put(p, "wq", winit(ks[0], (L, D, H * hd)))
        put(p, "wk", winit(ks[1], (L, D, KV * hd)))
        put(p, "wv", winit(ks[2], (L, D, KV * hd)))
        put(p, "wo", winit(ks[3], (L, H * hd, D), fan_in=H * hd))
    p["attn_qb"] = beta_init(stacked_layers=L)
    p["o_qb"] = beta_init(stacked_layers=L)
    return p


def _init_ffn(key, cfg: ModelConfig, L: int) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    p: dict = {}
    ks = jax.random.split(key, 4)
    if cfg.moe:
        # the router scores every expert; the weights are the held share's,
        # each with its own clip (L, E_here, 1, 1)
        E, Eh = cfg.moe.n_experts, cfg.moe.held
        p["router"] = jax.random.normal(ks[0], (L, D, E), jnp.float32) * 0.02
        put(p, "we_gate", winit(ks[1], (L, Eh, D, F), fan_in=D, lead=2))
        put(p, "we_up", winit(ks[2], (L, Eh, D, F), fan_in=D, lead=2))
        put(p, "we_down", winit(ks[3], (L, Eh, F, D), fan_in=F, lead=2))
    else:
        put(p, "w_gate", winit(ks[0], (L, D, F)))
        put(p, "w_up", winit(ks[1], (L, D, F)))
        put(p, "w_down", winit(ks[2], (L, F, D), fan_in=F))
    p["mlp_qb"] = beta_init(stacked_layers=L)
    p["down_qb"] = beta_init(stacked_layers=L)
    return p


def init_lm(key: Array, cfg: ModelConfig) -> dict:
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab
    k = jax.random.split(key, 6)
    blocks = {
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
        **_init_attn(k[0], cfg, L),
        **_init_ffn(k[1], cfg, L),
    }
    embed = jax.random.normal(k[2], (V, D), jnp.float32) * 0.02
    head, head_qa = winit(k[3], (D, V), fan_in=D, stacked=False)
    params = {
        "embed": embed,
        "embed_qa": alpha_like(embed),
        "blocks": blocks,
        "ln_f": jnp.ones((D,), jnp.float32),
        "lm_head": head,
        "lm_head_qa": head_qa,
        "head_qb": beta_init(),
    }
    return params


# ---------------------------------------------------------------------------
# Attention sub-blocks (full-sequence and decode paths)
# ---------------------------------------------------------------------------


def _attn_full_seq(p, x, cfg: ModelConfig, qcfg, positions) -> tuple[Array, dict]:
    """Train/prefill attention. Returns (out, cache_entry)."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if cfg.attention == "mla":
        m = cfg.mla
        q = dense(p, "wq_a", x, qcfg, "attn_qb")
        q = dense(p, "wq_b", q, qcfg).reshape(B, T, H, m.qk_nope_dim + m.qk_rope_dim)
        q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
        q_rope = rope(q_rope, positions, cfg.rope_theta)
        kv_a = dense(p, "wkv_a", x, qcfg, "attn_qb")
        latent, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
        k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,T,1,dr)
        kv = dense(p, "wkv_b", latent, qcfg).reshape(
            B, T, H, m.qk_nope_dim + m.v_head_dim
        )
        k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, T, H, m.qk_rope_dim))], axis=-1
        )
        out = flash_attention(q_full, k_full, v, causal=True, chunk=cfg.attn_chunk)
        out = dense(p, "wo", out.reshape(B, T, H * m.v_head_dim), qcfg, "o_qb")
        cache = {"latent": jnp.concatenate(
            [latent, k_rope[:, :, 0, :]], axis=-1).astype(COMPUTE_DTYPE)}
        return out, cache

    q = dense(p, "wq", x, qcfg, "attn_qb").reshape(B, T, H, hd)
    kk = dense(p, "wk", x, qcfg, "attn_qb").reshape(B, T, KV, hd)
    v = dense(p, "wv", x, qcfg, "attn_qb").reshape(B, T, KV, hd)
    q = rope(q, positions, cfg.rope_theta)
    kk = rope(kk, positions, cfg.rope_theta)
    if cfg.attention in ("swa", "local") and cfg.window and T > cfg.window:
        out = local_block_attention(q, kk, v, window=cfg.window)
    else:
        out = flash_attention(
            q, kk, v, causal=True,
            window=cfg.window if cfg.attention in ("swa", "local") else 0,
            chunk=cfg.attn_chunk,
        )
    out = dense(p, "wo", out.reshape(B, T, H * hd), qcfg, "o_qb")
    cache = {"k": kk.astype(COMPUTE_DTYPE), "v": v.astype(COMPUTE_DTYPE)}
    return out, cache


def _attn_decode(p, x, cfg: ModelConfig, qcfg, cache_entry, pos) -> tuple[Array, dict]:
    """One-token attention. ``pos`` is a scalar absolute position."""
    B, T, D = x.shape  # T == 1
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    positions = jnp.full((B, 1), pos, jnp.int32)
    if cfg.attention == "mla":
        m = cfg.mla
        q = dense(p, "wq_a", x, qcfg, "attn_qb")
        q = dense(p, "wq_b", q, qcfg).reshape(B, 1, H, m.qk_nope_dim + m.qk_rope_dim)
        q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
        q_rope = rope(q_rope, positions, cfg.rope_theta)
        kv_a = dense(p, "wkv_a", x, qcfg, "attn_qb")
        latent_new, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
        k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        new_entry = jnp.concatenate([latent_new, k_rope], axis=-1).astype(COMPUTE_DTYPE)
        lat_cache = jax.lax.dynamic_update_slice(
            cache_entry["latent"], new_entry, (0, pos, 0)
        )
        # absorbed attention: fold wkv_b into the query side
        wkv_b = p["wkv_b"].astype(COMPUTE_DTYPE)  # (r, H*(dn+dv))
        wkv_b = wkv_b.reshape(m.kv_lora_rank, H, m.qk_nope_dim + m.v_head_dim)
        w_k = wkv_b[..., : m.qk_nope_dim]   # (r, H, dn)
        w_v = wkv_b[..., m.qk_nope_dim:]    # (r, H, dv)
        q_abs = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32),
                           w_k.astype(jnp.float32))
        # cache operands stay bf16 (avoid a hoisted f32 cache copy); f32
        # accumulation via preferred_element_type
        lat = lat_cache[..., : m.kv_lora_rank]
        rop = lat_cache[..., m.kv_lora_rank:]
        scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
        from .common import cache_dot
        s = (
            cache_dot("bthr,bsr->bhts", q_abs, lat)
            + cache_dot("bthd,bsd->bhts", q_rope.astype(jnp.float32), rop)
        ) * scale
        S = lat_cache.shape[1]
        valid = jnp.arange(S)[None, :] <= pos
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = cache_dot("bhts,bsr->bthr", pr, lat)
        out = jnp.einsum("bthr,rhd->bthd", o_lat, w_v.astype(jnp.float32))
        out = dense(p, "wo", out.reshape(B, 1, H * m.v_head_dim).astype(COMPUTE_DTYPE),
                    qcfg, "o_qb")
        return out, {"latent": lat_cache}

    q = dense(p, "wq", x, qcfg, "attn_qb").reshape(B, 1, H, hd)
    kk = dense(p, "wk", x, qcfg, "attn_qb").reshape(B, 1, KV, hd)
    v = dense(p, "wv", x, qcfg, "attn_qb").reshape(B, 1, KV, hd)
    q = rope(q, positions, cfg.rope_theta)
    kk = rope(kk, positions, cfg.rope_theta)
    ring = cfg.attention in ("swa", "local") and cfg.window
    S = cache_entry["k"].shape[1]
    write_pos = (pos % cfg.window) if ring else pos
    k_cache = jax.lax.dynamic_update_slice(
        cache_entry["k"], kk.astype(COMPUTE_DTYPE), (0, write_pos, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        cache_entry["v"], v.astype(COMPUTE_DTYPE), (0, write_pos, 0, 0)
    )
    if ring:
        # ring buffer of size window: slot i holds absolute position
        # p_i = largest p <= pos with p % W == i; everything present is valid
        slots = jnp.arange(S)
        kpos = pos - ((pos - slots) % cfg.window)
        valid = (kpos >= 0) & (kpos <= pos)
        pos_b = jnp.broadcast_to(pos, (B,))
        from .common import cache_dot
        qg = q.reshape(B, 1, KV, H // KV, hd).astype(jnp.float32) \
            * (1.0 / np.sqrt(hd))
        s = cache_dot("btkgd,bskd->bkgts", qg, k_cache)
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        out = cache_dot("bkgts,bskd->btkgd", pr, v_cache)
        out = out.reshape(B, 1, H, hd).astype(x.dtype)
    else:
        out = decode_attention(q, k_cache, v_cache, jnp.broadcast_to(pos, (B,)))
    out = dense(p, "wo", out.reshape(B, 1, H * hd), qcfg, "o_qb")
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def _ffn(p, x, cfg: ModelConfig, qcfg) -> Array:
    if cfg.moe:
        return moe_lib.moe_ffn(p, x, cfg, qcfg)
    g = dense(p, "w_gate", x, qcfg, "mlp_qb")
    u = dense(p, "w_up", x, qcfg, "mlp_qb")
    return dense(p, "w_down", activation(g, cfg.act) * u, qcfg, "down_qb")


# ---------------------------------------------------------------------------
# Block + full model
# ---------------------------------------------------------------------------


def _block_full(h, layer_p, cfg: ModelConfig, qcfg, positions):
    x = rms_norm(h, layer_p["ln1"], cfg.norm_eps)
    attn_out, cache = _attn_full_seq(layer_p, x, cfg, qcfg, positions)
    h = h + attn_out
    h = hint(h, "batch", "seq", None)
    x = rms_norm(h, layer_p["ln2"], cfg.norm_eps)
    h = h + _ffn(layer_p, x, cfg, qcfg)
    h = hint(h, "batch", "seq", None)
    return h, cache


def _block_decode(h, layer_p, cache_entry, cfg: ModelConfig, qcfg, pos):
    x = rms_norm(h, layer_p["ln1"], cfg.norm_eps)
    attn_out, new_cache = _attn_decode(layer_p, x, cfg, qcfg, cache_entry, pos)
    h = h + attn_out
    x = rms_norm(h, layer_p["ln2"], cfg.norm_eps)
    h = h + _ffn(layer_p, x, cfg, qcfg)
    return h, new_cache


def _embed_inputs(params, tokens, cfg: ModelConfig, qcfg, patches=None):
    emb = params["embed"].astype(COMPUTE_DTYPE)
    h = emb[tokens]
    if cfg.n_patches and patches is not None:
        h = jnp.concatenate([patches.astype(COMPUTE_DTYPE), h], axis=1)
    return h


def forward_hidden(params, tokens, cfg: ModelConfig, qcfg: QATConfig,
                   patches=None) -> Array:
    """(B, T, D) hidden states after the final norm (train/prefill path)."""
    h = _embed_inputs(params, tokens, cfg, qcfg, patches)
    h = hint(h, "batch", "seq", None)
    B, T, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    def body(h, layer_p):
        return _block_full(h, layer_p, cfg, qcfg, positions)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, params["blocks"])
    return rms_norm(h, params["ln_f"], cfg.norm_eps)


def train_loss(params, batch: dict, cfg: ModelConfig, qcfg: QATConfig) -> Array:
    """batch: {'tokens': (B,T), 'labels': (B,T), ['patches': (B,P,D)]}"""
    patches = batch.get("patches")
    h = forward_hidden(params, batch["tokens"], cfg, qcfg, patches)
    labels = batch["labels"]
    if cfg.n_patches and patches is not None:
        pad = jnp.full(
            (labels.shape[0], patches.shape[1]), -1, labels.dtype
        )
        labels = jnp.concatenate([pad, labels], axis=1)
    return chunked_ce_loss(h, params, labels, qcfg, cfg.ce_chunks)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    L = cfg.n_layers
    if cfg.attention == "mla":
        m = cfg.mla
        lat = jnp.zeros(
            (L, batch, seq_len, m.kv_lora_rank + m.qk_rope_dim), COMPUTE_DTYPE
        )
        return {"latent": lat}
    S = min(seq_len, cfg.window) if cfg.attention in ("swa", "local") and cfg.window \
        else seq_len
    kv = (L, batch, S, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(kv, COMPUTE_DTYPE), "v": jnp.zeros(kv, COMPUTE_DTYPE)}


def prefill(params, tokens, cfg: ModelConfig, qcfg: QATConfig, patches=None):
    """Run the prompt; return (last-position logits, cache)."""
    h = _embed_inputs(params, tokens, cfg, qcfg, patches)
    B, T, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    def body(h, layer_p):
        h, cache = _block_full(h, layer_p, cfg, qcfg, positions)
        if cfg.attention in ("swa", "local") and cfg.window and T > cfg.window:
            cache = {k: v[:, -cfg.window:] for k, v in cache.items()}
        return h, cache

    h, cache = jax.lax.scan(body, h, params["blocks"])
    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    logits = logits_head(h[:, -1:], params, qcfg)[:, 0]
    return logits, cache


def decode_step(params, cache, token, pos, cfg: ModelConfig, qcfg: QATConfig):
    """One decode step. token: (B,), pos: scalar int32 absolute position."""
    emb = params["embed"].astype(COMPUTE_DTYPE)
    h = emb[token][:, None, :]  # (B,1,D)
    h = hint(h, "batch", None, None)

    def body(h, xs):
        layer_p, cache_entry = xs
        h, new_entry = _block_decode(h, layer_p, cache_entry, cfg, qcfg, pos)
        return h, new_entry

    h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    logits = logits_head(h, params, qcfg)[:, 0]
    return logits, new_cache
