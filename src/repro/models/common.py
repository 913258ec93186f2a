"""Shared LM building blocks: norms, RoPE, QAT-aware projections, init helpers,
sharding hints, chunked cross-entropy.

All large models use *stacked* per-layer parameters (leading layer axis) and
``jax.lax.scan`` over layers, keeping HLO size depth-independent — essential
for compiling the 95-layer configs in the dry-run. Clipping values follow
the ``_qa``/``_qb`` convention of ``repro.core.qat`` with ``stacked=True``
alphas of shape ``(L, 1, ..., 1)``.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.qat import QATConfig, _lsq_grad_scale, alpha_like, aq, beta_init, wq
from ..kernels import dispatch

Array = jax.Array

COMPUTE_DTYPE = jnp.bfloat16

# ---------------------------------------------------------------------------
# Sharding hints: model code names logical activation axes; the launcher
# installs a rule table mapping them to mesh axes. No-op when unset (CPU).
# ---------------------------------------------------------------------------

_RULES: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "shard_rules", default=None
)


@contextlib.contextmanager
def sharding_rules(rules: dict | None):
    token = _RULES.set(rules)
    try:
        yield
    finally:
        _RULES.reset(token)


def hint(x: Array, *logical: str | None) -> Array:
    """with_sharding_constraint if rules are installed, else identity."""
    rules = _RULES.get()
    if rules is None:
        return x
    spec = jax.sharding.PartitionSpec(
        *(rules.get(ax) if ax is not None else None for ax in logical)
    )
    mesh = rules.get("__mesh__")
    if mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec)
        )
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def winit(key, shape, fan_in=None, stacked=True, dtype=jnp.float32):
    """Truncated-normal-ish init + its stacked per-layer clipping value."""
    fan_in = fan_in if fan_in is not None else shape[-2]
    w = jax.random.normal(key, shape, dtype) * np.sqrt(1.0 / fan_in)
    return w, alpha_like(w, stacked=stacked and len(shape) > 2)


def put(params: dict, name: str, w_and_alpha):
    w, a = w_and_alpha
    params[name] = w
    params[name + "_qa"] = a


# ---------------------------------------------------------------------------
# Core ops
# ---------------------------------------------------------------------------


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale).astype(dt)


def _fused_dense_ok(p: dict, name: str, x: Array, qcfg: QATConfig,
                    act_site: str | None) -> bool:
    """Can this projection take the fused Pallas QAT-matmul path?

    Requires: both quantizers active and deterministic (paper default), an
    activation clip present, a plain 2-D weight with scalar clipping values
    (inside a scanned layer the per-layer slice is scalar), and a Pallas
    backend. Everything else falls back to the aq/wq + matmul chain.
    """
    if not (qcfg.enabled and qcfg.quantize_weights and qcfg.quantize_acts
            and qcfg.mode == "det"):
        return False
    if act_site is None or act_site not in p:
        return False
    w = p[name]
    if w.ndim != 2 or x.ndim < 2:
        return False
    if p[name + "_qa"].size != 1 or p[act_site].size != 1:
        return False
    return dispatch.use_kernels()


def dense(p: dict, name: str, x: Array, qcfg: QATConfig,
          act_site: str | None = None) -> Array:
    """QAT projection: optional activation fake-quant + weight fake-quant matmul.

    ``p[name]`` is (.., d_in, d_out); contraction over x's last dim. When
    the trainer pre-quantizes weights once per step (steps.py opt_level 1)
    ``qcfg.quantize_weights`` is False and the weight is already on the FP8
    grid in bf16 — no per-use work.

    On a Pallas backend the whole projection runs as ONE fused kernel
    (operands fake-quantized in VMEM right before the MXU, custom-VJP STE
    backward) via ``kernels.dispatch.qat_matmul`` — the quantized operands
    never round-trip through HBM.
    """
    if _fused_dense_ok(p, name, x, qcfg, act_site):
        w = p[name]
        beta = _lsq_grad_scale(
            p[act_site].astype(jnp.float32), x.size, qcfg.fmt
        )
        alpha = _lsq_grad_scale(p[name + "_qa"], w.size, qcfg.fmt)
        x2 = x.reshape(-1, x.shape[-1])
        out = dispatch.qat_matmul(
            x2.astype(jnp.float32), w.astype(jnp.float32), beta, alpha,
            qcfg.fmt,
        )
        return out.reshape(*x.shape[:-1], w.shape[-1]).astype(COMPUTE_DTYPE)
    if act_site is not None and act_site in p:
        x = aq(x, p[act_site].astype(jnp.float32), qcfg)
    w = p[name]
    if qcfg.enabled and qcfg.quantize_weights:
        w = wq(w.astype(jnp.float32), p[name + "_qa"], qcfg)
    return jnp.matmul(x.astype(COMPUTE_DTYPE), w.astype(COMPUTE_DTYPE))


def cache_dot(spec: str, a: Array, cache: Array) -> Array:
    """Einsum against a KV cache/state without upcasting the cache.

    In production lowerings (sharding rules installed => TPU target) the
    cache operand stays in its storage dtype — an explicit f32 upcast makes
    XLA hoist an f32 copy of the entire cache out of the decode loop
    (measured 2x cache memory). On the bare-CPU path (unit tests) the CPU
    runtime lacks bf16 dot thunks, so operands are upcast to f32.
    """
    if _RULES.get() is not None:
        return jnp.einsum(spec, a.astype(cache.dtype), cache,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32),
                      cache.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """Rotary embeddings. x: (..., T, H, D_head), positions: (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -np.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, half)
    ang = ang[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def activation(x: Array, kind: str) -> Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Chunked cross-entropy (bounds large-vocab logits memory)
# ---------------------------------------------------------------------------


def chunked_ce_loss(
    h: Array,            # (B, T, D) final hidden states
    head_p: dict,        # params holding 'lm_head' (+_qa) or tied 'embed'
    labels: Array,       # (B, T) int32, -1 = masked
    qcfg: QATConfig,
    n_chunks: int = 8,
    tied: bool = False,
) -> Array:
    """Mean CE over unmasked tokens, computed in T-chunks via lax.map so the
    (tokens x vocab) logits tensor never materializes whole."""
    B, T, D = h.shape
    n_chunks = min(n_chunks, T)
    while T % n_chunks:
        n_chunks -= 1
    hc = h.reshape(B, n_chunks, T // n_chunks, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, T // n_chunks).swapaxes(0, 1)

    wname = "embed" if tied else "lm_head"

    def chunk_loss(args):
        hx, lx = args
        logits = dense(head_p, wname, hx, qcfg, act_site="head_qb")
        if tied:
            pass  # tied path: dense() already contracted with embed.T upstream
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lx, 0)[..., None], axis=-1
        )[..., 0]
        mask = (lx >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * mask), jnp.sum(mask)

    losses, counts = jax.lax.map(chunk_loss, (hc, lc))
    return jnp.sum(losses) / jnp.maximum(jnp.sum(counts), 1.0)


def logits_head(h: Array, head_p: dict, qcfg: QATConfig) -> Array:
    """Full logits (decode path: single position, cheap)."""
    return dense(head_p, "lm_head", h, qcfg, act_site="head_qb").astype(jnp.float32)
