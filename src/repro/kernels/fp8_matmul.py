"""Pallas TPU kernel: fused FP8-QAT matmul.

The TPU-native analogue of H100 FP8 tensor-core GEMMs (DESIGN.md §3): both
operand tiles are fake-quantized onto the FP8 grid *in VMEM* immediately
before feeding the MXU, and the product accumulates in f32. The quantized
operands never round-trip to HBM — vs. the naive "quantize whole tensor,
then matmul" graph this removes one full read+write of both operands.

Blocking: (bm x bk) @ (bk x bn) with all three dims multiples of 128 to
match the 128x128 MXU systolic array; the K grid axis is innermost and
accumulates into the revisited output tile (standard Pallas matmul
pattern). Default tiles use ~(256+256+128)KB of VMEM, leaving room for
double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.fp8 import _ALPHA_FLOOR, E4M3, FP8Format
from .fp8_quant import _mant_const, _ste_terms, pad_to_blocks


def _fake_quant(x, alpha, fmt: FP8Format):
    b = 2.0 ** fmt.exp - jnp.log2(alpha) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -alpha, alpha)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    return s * jnp.round(xc / s)


def _qat_matmul_kernel(x_ref, w_ref, beta_ref, alpha_ref, o_ref, *, fmt, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = _fake_quant(x_ref[...].astype(jnp.float32), beta_ref[0, 0], fmt)
    wq = _fake_quant(w_ref[...].astype(jnp.float32), alpha_ref[0, 0], fmt)
    o_ref[...] += jnp.dot(
        xq, wq, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("fmt", "bm", "bk", "bn", "interpret")
)
def qat_matmul(
    x: jax.Array,       # (M, K)
    w: jax.Array,       # (K, N)
    beta: jax.Array,    # activation clip (scalar)
    alpha: jax.Array,   # weight clip (scalar)
    fmt: FP8Format = E4M3,
    bm: int = 256,
    bk: int = 512,
    bn: int = 256,
    interpret: bool = True,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    # zero-pad the contraction axis: out-of-bounds K tiles would otherwise
    # accumulate garbage into in-bounds output rows (see pad_to_blocks)
    xp = pad_to_blocks(x, bm, bk)
    wp = pad_to_blocks(w, bk, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)
    scalar = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    out = pl.pallas_call(
        functools.partial(_qat_matmul_kernel, fmt=fmt, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            scalar,
            scalar,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(xp, wp, jnp.reshape(jnp.maximum(beta.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)),
      jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)))
    return out[:m, :n].astype(x.dtype)


# ---------------------------------------------------------------------------
# Fused backward kernels. With out = Qdet(x; beta) @ Qdet(w; alpha):
#
#   d/dxq = g @ wq^T            d/dwq = xq^T @ g
#   dx     = d/dxq * 1{|x|<=beta}                       (STE clip mask)
#   dbeta  = sum d/dxq * [sign(x) 1{|x|>beta} + (q-y) s / beta]
#   dw, dalpha symmetrically.
#
# Both kernels RE-quantize the saved FP32 operand tile in VMEM (cheaper than
# round-tripping the quantized copies through HBM) and accumulate the matmul
# over the contraction grid axis into the revisited output tile; the mask /
# clip-routing epilogue runs once, on the last contraction step. The scalar
# clip-value cotangent accumulates across the whole grid into a revisited
# (1, 1) SMEM block (Mosaic stores scalars only to SMEM; sequential grid =>
# race-free; cheap partial reduction).
# ---------------------------------------------------------------------------


def _qat_matmul_dx_kernel(g_ref, w_ref, x_ref, beta_ref, alpha_ref,
                          gx_ref, gb_ref, *, fmt, n_j):
    i, k, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init_gx():
        gx_ref[...] = jnp.zeros_like(gx_ref)

    @pl.when((i == 0) & (k == 0) & (j == 0))
    def _init_gb():
        gb_ref[0, 0] = jnp.float32(0.0)

    wq = _fake_quant(w_ref[...].astype(jnp.float32), alpha_ref[0, 0], fmt)
    g = g_ref[...].astype(jnp.float32)
    gx_ref[...] += jnp.dot(g, wq.T, preferred_element_type=jnp.float32)

    @pl.when(j == n_j - 1)
    def _epilogue():
        x = x_ref[...].astype(jnp.float32)
        beta = beta_ref[0, 0]
        inside, s, y = _ste_terms(x, beta, fmt)
        q = jnp.round(y)
        gxq = gx_ref[...]
        gb_ref[0, 0] += jnp.sum(
            gxq * (jnp.sign(x) * (1.0 - inside) + (q - y) * s / beta)
        )
        gx_ref[...] = gxq * inside


def _qat_matmul_dw_kernel(g_ref, x_ref, w_ref, beta_ref, alpha_ref,
                          gw_ref, ga_ref, *, fmt, n_i):
    k, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init_gw():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    @pl.when((k == 0) & (j == 0) & (i == 0))
    def _init_ga():
        ga_ref[0, 0] = jnp.float32(0.0)

    xq = _fake_quant(x_ref[...].astype(jnp.float32), beta_ref[0, 0], fmt)
    g = g_ref[...].astype(jnp.float32)
    gw_ref[...] += jnp.dot(xq.T, g, preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _epilogue():
        w = w_ref[...].astype(jnp.float32)
        alpha = alpha_ref[0, 0]
        inside, s, y = _ste_terms(w, alpha, fmt)
        q = jnp.round(y)
        gwq = gw_ref[...]
        ga_ref[0, 0] += jnp.sum(
            gwq * (jnp.sign(w) * (1.0 - inside) + (q - y) * s / alpha)
        )
        gw_ref[...] = gwq * inside


@functools.partial(
    jax.jit, static_argnames=("fmt", "bm", "bk", "bn", "interpret")
)
def qat_matmul_dx(
    g: jax.Array,       # (M, N) upstream cotangent
    x: jax.Array,       # (M, K) forward activation input
    w: jax.Array,       # (K, N) forward weight input
    beta: jax.Array,
    alpha: jax.Array,
    fmt: FP8Format = E4M3,
    bm: int = 256,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Backward wrt activations: ``(dL/dx, dL/dbeta)``."""
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    gp = pad_to_blocks(g.astype(jnp.float32), bm, bn)
    wp = pad_to_blocks(w.astype(jnp.float32), bk, bn)
    xp = pad_to_blocks(x.astype(jnp.float32), bm, bk)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, kp // bk, np_ // bn)
    scalar = pl.BlockSpec((1, 1), lambda i, kk, j: (0, 0))
    gx, gb = pl.pallas_call(
        functools.partial(_qat_matmul_dx_kernel, fmt=fmt, n_j=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),    # g
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),   # w
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),   # x
            scalar,
            scalar,
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((1, 1), lambda i, kk, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(gp, wp, xp, jnp.reshape(jnp.maximum(beta.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)),
      jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)))
    return gx[:m, :k].astype(x.dtype), gb.reshape(jnp.shape(beta))


@functools.partial(
    jax.jit, static_argnames=("fmt", "bm", "bk", "bn", "interpret")
)
def qat_matmul_dw(
    g: jax.Array,       # (M, N) upstream cotangent
    x: jax.Array,       # (M, K)
    w: jax.Array,       # (K, N)
    beta: jax.Array,
    alpha: jax.Array,
    fmt: FP8Format = E4M3,
    bm: int = 256,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Backward wrt weights: ``(dL/dw, dL/dalpha)``."""
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    gp = pad_to_blocks(g.astype(jnp.float32), bm, bn)
    xp = pad_to_blocks(x.astype(jnp.float32), bm, bk)
    wp = pad_to_blocks(w.astype(jnp.float32), bk, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (kp // bk, np_ // bn, mp // bm)
    scalar = pl.BlockSpec((1, 1), lambda kk, j, i: (0, 0))
    gw, ga = pl.pallas_call(
        functools.partial(_qat_matmul_dw_kernel, fmt=fmt, n_i=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda kk, j, i: (i, j)),    # g
            pl.BlockSpec((bm, bk), lambda kk, j, i: (i, kk)),   # x
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),   # w
            scalar,
            scalar,
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
            pl.BlockSpec((1, 1), lambda kk, j, i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(gp, xp, wp, jnp.reshape(jnp.maximum(beta.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)),
      jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)))
    return gw[:k, :n].astype(w.dtype), ga.reshape(jnp.shape(alpha))
