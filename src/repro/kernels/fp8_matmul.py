"""Pallas TPU kernel: fused FP8-QAT matmul.

The TPU-native analogue of H100 FP8 tensor-core GEMMs (DESIGN.md §3): both
operand tiles are fake-quantized onto the FP8 grid *in VMEM* immediately
before feeding the MXU, and the product accumulates in f32. The quantized
operands never round-trip to HBM — vs. the naive "quantize whole tensor,
then matmul" graph this removes one full read+write of both operands.

What the MXU is fed. A grid value is ``s * q`` with ``q = round(xc / s)``
and ``s = 2^(p - b - mant)``; the bias ``b`` holds ``log2(alpha)``, so ``s``
carries alpha's full f32 mantissa and ``s * q`` is not a bf16 value. The
kernels factor the scale out (:func:`_codes`): ``s * q = sigma * c`` with
the integer-valued code ``c = q * 2^(p - p_top)`` (at most ``mant + 2``
significant bits, exponent in ``[1 - p_top, 0]``: exact in bf16) and one
scalar ``sigma = 2^(p_top - b - mant)`` per operand, ``p_top = 2^exp - 1``
the top of the format's exponent range. The MXU multiplies codes in ONE
bf16 pass (each product is exact in f32, the accumulation is f32) and the
accumulated tile is scaled by the operands' sigmas once. The rounding
decisions ``q`` are the kernels' as before; only the dequantized value's
representation moves, by ``exp2``'s own last-bit differences between
arguments an integer apart (~2^-20 relative, 2^-19 at the clip floor).
The backward kernels feed the cotangent ``g`` to the MXU in its own
dtype: bf16 (every LM here) beside bf16 codes, and f32 beside f32 codes
when ``g`` is f32 (:func:`mxu_dtype`), the f32 dot it had before.

Blocking: (bm x bk) @ (bk x bn) with all three dims multiples of 128 to
match the 128x128 MXU systolic array; the K grid axis is innermost and
accumulates into the revisited output tile (standard Pallas matmul
pattern). Operands enter in their own dtype and are cast in VMEM. The
operand tiles are re-quantized on every grid step, which bounds the
kernels, so the default blocks are large (``_BLOCKS``).
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


# The codes' dtype: every code is exact in it.
CODE_DTYPE = jnp.dtype(jnp.bfloat16)


def _code_exp_top(fmt: FP8Format) -> int:
    """``p_top``: the largest exponent code ``p`` of ``fmt``'s grid."""
    return 2 ** fmt.exp - 1


def _code_bias(alpha, fmt: FP8Format):
    """The exponent bias ``b`` of ``core.fp8.exponent_bias``, as the
    kernels compute it."""
    return 2.0 ** fmt.exp - jnp.log2(alpha) + _mant_const(fmt) - 1.0


def _code_scale(alpha, fmt: FP8Format):
    """``sigma = 2^(p_top - b - mant)``: the grid value of code 1."""
    return jnp.exp2(_code_exp_top(fmt) - _code_bias(alpha, fmt) - fmt.mant)


def _pow2_from(p, top: int):
    """Exact ``2^(p - top)`` for integer-valued f32 ``p`` in [top - 126,
    top], from exponent bits (no transcendental, so no rounding): ``p + (2^23
    + 127 - top)`` is exact and holds ``p + 127 - top`` in its low mantissa
    bits, which the shift moves into the exponent field. Two VPU ops."""
    bits = jax.lax.bitcast_convert_type(p + float(2 ** 23 + 127 - top),
                                        jnp.int32)
    return jax.lax.bitcast_convert_type(bits << 23, jnp.float32)


def _codes(x, alpha, fmt: FP8Format):
    """FP8 fake-quant of f32 ``x`` as integer-valued codes ``c``, exact in
    bf16: ``sigma * c`` is ``s * round(xc / s)`` up to ``exp2``'s last bits,
    with ``sigma = _code_scale(alpha, fmt)``."""
    b = _code_bias(alpha, fmt)
    xc = jnp.clip(x, -alpha, alpha)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    return jnp.round(xc / s) * _pow2_from(p, _code_exp_top(fmt))


def mxu_dtype(g_dtype) -> np.dtype:
    """The dtype the backward kernels feed the MXU beside cotangent
    ``g``: bf16 when ``g`` is bf16 (the codes are exact in bf16), else f32.
    The forward always feeds bf16 codes."""
    return CODE_DTYPE if jnp.dtype(g_dtype) == CODE_DTYPE \
        else jnp.dtype(jnp.float32)


# Default blocks (bm, bk, bn) of each kernel, by the itemsize of its
# activation and cotangent operands. Every kernel re-quantizes one or both
# operand tiles on each grid step, on the VPU, and that work, not the MXU,
# bounds it: the more MXU work a step amortizes it over, the faster. The
# 2-byte rows are the fastest blocks measured on a v5e, at (4096, 4096,
# 12800), whose temporaries fit the kernel's VMEM (PERF.md). The 4-byte
# rows only shorten M until the compiler fits them in VMEM; they were not
# timed, since no workload on the chip hands the kernels f32.
_BLOCKS = {
    "qat_matmul": {2: (1024, 512, 1024), 4: (512, 512, 1024)},
    "qat_matmul_dx": {2: (1024, 256, 512), 4: (512, 256, 512)},
    "qat_matmul_dw": {2: (4096, 128, 512), 4: (1024, 128, 512)},
}


def _fit(block: int, dim: int) -> int:
    """The block for an axis of length ``dim``, given the default
    ``block`` (a multiple of 128): the whole axis if it is no longer, else
    the largest multiple of 128 up to ``block`` that pads the axis to
    within ``dim / 16`` of its shortest 128-multiple (6176 columns take
    896, padded to 6272, not 1024, padded to 7168)."""
    if dim <= block:
        return dim
    least = -(-dim // 128) * 128
    return next(b for b in range(block, 0, -128)
                if -(-dim // b) * b <= least + dim // 16)


def _blocks(kernel: str, dims, blocks, *operands) -> tuple[int, int, int]:
    """(bm, bk, bn) for an (m, k) @ (k, n) product: each given block (none
    longer than its axis), else the kernel's default for the widest of
    ``operands``, fitted to its axis (:func:`_fit`)."""
    wide = max(jnp.dtype(o.dtype).itemsize for o in operands) > 2
    default = _BLOCKS[kernel][4 if wide else 2]
    return tuple(_fit(d, dim) if b is None else min(b, dim)
                 for b, d, dim in zip(blocks, default, dims))


def _clip_scalar(c):
    return jnp.reshape(jnp.maximum(c.astype(jnp.float32), _ALPHA_FLOOR), (1, 1))


def _qat_matmul_kernel(x_ref, w_ref, beta_ref, alpha_ref, o_ref, *, fmt, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    beta, alpha = beta_ref[0, 0], alpha_ref[0, 0]
    cx = _codes(x_ref[...].astype(jnp.float32), beta, fmt)
    cw = _codes(w_ref[...].astype(jnp.float32), alpha, fmt)
    o_ref[...] += jnp.dot(cx.astype(CODE_DTYPE), cw.astype(CODE_DTYPE),
                          preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _scale():
        o_ref[...] *= _code_scale(beta, fmt) * _code_scale(alpha, fmt)


@functools.partial(
    jax.jit, static_argnames=("fmt", "bm", "bk", "bn", "interpret")
)
def qat_matmul(
    x: jax.Array,       # (M, K)
    w: jax.Array,       # (K, N)
    beta: jax.Array,    # activation clip (scalar)
    alpha: jax.Array,   # weight clip (scalar)
    fmt: FP8Format = E4M3,
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bk, bn = _blocks("qat_matmul", (m, k, n), (bm, bk, bn), x)
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
        name="qat_matmul",
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            scalar,
            scalar,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(xp, wp, _clip_scalar(beta), _clip_scalar(alpha))
    return out[:m, :n].astype(x.dtype)


# ---------------------------------------------------------------------------
# Fused backward kernels. With out = Qdet(x; beta) @ Qdet(w; alpha):
#
#   d/dxq = g @ wq^T            d/dwq = xq^T @ g
#   dx     = d/dxq * 1{|x|<=beta}                       (STE clip mask)
#   dbeta  = sum d/dxq * [sign(x) 1{|x|>beta} + (q-y) s / beta]
#   dw, dalpha symmetrically.
#
# Both kernels RE-quantize the saved operand tile in VMEM (cheaper than
# round-tripping the quantized copies through HBM) to its codes, feed the
# MXU ``g`` and the codes in ``mxu_dtype(g.dtype)`` (contracting over the
# shared axis with ``dot_general``, no transposed copy), and accumulate over
# the contraction grid axis into the revisited output tile; the epilogue
# scales by the operand's sigma and runs the mask / clip-routing terms
# once, on the last contraction step. The scalar clip-value cotangent
# accumulates across the whole grid into a revisited (1, 1) SMEM block
# (Mosaic stores scalars only to SMEM; sequential grid => race-free; cheap
# partial reduction).
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b


def _ste_epilogue(acc, v, clip, fmt):
    """(cotangent of v, partial clip cotangent) from the accumulated
    cotangent ``acc`` of v's grid value: the STE clip mask and routing."""
    inside, s, y = _ste_terms(v, clip, fmt)
    q = jnp.round(y)
    partial = jnp.sum(
        acc * (jnp.sign(v) * (1.0 - inside) + (q - y) * s / clip)
    )
    return acc * inside, partial


def _qat_matmul_dx_kernel(g_ref, w_ref, x_ref, beta_ref, alpha_ref,
                          gx_ref, gb_ref, *, fmt, n_j, mxu):
    i, k, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init_gx():
        gx_ref[...] = jnp.zeros_like(gx_ref)

    @pl.when((i == 0) & (k == 0) & (j == 0))
    def _init_gb():
        gb_ref[0, 0] = jnp.float32(0.0)

    alpha = alpha_ref[0, 0]
    cw = _codes(w_ref[...].astype(jnp.float32), alpha, fmt)
    gx_ref[...] += jax.lax.dot_general(
        g_ref[...].astype(mxu), cw.astype(mxu), _NT,
        preferred_element_type=jnp.float32)

    @pl.when(j == n_j - 1)
    def _epilogue():
        acc = gx_ref[...] * _code_scale(alpha, fmt)
        gx, partial = _ste_epilogue(acc, x_ref[...].astype(jnp.float32),
                                    beta_ref[0, 0], fmt)
        gb_ref[0, 0] += partial
        gx_ref[...] = gx


def _qat_matmul_dw_kernel(g_ref, x_ref, w_ref, beta_ref, alpha_ref,
                          gw_ref, ga_ref, *, fmt, n_i, mxu):
    k, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init_gw():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    @pl.when((k == 0) & (j == 0) & (i == 0))
    def _init_ga():
        ga_ref[0, 0] = jnp.float32(0.0)

    beta = beta_ref[0, 0]
    cx = _codes(x_ref[...].astype(jnp.float32), beta, fmt)
    gw_ref[...] += jax.lax.dot_general(
        cx.astype(mxu), g_ref[...].astype(mxu), _TN,
        preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _epilogue():
        acc = gw_ref[...] * _code_scale(beta, fmt)
        gw, partial = _ste_epilogue(acc, w_ref[...].astype(jnp.float32),
                                    alpha_ref[0, 0], fmt)
        ga_ref[0, 0] += partial
        gw_ref[...] = gw


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
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Backward wrt activations: ``(dL/dx, dL/dbeta)``."""
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = _blocks("qat_matmul_dx", (m, k, n), (bm, bk, bn), g, x)
    gp = pad_to_blocks(g, bm, bn)
    wp = pad_to_blocks(w, bk, bn)
    xp = pad_to_blocks(x, bm, bk)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, kp // bk, np_ // bn)
    scalar = pl.BlockSpec((1, 1), lambda i, kk, j: (0, 0))
    gx, gb = pl.pallas_call(
        functools.partial(_qat_matmul_dx_kernel, fmt=fmt, n_j=grid[2],
                          mxu=mxu_dtype(g.dtype)),
        grid=grid,
        name="qat_matmul_dx",
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
    )(gp, wp, xp, _clip_scalar(beta), _clip_scalar(alpha))
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
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Backward wrt weights: ``(dL/dw, dL/dalpha)``."""
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = _blocks("qat_matmul_dw", (m, k, n), (bm, bk, bn), g, x)
    gp = pad_to_blocks(g, bm, bn)
    xp = pad_to_blocks(x, bm, bk)
    wp = pad_to_blocks(w, bk, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (kp // bk, np_ // bn, mp // bm)
    scalar = pl.BlockSpec((1, 1), lambda kk, j, i: (0, 0))
    gw, ga = pl.pallas_call(
        functools.partial(_qat_matmul_dw_kernel, fmt=fmt, n_i=grid[2],
                          mxu=mxu_dtype(g.dtype)),
        grid=grid,
        name="qat_matmul_dw",
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
    )(gp, xp, wp, _clip_scalar(beta), _clip_scalar(alpha))
    return gw[:k, :n].astype(w.dtype), ga.reshape(jnp.shape(alpha))
