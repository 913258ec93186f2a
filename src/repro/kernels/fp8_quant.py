"""Pallas TPU kernel: fused FP8 fake-quantization (paper Eq. 2 / Eq. 3).

QAT's hot loop is an elementwise clip -> scale (Eq. 2) -> round ->
dequantize chain over *every* weight and activation tensor, every step.
Left to XLA this lowers as several HBM-roundtripping elementwise HLOs; the
kernel fuses the whole chain into a single HBM read + write per element,
which puts the op at the HBM-bandwidth roofline (its arithmetic intensity
is a few flops/byte, firmly memory-bound).

TPU adaptation (DESIGN.md §3): blocks are (block_m x block_n) VMEM tiles
with lane dimension a multiple of 128 (VPU lane width) and sublane a
multiple of 8. The QAT quantizers consume a uint32 random operand
generated by ``jax.random`` *outside* the kernel — keeps the kernel
deterministic given its inputs and makes ``interpret=True`` CPU validation
bit-exact against the oracle in ``ref.py``.

Besides the QAT fake-quantizers this module holds their fused STE
*backward* kernels (see the derivation further down) and the wire-codec
tile kernels used by ``core.wire``: quantize+bit-pack to uint8, unpack,
and a fused quantize-dequantize for the simulator, with stochastic
rounding drawn from an in-kernel counter RNG (``counter_bits``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.fp8 import _ALPHA_FLOOR, E4M3, FP8Format

DEFAULT_BLOCK = (256, 512)  # 256*512*4B*3 buffers ~ 1.5 MiB of 16 MiB VMEM


def _mant_const(fmt: FP8Format) -> float:
    return float(np.log2(2.0 - 2.0 ** (-fmt.mant)))


def uniform_from_bits(bits):
    """u32 random bits -> f32 uniform in [0, 1): ``f32(bits) / 2**32``.

    Mosaic has no u32 -> f32 cast. Both 16-bit halves fit int32, and
    ``hi * 65536`` is exact, so the sum is ONE rounding of the exact integer
    — the same float as the direct cast. Kernels and jnp fallbacks share
    this helper, so every backend draws the same uniforms.
    """
    hi = (bits >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * 65536.0 + lo) * (1.0 / 4294967296.0)


def _quant_det_kernel(x_ref, alpha_ref, o_ref, *, fmt: FP8Format):
    x = x_ref[...].astype(jnp.float32)
    alpha = alpha_ref[0, 0]
    b = 2.0 ** fmt.exp - jnp.log2(alpha) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -alpha, alpha)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    o_ref[...] = (s * jnp.round(xc / s)).astype(o_ref.dtype)


def _quant_rand_kernel(x_ref, alpha_ref, u_ref, o_ref, *, fmt: FP8Format):
    x = x_ref[...].astype(jnp.float32)
    alpha = alpha_ref[0, 0]
    b = 2.0 ** fmt.exp - jnp.log2(alpha) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -alpha, alpha)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    y = xc / s
    fl = jnp.floor(y)
    u = uniform_from_bits(u_ref[...])
    q = fl + (u < (y - fl)).astype(jnp.float32)
    o_ref[...] = (s * q).astype(o_ref.dtype)


def _grid_and_specs(shape, block):
    bm = min(block[0], shape[0])
    bn = min(block[1], shape[1])
    grid = (pl.cdiv(shape[0], bm), pl.cdiv(shape[1], bn))
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return grid, tile, scalar


def pad_to_blocks(arr: jax.Array, bm: int, bn: int) -> jax.Array:
    """Zero-pad a 2-D array up to block multiples.

    Kernels that REDUCE over whole tiles (the scalar clip-value cotangent
    here, the contraction axis in fp8_matmul) must never see out-of-bounds
    tile padding — NaN in interpret mode, undefined on TPU. Zero padding
    contributes exactly 0 to every gradient/accumulation term.
    """
    m, n = arr.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        arr = jnp.pad(arr, ((0, pm), (0, pn)))
    return arr


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def quant_det(
    x: jax.Array,
    alpha: jax.Array,
    fmt: FP8Format = E4M3,
    block: tuple[int, int] = DEFAULT_BLOCK,
    interpret: bool = True,
) -> jax.Array:
    """Deterministic FP8 fake-quant of a 2-D-viewable array (per-tensor alpha)."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]) if x.ndim != 2 else x
    grid, tile, scalar = _grid_and_specs(x2.shape, block)
    out = pl.pallas_call(
        functools.partial(_quant_det_kernel, fmt=fmt),
        grid=grid,
        name="quant_det",
        in_specs=[tile, scalar],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
    )(x2, jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)))
    return out.reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def quant_rand(
    x: jax.Array,
    alpha: jax.Array,
    rand_u32: jax.Array,
    fmt: FP8Format = E4M3,
    block: tuple[int, int] = DEFAULT_BLOCK,
    interpret: bool = True,
) -> jax.Array:
    """Stochastic FP8 quant; ``rand_u32`` must match ``x``'s shape."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]) if x.ndim != 2 else x
    u2 = rand_u32.reshape(x2.shape)
    grid, tile, scalar = _grid_and_specs(x2.shape, block)
    out = pl.pallas_call(
        functools.partial(_quant_rand_kernel, fmt=fmt),
        grid=grid,
        name="quant_rand",
        in_specs=[tile, scalar, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
    )(x2, jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)), u2)
    return out.reshape(orig_shape)


# ---------------------------------------------------------------------------
# Backward kernels (the paper's STE, fused): one HBM read of (x, g) and one
# write of gx per element, with the alpha cotangent reduced across the whole
# grid into a revisited (1, 1) SMEM accumulator (Mosaic stores scalars only
# to SMEM; grid iteration on TPU is sequential, so the accumulation is
# race-free; same in interpret mode).
#
# Gradient derivation (matches jnp autodiff of repro.core.fp8.quantize_det,
# where round/floor are STE and the exponent term p is stop-gradded):
#
#   out = s(alpha) * ste(y),  y = clip(x, -a, a) / s
#   dout/dx     = 1{|x| <= a}                                  (clip mask)
#   dout/dalpha = sign(x) * 1{|x| > a}        (clip routing)
#             + (q - y) * s / alpha           (scale term: ds/da = s/a)
# ---------------------------------------------------------------------------


_SMEM_ACC = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM)


def _ste_terms(x, alpha, fmt: FP8Format):
    """Shared STE geometry: (inside-clip mask, scale s, pre-round y)."""
    b = 2.0 ** fmt.exp - jnp.log2(alpha) + _mant_const(fmt) - 1.0
    inside = (jnp.abs(x) <= alpha).astype(jnp.float32)
    xc = jnp.clip(x, -alpha, alpha)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    return inside, s, xc / s


def _quant_bwd_kernel(x_ref, alpha_ref, g_ref, gx_ref, ga_ref, *,
                      fmt: FP8Format, u_ref=None):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    alpha = alpha_ref[0, 0]
    inside, s, y = _ste_terms(x, alpha, fmt)
    if u_ref is None:
        q = jnp.round(y)
    else:
        fl = jnp.floor(y)
        u = uniform_from_bits(u_ref[...])
        q = fl + (u < (y - fl)).astype(jnp.float32)
    gx_ref[...] = (g * inside).astype(gx_ref.dtype)
    partial = jnp.sum(
        g * (jnp.sign(x) * (1.0 - inside) + (q - y) * s / alpha)
    )
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        ga_ref[0, 0] = jnp.float32(0.0)

    ga_ref[0, 0] += partial


def _quant_rand_bwd_kernel(x_ref, alpha_ref, u_ref, g_ref, gx_ref, ga_ref, *,
                           fmt: FP8Format):
    _quant_bwd_kernel(x_ref, alpha_ref, g_ref, gx_ref, ga_ref,
                      fmt=fmt, u_ref=u_ref)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def quant_det_bwd(
    x: jax.Array,
    alpha: jax.Array,
    g: jax.Array,
    fmt: FP8Format = E4M3,
    block: tuple[int, int] = DEFAULT_BLOCK,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused STE backward of :func:`quant_det`: ``(dL/dx, dL/dalpha)``."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]) if x.ndim != 2 else x
    g2 = g.reshape(x2.shape)
    m, n = x2.shape
    bm, bn = min(block[0], m), min(block[1], n)
    # zero-pad: the ga accumulator sums whole tiles (see pad_to_blocks)
    x2 = pad_to_blocks(x2, bm, bn)
    g2 = pad_to_blocks(g2, bm, bn)
    grid, tile, scalar = _grid_and_specs(x2.shape, block)
    gx, ga = pl.pallas_call(
        functools.partial(_quant_bwd_kernel, fmt=fmt),
        grid=grid,
        name="quant_det_bwd",
        in_specs=[tile, scalar, tile],
        out_specs=[tile, _SMEM_ACC],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)), g2)
    return gx[:m, :n].reshape(orig_shape), ga.reshape(jnp.shape(alpha))


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def quant_rand_bwd(
    x: jax.Array,
    alpha: jax.Array,
    rand_u32: jax.Array,
    g: jax.Array,
    fmt: FP8Format = E4M3,
    block: tuple[int, int] = DEFAULT_BLOCK,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused STE backward of :func:`quant_rand` (same bits as forward)."""
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]) if x.ndim != 2 else x
    g2 = g.reshape(x2.shape)
    u2 = rand_u32.reshape(x2.shape)
    m, n = x2.shape
    bm, bn = min(block[0], m), min(block[1], n)
    # zero-pad: the ga accumulator sums whole tiles (see pad_to_blocks)
    x2 = pad_to_blocks(x2, bm, bn)
    g2 = pad_to_blocks(g2, bm, bn)
    u2 = pad_to_blocks(u2, bm, bn)
    grid, tile, scalar = _grid_and_specs(x2.shape, block)
    gx, ga = pl.pallas_call(
        functools.partial(_quant_rand_bwd_kernel, fmt=fmt),
        grid=grid,
        name="quant_rand_bwd",
        in_specs=[tile, scalar, tile, tile],
        out_specs=[tile, _SMEM_ACC],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, jnp.reshape(jnp.maximum(alpha.astype(jnp.float32), _ALPHA_FLOOR), (1, 1)), u2, g2)
    return gx[:m, :n].reshape(orig_shape), ga.reshape(jnp.shape(alpha))


# ---------------------------------------------------------------------------
# Flat-buffer wire codec kernels: fused quantize + bit-pack producing the
# uint8 payload that crosses the federated wire, and its inverse. Per-ELEMENT
# alpha operand (the flat buffer concatenates many tensors, each with its own
# clipping value, broadcast to element granularity by the caller) — so the
# whole model quantizes+packs in ONE kernel launch instead of one per leaf.
# Bit layout matches repro.core.fp8.pack_fp8: [sign:1][exp:e][mant:m].
# ---------------------------------------------------------------------------

def _pack_code(xc, a, b, fmt: FP8Format, u=None):
    """Quantize clipped values onto the FP8 grid and assemble uint8 codes."""
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    p = jnp.minimum(p, float(fmt.max_exp_code))
    s = jnp.exp2(p - b - fmt.mant)
    # round the SIGNED scaled value — bit-exact with quantize_det/_rand given
    # the same random operand (stochastic rounding is sign-asymmetric)
    y = xc / s
    if u is None:
        v_signed = jnp.round(y)
    else:
        fl = jnp.floor(y)
        v_signed = fl + (u < (y - fl)).astype(jnp.float32)
    sign = (v_signed < 0).astype(jnp.int32)
    v = jnp.abs(v_signed).astype(jnp.int32)   # in [0, 2^(m+1)]
    # mantissa overflow at bin edges (v == 2^(m+1)): renormalize into the
    # next exponent bin (same real value, valid field encoding). With the
    # exponent already saturated there is no next bin — saturate the
    # mantissa instead (halving v without bumping p would decode at half
    # the real value).
    overflow = v >= 2 ** (fmt.mant + 1)
    at_max = p >= float(fmt.max_exp_code)
    v = jnp.where(overflow & at_max, 2 ** (fmt.mant + 1) - 1,
                  jnp.where(overflow, v // 2, v))
    p = jnp.where(overflow & ~at_max, p + 1.0, p)
    is_normal = v >= 2 ** fmt.mant
    f = jnp.where(is_normal, p.astype(jnp.int32), 0)
    m_field = jnp.where(is_normal, v - 2 ** fmt.mant, v)
    return (sign << (fmt.exp + fmt.mant)) | (f << fmt.mant) | m_field


def _quant_pack_det_kernel(x_ref, a_ref, o_ref, *, fmt: FP8Format):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    o_ref[...] = _pack_code(xc, a, b, fmt).astype(jnp.uint8)


# -- in-kernel counter-based RNG for the wire codec --------------------------
#
# Stochastic rounding on the wire needs a uniform per element, not a
# cryptographic stream. Drawing it from jax.random outside the kernel costs
# a full threefry pass over the model on the host AND a u32 operand read
# per element inside the kernel; here the kernel derives bits from
# (global element index, key) with two rounds of the murmur3 finalizer —
# ~10 integer ops per element fused into the same VMEM pass. The identical
# integer math runs on the jnp fallback path, so the wire payload is
# bit-identical across backends (tested).


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EB_CA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2_AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def counter_bits(idx, k0, k1):
    """Per-element u32 from a counter and two u32 key words (murmur3 mix)."""
    h = _fmix32(idx.astype(jnp.uint32) ^ k0.astype(jnp.uint32))
    return _fmix32(h ^ k1.astype(jnp.uint32))


def _tile_counter_bits(row0, shape, k0, k1):
    """counter_bits for a (rows, LANE) tile starting at absolute row ``row0``."""
    rows, lanes = shape
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    idx = (row0.astype(jnp.uint32) + r) * jnp.uint32(lanes) + c
    return counter_bits(idx, k0, k1)


def _quant_pack_rand_ctr_kernel(x_ref, a_ref, k_ref, o_ref, *,
                                fmt: FP8Format, block_rows: int):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    row0 = pl.program_id(0) * block_rows
    bits = _tile_counter_bits(jnp.uint32(row0), x.shape,
                              k_ref[0, 0], k_ref[0, 1])
    u = uniform_from_bits(bits)
    o_ref[...] = _pack_code(xc, a, b, fmt, u=u).astype(jnp.uint8)


def _fake_quant_tiles_kernel(x_ref, a_ref, o_ref, *, fmt: FP8Format,
                             block_rows: int, k_ref=None):
    """Fused quantize->dequantize (pack∘unpack transit) for the simulator.

    The wire sim's quantize-dequantize never needs the uint8 codes
    materialized: ``v * s`` here and the decoder's renormalized
    ``v' * s'`` are float32 spellings of the SAME FP8 grid point, equal
    within 1 ULP (the decoder recomputes the scale after bin-edge
    renormalization) — so this single pass stands in for encode->decode at
    ~half the HBM traffic, one launch, and ~1/3 fewer interpreter passes.
    """
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    p = jnp.minimum(p, float(fmt.max_exp_code))
    s = jnp.exp2(p - b - fmt.mant)
    y = xc / s
    if k_ref is None:
        q = jnp.round(y)
    else:
        row0 = pl.program_id(0) * block_rows
        bits = _tile_counter_bits(jnp.uint32(row0), x.shape,
                                  k_ref[0, 0], k_ref[0, 1])
        u = uniform_from_bits(bits)
        fl = jnp.floor(y)
        q = fl + (u < (y - fl)).astype(jnp.float32)
    # saturated-exponent clamp mirroring _pack_code: with p at max there is
    # no next bin for a mantissa-overflow round-up (|q| == 2^(m+1), only
    # reachable through float fuzz at the clip boundary)
    vmax = float(2 ** (fmt.mant + 1) - 1)
    q = jnp.where(p >= float(fmt.max_exp_code), jnp.clip(q, -vmax, vmax), q)
    o_ref[...] = (s * q).astype(o_ref.dtype)


def _fake_quant_tiles_rand_kernel(x_ref, a_ref, k_ref, o_ref, *,
                                  fmt: FP8Format, block_rows: int):
    _fake_quant_tiles_kernel(x_ref, a_ref, o_ref, fmt=fmt,
                             block_rows=block_rows, k_ref=k_ref)


def fake_quant_bits_jnp(x2, a2, bits, fmt: FP8Format = E4M3):
    """Quantize-dequantize with explicit uint32 bits (``None`` -> det).

    The arithmetic twin of the fused sim kernel body; callers that need the
    SAME stochastic-rounding decisions as a fused plane launch (e.g. the
    per-leaf reference in ``core.server_opt``) compute ``bits`` with
    :func:`_tile_counter_bits` at the matching absolute row offset.
    """
    x = x2.astype(jnp.float32) if x2.dtype != jnp.float32 else x2
    a = a2
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    p = jnp.minimum(p, float(fmt.max_exp_code))
    s = jnp.exp2(p - b - fmt.mant)
    y = xc / s
    if bits is None:
        q = jnp.round(y)
    else:
        u = uniform_from_bits(bits)
        fl = jnp.floor(y)
        q = fl + (u < (y - fl)).astype(jnp.float32)
    vmax = float(2 ** (fmt.mant + 1) - 1)
    q = jnp.where(p >= float(fmt.max_exp_code), jnp.clip(q, -vmax, vmax), q)
    return s * q


def fake_quant_tiles_jnp(x2, a2, key2, fmt: FP8Format = E4M3):
    """jnp twin of the fused sim kernel — op-for-op the same arithmetic,
    so both backends draw identical stochastic-rounding decisions."""
    bits = None
    if key2 is not None:
        k2 = key2.astype(jnp.uint32)
        bits = _tile_counter_bits(jnp.uint32(0), x2.shape, k2[0], k2[1])
    return fake_quant_bits_jnp(x2, a2, bits, fmt)


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def fake_quant_tiles(
    x2: jax.Array,                  # (R, LANE) f32 tile layout
    a2: jax.Array,                  # (R, LANE) f32 per-element clipping values
    key2: jax.Array | None = None,  # (2,) u32 -> stochastic; None -> det
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """One-launch quantize-dequantize on the wire tile layout (f32 out)."""
    rows = x2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    if key2 is None:
        return pl.pallas_call(
            functools.partial(_fake_quant_tiles_kernel, fmt=fmt,
                              block_rows=bm),
            grid=grid,
            name="fake_quant_tiles",
            in_specs=[tile, tile_a],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
            interpret=interpret,
        )(x2, a2)
    return pl.pallas_call(
        functools.partial(_fake_quant_tiles_rand_kernel, fmt=fmt,
                          block_rows=bm),
        grid=grid,
        name="fake_quant_tiles",
        in_specs=[tile, tile_a, pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        interpret=interpret,
    )(x2, a2, jnp.reshape(key2.astype(jnp.uint32), (1, 2)))


# ---------------------------------------------------------------------------
# Parameter-plane tiles: fused Q_det forward/backward on the (rows, LANE)
# plane layout of ``core.plane``. Alpha rides as a per-ROW column (R, 1) —
# every row belongs to exactly one clipping scalar by construction — and the
# backward kernel reduces the alpha cotangent per row in VMEM; the caller's
# gather transpose (a scatter-add over row->segment ids) finishes the
# segment-sum back to each leaf's scalar or stacked per-layer alpha. The
# arithmetic matches ``_quant_det_kernel`` (== ``core.fp8.quantize_det``)
# exactly: no wire-codec saturation clamps, because clipped inputs cannot
# leave the representable range (|y| <= 2^mant * mant_scale at p_max).
# ---------------------------------------------------------------------------


def _quant_det_tiles_kernel(x_ref, a_ref, o_ref, *, fmt: FP8Format):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)          # (bm, 1), broadcasts
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    o_ref[...] = (s * jnp.round(xc / s)).astype(o_ref.dtype)


def _quant_det_tiles_bwd_kernel(x_ref, a_ref, g_ref, gx_ref, ga_ref, *,
                                fmt: FP8Format):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)          # (bm, 1), broadcasts
    g = g_ref[...].astype(jnp.float32)
    inside, s, y = _ste_terms(x, a, fmt)
    q = jnp.round(y)
    gx_ref[...] = (g * inside).astype(gx_ref.dtype)
    ga_ref[...] = jnp.sum(
        g * (jnp.sign(x) * (1.0 - inside) + (q - y) * s / a),
        axis=1, keepdims=True,
    )


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def quant_det_tiles(
    x2: jax.Array,                  # (R, LANE) f32 plane layout
    a2: jax.Array,                  # (R, 1) per-row clipping values
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """One-launch deterministic fake-quant on the plane layout (f32 out)."""
    rows = x2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_quant_det_tiles_kernel, fmt=fmt),
        grid=grid,
        name="quant_det_tiles",
        in_specs=[tile, tile_a],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        interpret=interpret,
    )(x2, a2)


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def quant_det_tiles_bwd(
    x2: jax.Array,                  # (R, LANE) f32 plane layout
    a2: jax.Array,                  # (R, 1) per-row clipping values
    g2: jax.Array,                  # (R, LANE) output cotangent
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused STE backward of :func:`quant_det_tiles`.

    Returns ``(gx (R, LANE), ga_row (R, 1))`` — the clip-mask gradient to
    the tiles and the per-row sum of the clip-routing + scale-term alpha
    gradient. Rows are independent (the reduction is lane-wise only), so no
    whole-tile padding is needed; the partial last block's out-of-bounds
    rows never reach the output.
    """
    rows = x2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    col = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_quant_det_tiles_bwd_kernel, fmt=fmt),
        grid=grid,
        name="quant_det_tiles_bwd",
        in_specs=[tile, tile_a, tile],
        out_specs=[tile, col],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, a2, g2)


def _decode_codes(code, a, fmt: FP8Format):
    """Shared [sign|exp|mant] field decode -> f32 grid values."""
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    sign = (code >> (fmt.exp + fmt.mant)) & 0x1
    f = (code >> fmt.mant) & (2 ** fmt.exp - 1)
    m_field = code & (2 ** fmt.mant - 1)
    is_normal = f >= 1
    v = jnp.where(is_normal, m_field + 2 ** fmt.mant, m_field)
    p_eff = jnp.where(is_normal, f, 1)
    s = jnp.exp2(p_eff.astype(jnp.float32) - b - fmt.mant)
    mag = v.astype(jnp.float32) * s
    return jnp.where(sign == 1, -mag, mag)


def _unpack_kernel(c_ref, a_ref, o_ref, *, fmt: FP8Format):
    code = c_ref[...].astype(jnp.int32)
    a = a_ref[...].astype(jnp.float32)
    o_ref[...] = _decode_codes(code, a, fmt).astype(o_ref.dtype)


WIRE_LANE = 1024  # lane width of the (rows, WIRE_LANE) tile layout (core.wire)


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def quant_pack_tiles(
    x2: jax.Array,               # (R, LANE) f32 tile layout (core.wire builds it)
    a2: jax.Array,               # (R, LANE) f32 per-element clipping values
    key2: jax.Array | None = None,  # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Fused quantize+pack on the wire tile layout -> (R, LANE) uint8 codes.

    The tile layout exists because XLA:CPU pessimizes a flat
    concat-of-reshapes feeding an interpret-mode pallas_call (measured ~7x
    on whole-model encode); per-leaf lane-aligned tiles concatenated on the
    row axis sidestep it and are also the natural TPU layout (lane multiple
    of 128). Out-of-bounds tile padding is sliced off by the caller — the
    kernel is purely elementwise, so pad garbage never contaminates real
    elements. Stochastic rounding derives its uniforms IN-KERNEL from
    (element index, ``key2``) — see ``counter_bits``.
    """
    rows = x2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    # alpha may ride as a per-row column (R, 1) when every leaf's clipping
    # value is scalar — 1/LANE the operand traffic; broadcasts in-kernel
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    if key2 is None:
        return pl.pallas_call(
            functools.partial(_quant_pack_det_kernel, fmt=fmt),
            grid=grid,
            name="quant_pack_tiles",
            in_specs=[tile, tile_a],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.uint8),
            interpret=interpret,
        )(x2, a2)
    return pl.pallas_call(
        functools.partial(_quant_pack_rand_ctr_kernel, fmt=fmt,
                          block_rows=bm),
        grid=grid,
        name="quant_pack_tiles",
        in_specs=[tile, tile_a, pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.uint8),
        interpret=interpret,
    )(x2, a2, jnp.reshape(key2.astype(jnp.uint32), (1, 2)))


# ---------------------------------------------------------------------------
# Sub-byte wire codes: ExMy formats with bits < 8 (FP4 E2M1/E3M0) pack
# ``8 // bits`` codes into each payload byte. The quantization math is the
# SAME parametric grid as FP8, so the 1-byte kernels above produce the codes
# and ``fold_codes`` assembles the bytes outside the kernel: a (R, LANE) tile
# of b-bit codes folds to (R, LANE / (8//b)) uint8, element ``k*i + j`` in
# bit field ``j*b .. (j+1)*b`` (little-endian sub-fields, so consecutive
# elements share a byte and a leaf's packed bytes slice to exactly
# ``ceil(n * bits / 8)`` on the wire). The fold has no kernel form: Mosaic
# refuses the lane-splitting reshape it needs. Packing never changes a
# rounding decision, so ``fake_quant_tiles`` at the sub-byte format is the
# exact transit twin of pack+unpack.
# ---------------------------------------------------------------------------


def codes_per_byte(fmt: FP8Format) -> int:
    """How many ``fmt`` codes share one payload byte (1 for FP8, 2 for FP4)."""
    if fmt.bits > 8 or 8 % fmt.bits:
        raise ValueError(f"cannot byte-pack a {fmt.bits}-bit format")
    return 8 // fmt.bits


def fold_codes(codes, fmt: FP8Format):
    """(bm, L) b-bit codes -> (bm, L // (8//b)) packed uint8 (little-endian)."""
    k = codes_per_byte(fmt)
    if k == 1:
        return codes.astype(jnp.uint8)
    bm, lanes = codes.shape
    c = codes.astype(jnp.int32).reshape(bm, lanes // k, k)
    out = c[..., 0]
    for j in range(1, k):
        out = out | (c[..., j] << (fmt.bits * j))
    return out.astype(jnp.uint8)


def unfold_codes(packed, fmt: FP8Format):
    """Inverse of :func:`fold_codes`: packed uint8 -> (bm, L) b-bit codes."""
    k = codes_per_byte(fmt)
    p = packed.astype(jnp.int32)
    if k == 1:
        return p
    mask = (1 << fmt.bits) - 1
    parts = [(p >> (fmt.bits * j)) & mask for j in range(k)]
    code = jnp.stack(parts, axis=-1)
    return code.reshape(p.shape[0], p.shape[1] * k)


# ---------------------------------------------------------------------------
# Fused quantize + amax-update kernels (delayed scaling, core.scaling).
# TE-style delayed scaling needs NEXT round's per-segment amax, but running a
# standalone reduction before (or after) the quantize launch would put the
# very reduction the policy exists to remove back in the critical path. These
# variants emit the per-ROW max|x| of the RAW (pre-clip) input as a second
# (R, 1) output of the SAME launch — one extra VMEM reduction per tile, zero
# extra HBM reads. The caller finishes with a tiny (n_rows -> n_leaves)
# segment-max over the row column (core.codec._plane_segment_amax); rows are
# independent (lane-wise reduction only), so no whole-tile padding is needed
# and the partial last block's out-of-bounds rows never reach the output.
# Quantization arithmetic is IDENTICAL to the non-amax kernels — same codes,
# bit for bit.
# ---------------------------------------------------------------------------


def _quant_pack_det_amax_kernel(x_ref, a_ref, o_ref, mx_ref, *,
                                fmt: FP8Format):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    o_ref[...] = _pack_code(xc, a, b, fmt).astype(jnp.uint8)
    mx_ref[...] = jnp.max(jnp.abs(x), axis=1, keepdims=True)


def _quant_pack_rand_ctr_amax_kernel(x_ref, a_ref, k_ref, o_ref, mx_ref, *,
                                     fmt: FP8Format, block_rows: int):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    row0 = pl.program_id(0) * block_rows
    bits = _tile_counter_bits(jnp.uint32(row0), x.shape,
                              k_ref[0, 0], k_ref[0, 1])
    u = uniform_from_bits(bits)
    o_ref[...] = _pack_code(xc, a, b, fmt, u=u).astype(jnp.uint8)
    mx_ref[...] = jnp.max(jnp.abs(x), axis=1, keepdims=True)


def _fake_quant_amax_tiles_kernel(x_ref, a_ref, o_ref, mx_ref, *,
                                  fmt: FP8Format, block_rows: int,
                                  k_ref=None):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = 2.0 ** fmt.exp - jnp.log2(a) + _mant_const(fmt) - 1.0
    xc = jnp.clip(x, -a, a)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    p = jnp.minimum(p, float(fmt.max_exp_code))
    s = jnp.exp2(p - b - fmt.mant)
    y = xc / s
    if k_ref is None:
        q = jnp.round(y)
    else:
        row0 = pl.program_id(0) * block_rows
        bits = _tile_counter_bits(jnp.uint32(row0), x.shape,
                                  k_ref[0, 0], k_ref[0, 1])
        u = uniform_from_bits(bits)
        fl = jnp.floor(y)
        q = fl + (u < (y - fl)).astype(jnp.float32)
    vmax = float(2 ** (fmt.mant + 1) - 1)
    q = jnp.where(p >= float(fmt.max_exp_code), jnp.clip(q, -vmax, vmax), q)
    o_ref[...] = (s * q).astype(o_ref.dtype)
    mx_ref[...] = jnp.max(jnp.abs(x), axis=1, keepdims=True)


def _fake_quant_amax_tiles_rand_kernel(x_ref, a_ref, k_ref, o_ref, mx_ref, *,
                                       fmt: FP8Format, block_rows: int):
    _fake_quant_amax_tiles_kernel(x_ref, a_ref, o_ref, mx_ref, fmt=fmt,
                                  block_rows=block_rows, k_ref=k_ref)


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def quant_pack_amax_tiles(
    x2: jax.Array,                  # (R, LANE) f32 wire tile layout
    a2: jax.Array,                  # (R, 1) or (R, LANE) f32 clipping values
    key2: jax.Array | None = None,  # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """:func:`quant_pack_tiles` + fused per-row raw amax.

    Returns ``(codes (R, LANE) u8, rowmax (R, 1) f32)`` — codes bit-identical
    to the non-amax kernel, rowmax = max|x| of the UNclipped input row.
    """
    rows = x2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    col = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct(x2.shape, jnp.uint8),
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),
    ]
    if key2 is None:
        return pl.pallas_call(
            functools.partial(_quant_pack_det_amax_kernel, fmt=fmt),
            grid=grid,
            name="quant_pack_amax_tiles",
            in_specs=[tile, tile_a],
            out_specs=[tile, col],
            out_shape=out_shape,
            interpret=interpret,
        )(x2, a2)
    return pl.pallas_call(
        functools.partial(_quant_pack_rand_ctr_amax_kernel, fmt=fmt,
                          block_rows=bm),
        grid=grid,
        name="quant_pack_amax_tiles",
        in_specs=[tile, tile_a, pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[tile, col],
        out_shape=out_shape,
        interpret=interpret,
    )(x2, a2, jnp.reshape(key2.astype(jnp.uint32), (1, 2)))


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def fake_quant_amax_tiles(
    x2: jax.Array,                  # (R, LANE) f32 tile layout
    a2: jax.Array,                  # (R, 1) or (R, LANE) f32 clipping values
    key2: jax.Array | None = None,  # (2,) u32 -> stochastic; None -> det
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """:func:`fake_quant_tiles` + fused per-row raw amax (f32, (R, 1))."""
    rows = x2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    col = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        jax.ShapeDtypeStruct((rows, 1), jnp.float32),
    ]
    if key2 is None:
        return pl.pallas_call(
            functools.partial(_fake_quant_amax_tiles_kernel, fmt=fmt,
                              block_rows=bm),
            grid=grid,
            name="fake_quant_amax_tiles",
            in_specs=[tile, tile_a],
            out_specs=[tile, col],
            out_shape=out_shape,
            interpret=interpret,
        )(x2, a2)
    return pl.pallas_call(
        functools.partial(_fake_quant_amax_tiles_rand_kernel, fmt=fmt,
                          block_rows=bm),
        grid=grid,
        name="fake_quant_amax_tiles",
        in_specs=[tile, tile_a, pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[tile, col],
        out_shape=out_shape,
        interpret=interpret,
    )(x2, a2, jnp.reshape(key2.astype(jnp.uint32), (1, 2)))


def fake_quant_amax_tiles_jnp(x2, a2, key2, fmt: FP8Format = E4M3):
    """jnp twin of :func:`fake_quant_amax_tiles` — same arithmetic, same
    stochastic-rounding decisions, plus the per-row raw amax column."""
    q = fake_quant_tiles_jnp(x2, a2, key2, fmt)
    mx = jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=1, keepdims=True)
    return q, mx


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def unpack_tiles(
    c2: jax.Array,               # (R, LANE) uint8 codes
    a2: jax.Array,               # (R, LANE) f32 per-element clipping values
    fmt: FP8Format = E4M3,
    block_rows: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Inverse of :func:`quant_pack_tiles` -> (R, LANE) f32 grid values."""
    rows = c2.shape[0]
    bm = min(block_rows, rows)
    grid = (pl.cdiv(rows, bm),)
    tile = pl.BlockSpec((bm, WIRE_LANE), lambda i: (i, 0))
    tile_a = pl.BlockSpec((bm, a2.shape[1]), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_unpack_kernel, fmt=fmt),
        grid=grid,
        name="unpack_tiles",
        in_specs=[tile, tile_a],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(c2.shape, jnp.float32),
        interpret=interpret,
    )(c2, a2)
