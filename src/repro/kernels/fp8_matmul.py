"""Pallas TPU kernels: fused FP8-QAT matmul, dense and grouped.

The TPU-native analogue of H100 FP8 tensor-core GEMMs: both
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
# timed, since no workload on the chip hands the kernels f32. A kernel
# without a 4-byte row takes half its 2-byte row's M.
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
    rows = _BLOCKS[kernel]
    default = rows[2]
    if max(jnp.dtype(o.dtype).itemsize for o in operands) > 2:
        default = rows.get(4, (default[0] // 2, *default[1:]))
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


# ---------------------------------------------------------------------------
# Grouped QAT matmul (sparse experts). The rows of ``x`` (M, K) are sorted
# by group: rows [offsets[e], offsets[e + 1]) belong to group e, whose
# weight is ``w[e]`` (K, N) with its own clip ``alpha[e]``; one activation
# clip ``beta`` serves every row. Rows from ``offsets[-1]`` to M belong to
# no group: the kernels never visit them and their output rows are zero.
#
# The grid walks a schedule of visits (:func:`_gmm_schedule`): each group
# visits the row tiles it covers, in order, so a tile shared by two groups
# is visited by both, one after the other, each writing its own rows. An
# empty group visits one tile and computes nothing (the weight gradient
# still writes its zero). The visit axis's extent is the number of visits,
# read at run time, so the work scales with the routed rows, not with M.
# The schedule reaches the index maps as scalar prefetch. Forward and dx
# keep the f32 accumulator in a VMEM scratch, so that an output tile keeps
# the rows an earlier group wrote.
#
# The kernels are batched natively over a leading axis (the clients that
# ``vmap`` trains side by side): the batch is the outermost grid axis and
# each batch entry has its own schedule; visits past an entry's own count
# do nothing and keep their blocks where they are. ``vmap`` of the public
# functions lands there (``custom_vmap``), not in Pallas's generic
# batching, which would loop over the batch with a copy of every operand.
# ---------------------------------------------------------------------------

_BLOCKS.update({
    "qat_gmm": {2: (512, 512, 1024)},
    "qat_gmm_dx": {2: (512, 256, 512)},
    "qat_gmm_dw": {2: (512, 512, 512)},
})


def _gmm_schedule(offsets, m_tiles: int, bm: int):
    """``(group_ids, m_tile_ids, n_visits)``: the group and the row tile of
    each visit, padded to the static bound ``m_tiles + G - 1`` by repeating
    the last visit, and the number of visits."""
    n_groups = offsets.shape[0] - 1
    start, stop = offsets[:-1], offsets[1:]
    first = jnp.minimum(start // bm, m_tiles - 1)
    last = jnp.where(stop > start, (stop - 1) // bm, first)
    span = last - first + 1
    bound = m_tiles + n_groups - 1
    n_visits = jnp.sum(span).astype(jnp.int32)
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), span,
                           total_repeat_length=bound)
    t = jnp.minimum(jnp.arange(bound, dtype=jnp.int32), n_visits - 1)
    group_ids = group_ids[t]
    tiles = first[group_ids] + t - (jnp.cumsum(span) - span)[group_ids]
    return group_ids, tiles.astype(jnp.int32), n_visits


class _Visit:
    """Scalar-prefetch view of batch entry ``b``'s visit ``t``."""

    def __init__(self, refs, b, t, n_groups: int, bound: int):
        self.gids, self.mids, self.offs, self.nv = refs
        self.b, self.t, self.bound = b, t, bound
        self.group = self.gids[b * bound + t]
        self.tile = self.mids[b * bound + t]
        self._o = b * (n_groups + 1) + self.group
        self.active = t < self.nv[b]
        self.nonempty = self.offs[self._o + 1] > self.offs[self._o]

    def rows(self, bm: int):
        """(bm, 1) mask of the tile's rows that belong to the group."""
        r = self.tile * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        return (r >= self.offs[self._o]) & (r < self.offs[self._o + 1])

    def first_of_tile(self):
        prev = self.b * self.bound + jnp.maximum(self.t - 1, 0)
        return (self.t == 0) | (self.mids[prev] != self.tile)

    def first_of_group(self):
        prev = self.b * self.bound + jnp.maximum(self.t - 1, 0)
        return (self.t == 0) | (self.gids[prev] != self.group)

    def last_of_group(self):
        nxt = self.b * self.bound + jnp.minimum(self.t + 1, self.bound - 1)
        return (self.t == self.nv[self.b] - 1) | (self.gids[nxt] != self.group)


def _frozen(t, nv, b, i, last):
    """Index ``i`` of an axis inside the visit axis, held at ``last`` on
    visits past the entry's count (so that no block moves there)."""
    return jnp.where(t < nv[b], i, last)


def _gmm_store(o_ref, value, own, first):
    """Write the visiting group's rows of an output tile; on the tile's
    first visit the other rows are zeroed, else kept."""
    kept = jnp.where(first, 0.0, o_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.where(own, value, kept).astype(o_ref.dtype)


def _qat_gmm_kernel(gids, mids, offs, nv, x_ref, w_ref, beta_ref, alpha_ref,
                    o_ref, acc_ref, *, fmt, n_k, bm, n_groups, bound):
    b, t, kk = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    v = _Visit((gids, mids, offs, nv), b, t, n_groups, bound)
    beta = jnp.maximum(beta_ref[b], _ALPHA_FLOOR)
    alpha = jnp.maximum(alpha_ref[b * n_groups + v.group], _ALPHA_FLOOR)

    @pl.when(v.active & (kk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v.active & v.nonempty)
    def _accumulate():
        cx = _codes(x_ref[...].astype(jnp.float32), beta, fmt)
        cw = _codes(w_ref[...].astype(jnp.float32), alpha, fmt)
        acc_ref[...] += jnp.dot(cx.astype(CODE_DTYPE), cw.astype(CODE_DTYPE),
                                preferred_element_type=jnp.float32)

    @pl.when(v.active & (kk == n_k - 1))
    def _store():
        value = acc_ref[...] * (_code_scale(beta, fmt)
                                * _code_scale(alpha, fmt))
        _gmm_store(o_ref, value, v.rows(bm), v.first_of_tile())


def _qat_gmm_dx_kernel(gids, mids, offs, nv, g_ref, w_ref, x_ref, beta_ref,
                       alpha_ref, gx_ref, gb_ref, acc_ref, *, fmt, n_j, bm,
                       mxu, n_groups, bound):
    b, kk, t, j = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                   pl.program_id(3))
    v = _Visit((gids, mids, offs, nv), b, t, n_groups, bound)
    alpha = jnp.maximum(alpha_ref[b * n_groups + v.group], _ALPHA_FLOOR)

    @pl.when((kk == 0) & (t == 0) & (j == 0))
    def _init_gb():
        gb_ref[b] = jnp.float32(0.0)

    @pl.when(v.active & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v.active & v.nonempty)
    def _accumulate():
        cw = _codes(w_ref[...].astype(jnp.float32), alpha, fmt)
        acc_ref[...] += jax.lax.dot_general(
            g_ref[...].astype(mxu), cw.astype(mxu), _NT,
            preferred_element_type=jnp.float32)

    @pl.when(v.active & (j == n_j - 1))
    def _epilogue():
        own = v.rows(bm)
        acc = jnp.where(own, acc_ref[...] * _code_scale(alpha, fmt), 0.0)
        gx, partial = _ste_epilogue(
            acc, x_ref[...].astype(jnp.float32),
            jnp.maximum(beta_ref[b], _ALPHA_FLOOR), fmt)
        gb_ref[b] += partial
        _gmm_store(gx_ref, gx, own, v.first_of_tile())


def _qat_gmm_dw_kernel(gids, mids, offs, nv, g_ref, x_ref, w_ref, beta_ref,
                       alpha_ref, gw_ref, ga_ref, *, fmt, bm, mxu, n_groups,
                       bound):
    b, kk, j, t = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                   pl.program_id(3))
    v = _Visit((gids, mids, offs, nv), b, t, n_groups, bound)
    beta = jnp.maximum(beta_ref[b], _ALPHA_FLOOR)
    first = v.active & v.first_of_group()
    slot = b * n_groups + v.group

    @pl.when(first)
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    @pl.when(first & (kk == 0) & (j == 0))
    def _init_ga():
        ga_ref[slot] = jnp.float32(0.0)

    @pl.when(v.active & v.nonempty)
    def _accumulate():
        cx = jnp.where(v.rows(bm),
                       _codes(x_ref[...].astype(jnp.float32), beta, fmt), 0.0)
        gw_ref[...] += jax.lax.dot_general(
            cx.astype(mxu), g_ref[...].astype(mxu), _TN,
            preferred_element_type=jnp.float32)

    @pl.when(v.active & v.last_of_group())
    def _epilogue():
        acc = gw_ref[...] * _code_scale(beta, fmt)
        gw, partial = _ste_epilogue(
            acc, w_ref[...].astype(jnp.float32),
            jnp.maximum(alpha_ref[slot], _ALPHA_FLOOR), fmt)
        ga_ref[slot] += partial
        gw_ref[...] = gw


_SMEM = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)


def _gmm_prep(kernel, blocks, x, w, offsets, *cotangents):
    """Blocks, zero-padded operands and the visit schedule of a batched
    call: ``x`` (B, M, K), ``w`` (B, G, K, N), ``offsets`` (B, G + 1)."""
    _, m, k = x.shape
    n = w.shape[3]
    bm, bk, bn = _blocks(kernel, (m, k, n), blocks, x, *cotangents)
    xp = jnp.pad(x, ((0, 0), (0, (-m) % bm), (0, (-k) % bk)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, (-k) % bk), (0, (-n) % bn)))
    m_tiles = xp.shape[1] // bm
    offsets = offsets.astype(jnp.int32)
    gids, mids, nv = jax.vmap(
        functools.partial(_gmm_schedule, m_tiles=m_tiles, bm=bm))(offsets)
    prefetch = (gids.reshape(-1), mids.reshape(-1), offsets.reshape(-1), nv)
    return (bm, bk, bn), xp, wp, prefetch, jnp.max(nv), gids.shape[1]


def _gmm_rows_out(out, offsets, m: int):
    """The first ``m`` rows of each entry's output, zero past its groups'
    end (rows no visit wrote)."""
    rows = jnp.arange(m, dtype=jnp.int32)[None, :, None]
    return jnp.where(rows < offsets[:, -1:, None], out[:, :m], 0)


def _clips(beta, alpha):
    return (beta.astype(jnp.float32).reshape(-1),
            alpha.astype(jnp.float32).reshape(-1))


_GMM_STATIC = ("fmt", "bm", "bk", "bn", "interpret")


@functools.partial(jax.jit, static_argnames=_GMM_STATIC)
def _qat_gmm_batched(x, w, beta, alpha, offsets, fmt=E4M3, bm=None, bk=None,
                     bn=None, interpret=True):
    m = x.shape[1]
    n_groups, n = w.shape[1], w.shape[3]
    (bm, bk, bn), xp, wp, prefetch, n_visits, bound = _gmm_prep(
        "qat_gmm", (bm, bk, bn), x, w, offsets)
    kp, np_ = wp.shape[2:]
    n_k = kp // bk
    grid = (x.shape[0], np_ // bn, n_visits, n_k)

    def rows(b, j, t, kk, gi, mi, o, nv):
        return b, mi[b * bound + t], _frozen(t, nv, b, kk, n_k - 1)

    def weight(b, j, t, kk, gi, mi, o, nv):
        return b, gi[b * bound + t], _frozen(t, nv, b, kk, n_k - 1), j

    def out(b, j, t, kk, gi, mi, o, nv):
        return b, mi[b * bound + t], j

    res = pl.pallas_call(
        functools.partial(_qat_gmm_kernel, fmt=fmt, n_k=n_k, bm=bm,
                          n_groups=n_groups, bound=bound),
        name="qat_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((None, bm, bk), rows),
                      pl.BlockSpec((None, None, bk, bn), weight),
                      _SMEM(), _SMEM()],
            out_specs=pl.BlockSpec((None, bm, bn), out),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], xp.shape[1], np_),
                                       x.dtype),
        interpret=interpret,
    )(*prefetch, xp, wp, *_clips(beta, alpha))
    return _gmm_rows_out(res[:, :, :n], offsets, m)


@functools.partial(jax.jit, static_argnames=_GMM_STATIC)
def _qat_gmm_dx_batched(g, x, w, beta, alpha, offsets, fmt=E4M3, bm=None,
                        bk=None, bn=None, interpret=True):
    m, k = x.shape[1:]
    n_groups = w.shape[1]
    (bm, bk, bn), xp, wp, prefetch, n_visits, bound = _gmm_prep(
        "qat_gmm_dx", (bm, bk, bn), x, w, offsets, g)
    gp = jnp.pad(g, ((0, 0), (0, xp.shape[1] - m), (0, wp.shape[3]
                                                     - g.shape[2])))
    kp, np_ = wp.shape[2:]
    n_j = np_ // bn
    grid = (x.shape[0], kp // bk, n_visits, n_j)

    def cot(b, kk, t, j, gi, mi, o, nv):
        return b, mi[b * bound + t], _frozen(t, nv, b, j, n_j - 1)

    def weight(b, kk, t, j, gi, mi, o, nv):
        return b, gi[b * bound + t], kk, _frozen(t, nv, b, j, n_j - 1)

    def rows(b, kk, t, j, gi, mi, o, nv):
        return b, mi[b * bound + t], kk

    gx, gb = pl.pallas_call(
        functools.partial(_qat_gmm_dx_kernel, fmt=fmt, n_j=n_j, bm=bm,
                          mxu=mxu_dtype(g.dtype), n_groups=n_groups,
                          bound=bound),
        name="qat_gmm_dx",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((None, bm, bn), cot),
                      pl.BlockSpec((None, None, bk, bn), weight),
                      pl.BlockSpec((None, bm, bk), rows),
                      _SMEM(), _SMEM()],
            out_specs=[pl.BlockSpec((None, bm, bk), rows), _SMEM()],
            scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], xp.shape[1], kp), x.dtype),
            jax.ShapeDtypeStruct((x.shape[0],), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch, gp, wp, xp, *_clips(beta, alpha))
    return _gmm_rows_out(gx[:, :, :k], offsets, m), gb.reshape(beta.shape)


@functools.partial(jax.jit, static_argnames=_GMM_STATIC)
def _qat_gmm_dw_batched(g, x, w, beta, alpha, offsets, fmt=E4M3, bm=None,
                        bk=None, bn=None, interpret=True):
    m, k = x.shape[1:]
    n_groups, n = w.shape[1], w.shape[3]
    (bm, bk, bn), xp, wp, prefetch, n_visits, bound = _gmm_prep(
        "qat_gmm_dw", (bm, bk, bn), x, w, offsets, g)
    gp = jnp.pad(g, ((0, 0), (0, xp.shape[1] - m), (0, wp.shape[3] - n)))
    kp, np_ = wp.shape[2:]
    grid = (x.shape[0], kp // bk, np_ // bn, n_visits)

    def cot(b, kk, j, t, gi, mi, o, nv):
        return b, mi[b * bound + t], j

    def rows(b, kk, j, t, gi, mi, o, nv):
        return b, mi[b * bound + t], kk

    def weight(b, kk, j, t, gi, mi, o, nv):
        return b, gi[b * bound + t], kk, j

    gw, ga = pl.pallas_call(
        functools.partial(_qat_gmm_dw_kernel, fmt=fmt, bm=bm,
                          mxu=mxu_dtype(g.dtype), n_groups=n_groups,
                          bound=bound),
        name="qat_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((None, bm, bn), cot),
                      pl.BlockSpec((None, bm, bk), rows),
                      pl.BlockSpec((None, None, bk, bn), weight),
                      _SMEM(), _SMEM()],
            out_specs=[pl.BlockSpec((None, None, bk, bn), weight), _SMEM()],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], n_groups, kp, np_), jnp.float32),
            jax.ShapeDtypeStruct((x.shape[0] * n_groups,), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch, gp, xp, wp, *_clips(beta, alpha))
    return gw[:, :, :k, :n].astype(w.dtype), ga.reshape(alpha.shape)


def _batchable(launch, **statics):
    """``launch`` (a batched kernel call) as a function of one entry's
    operands, whose ``vmap`` calls ``launch`` on the whole batch."""
    run = functools.partial(launch, **statics)

    @jax.custom_batching.custom_vmap
    def one(*args):
        return jax.tree.map(lambda o: o[0], run(*(a[None] for a in args)))

    @one.def_vmap
    def _batched(axis_size, in_batched, *args):
        args = [a if batched else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, batched in zip(args, in_batched)]
        out = run(*args)
        return out, jax.tree.map(lambda _: True, out)

    return one


def qat_gmm(
    x: jax.Array,        # (M, K) rows sorted by group
    w: jax.Array,        # (G, K, N)
    beta: jax.Array,     # activation clip (scalar)
    alpha: jax.Array,    # (G,) weight clips
    offsets: jax.Array,  # (G + 1,) int32 group row offsets, offsets[0] == 0
    fmt: FP8Format = E4M3,
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """Row r of group e: ``Q(x[r]; beta) @ Q(w[e]; alpha[e])``; rows past
    ``offsets[-1]`` are zero. Output in ``x``'s dtype."""
    return _batchable(_qat_gmm_batched, fmt=fmt, bm=bm, bk=bk, bn=bn,
                      interpret=interpret)(x, w, beta, alpha, offsets)


def qat_gmm_dx(
    g: jax.Array,        # (M, N) upstream cotangent
    x: jax.Array,        # (M, K)
    w: jax.Array,        # (G, K, N)
    beta: jax.Array,
    alpha: jax.Array,    # (G,)
    offsets: jax.Array,  # (G + 1,)
    fmt: FP8Format = E4M3,
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Backward of :func:`qat_gmm` wrt the rows: ``(dL/dx, dL/dbeta)``;
    rows past ``offsets[-1]`` get zero."""
    return _batchable(_qat_gmm_dx_batched, fmt=fmt, bm=bm, bk=bk, bn=bn,
                      interpret=interpret)(g, x, w, beta, alpha, offsets)


def qat_gmm_dw(
    g: jax.Array,        # (M, N) upstream cotangent
    x: jax.Array,        # (M, K)
    w: jax.Array,        # (G, K, N)
    beta: jax.Array,
    alpha: jax.Array,    # (G,)
    offsets: jax.Array,  # (G + 1,)
    fmt: FP8Format = E4M3,
    bm: int | None = None,
    bk: int | None = None,
    bn: int | None = None,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Backward of :func:`qat_gmm` wrt the weights: ``(dL/dw, dL/dalpha)``,
    each group's from its own rows (zero for an empty group)."""
    return _batchable(_qat_gmm_dw_batched, fmt=fmt, bm=bm, bk=bk, bn=bn,
                      interpret=interpret)(g, x, w, beta, alpha, offsets)
