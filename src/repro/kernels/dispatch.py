"""Backend-aware kernel dispatch: one entry point per FP8 hot-path op.

This module is the single seam between the model/federated code and the
Pallas kernels. Callers (``core.qat.wq``/``aq``, ``core.wire``,
``models.common.dense``) never touch ``pallas_call`` directly — they call
the dispatchers here, which pick an execution path per op:

* ``pallas``    — compiled Pallas kernels (Mosaic on TPU). The QAT forward
  *and* backward run as fused kernels: one HBM read + write per element for
  the quantizers, quantize-in-VMEM for the matmul. This is the production
  path; it is selected automatically when ``jax.default_backend()`` is TPU.
* ``interpret`` — the same Pallas kernels under ``interpret=True``. The
  kernel bodies execute exactly (bit-for-bit what Mosaic would compute
  modulo 1-ULP transcendental differences), which is what the CPU
  correctness/parity tests validate. Selected only by explicit request —
  it is far slower than jnp on CPU.
* ``jnp``       — the unfused jnp reference chain from ``core.fp8`` with
  native STE autodiff. Selected automatically on CPU/GPU hosts, where no
  Mosaic backend exists and interpret mode would be pure overhead.

Selection: ``REPRO_KERNEL_BACKEND`` ∈ {``auto`` (default), ``pallas``,
``interpret``, ``jnp``}. ``auto`` resolves to ``pallas`` on TPU and ``jnp``
elsewhere. The variable is read at *trace* time, so a jitted train step
bakes in whichever path was active when it was traced.

Gradients: the kernel-backed ops carry a ``jax.custom_vjp`` implementing
the paper's straight-through estimator exactly as jnp autodiff derives it
from ``core.fp8.quantize_det`` (round/floor pass-through, exponent term
stop-gradded, clip gradient routed to the clipping value, plus the
``(q - y) * s / alpha`` scale term from the differentiable exponent bias).
Parity with the jnp autodiff oracle is enforced to <= 1e-5 relative by
``tests/test_dispatch_vjp.py``. Ops that fall back to jnp use jnp autodiff
natively, so CPU training is bitwise-unchanged by this module. One
measure-zero convention difference: at an element sitting EXACTLY on the
clip boundary (|x| == alpha, e.g. the max weight right after the
alpha = max|w| init), ``jnp.clip`` autodiff tie-splits the subgradient
(0.5 to x, 0.5 routed to alpha) while the kernels use the closed-form
mask (1 to x) — gone after the first optimizer step.

Shape contract: the fused quantizers require a single (per-tensor) clipping
scalar; stacked per-layer clipping values of shape ``(L, 1, ..., 1)``
dispatch to jnp (inside ``lax.scan`` over layers each slice is a scalar, so
the scanned models do hit the kernels on TPU). The grouped QAT matmul of
the sparse experts takes its per-expert clips ``(G,)`` itself
(:func:`qat_gmm`), so an expert stack's ``(E, 1, 1)`` clips route nothing
to jnp. A region that GSPMD partitions over a mesh traces on jnp too
(:func:`gspmd_partitioned`). Both routes are static, decided at trace
time and counted in ``JNP_ROUTES``; no dispatcher tries a kernel and
falls back when it fails.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core import fp8
from ..core.fp8 import E4M3, FP8Format
from . import fp8_matmul, fp8_quant

Array = jax.Array

BACKENDS = ("auto", "pallas", "interpret", "jnp")
_ENV = "REPRO_KERNEL_BACKEND"


# Trace-time counts of ops a kernel backend sent to jnp, by reason:
# ``stacked_clip:<op>`` — a quantizer whose clip is stacked (L, 1, ..., 1);
# ``gspmd_region`` — a region traced under :func:`gspmd_partitioned`.
JNP_ROUTES: collections.Counter = collections.Counter()

# Trace-time counts of the fused QAT-matmul kernels traced, dense and
# grouped, by kernel and the dtype of their MXU operands:
# ``qat_matmul:bfloat16``, ``qat_matmul_dx:float32``,
# ``qat_gmm_dw:bfloat16`` (see ``fp8_matmul.mxu_dtype``).
QAT_MXU: collections.Counter = collections.Counter()

_GSPMD = contextvars.ContextVar("repro_gspmd_region", default=False)


def backend() -> str:
    """Resolve the active kernel backend (reads ``REPRO_KERNEL_BACKEND``)."""
    choice = os.environ.get(_ENV, "auto").lower()
    if choice not in BACKENDS:
        raise ValueError(
            f"{_ENV}={choice!r}; expected one of {BACKENDS}"
        )
    if choice == "auto":
        choice = "pallas" if jax.default_backend() == "tpu" else "jnp"
    return choice


@contextlib.contextmanager
def gspmd_partitioned():
    """Trace a region that GSPMD partitions over a device mesh.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so inside the region the ``pallas``
    backend's dispatchers (:func:`use_kernels`) trace the jnp ops instead;
    :func:`backend` still reports ``pallas``, and the interpret and jnp
    backends lower to plain HLO and are unchanged. The choice is static,
    made at trace time, and counted in ``JNP_ROUTES["gspmd_region"]``.
    """
    if backend() == "pallas":
        JNP_ROUTES["gspmd_region"] += 1
    token = _GSPMD.set(True)
    try:
        yield
    finally:
        _GSPMD.reset(token)


def _pallas_opts() -> tuple[bool, bool]:
    """(use Pallas kernels, interpret mode) for the active backend.

    ``interpret`` is True for every backend except real TPU ``pallas`` so
    that the kernel-backed custom-VJP functions stay runnable even when
    invoked directly (e.g. via ``kernels.ops``) on a CPU host.
    """
    be = backend()
    if be == "pallas" and _GSPMD.get():
        return False, False
    return be in ("pallas", "interpret"), be != "pallas"


def use_kernels() -> bool:
    """Whether the dispatchers run the Pallas kernels here (False on the
    jnp backend and inside a :func:`gspmd_partitioned` region on TPU)."""
    return _pallas_opts()[0]


def _quant_kernel_ok(x, alpha, op: str) -> bool:
    if jnp.size(alpha) != 1:
        JNP_ROUTES["stacked_clip:" + op] += 1
        return False
    return jnp.ndim(x) >= 1


def _matmul_kernel_ok(x, w, beta, alpha) -> bool:
    return (
        jnp.ndim(x) == 2 and jnp.ndim(w) == 2
        and jnp.size(beta) == 1 and jnp.size(alpha) == 1
    )


# ---------------------------------------------------------------------------
# Shared jnp helpers for the codec fallback paths
# ---------------------------------------------------------------------------


def _rand_with_bits_jnp(x, alpha, bits, fmt: FP8Format):
    """Q_rand with explicit uint32 bits — bit-exact with the Pallas kernel."""
    a = jnp.maximum(alpha, fp8._ALPHA_FLOOR).astype(jnp.float32)
    xc = jnp.clip(x.astype(jnp.float32), -a, a)
    b = fp8.exponent_bias(a, fmt)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.where(p > 1.0, p, 1.0)
    s = jnp.exp2(p - b - fmt.mant)
    y = xc / s
    fl = jnp.floor(y)
    u = fp8_quant.uniform_from_bits(bits)
    q = fl + (u < (y - fl)).astype(jnp.float32)
    return (s * q).astype(x.dtype)


def _zero_bits_cotangent(bits):
    return np.zeros(np.shape(bits), dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# Q_det — kernel-backed custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _quant_det_kernel_ste(x, alpha, fmt):
    _, interp = _pallas_opts()
    return fp8_quant.quant_det(x, alpha, fmt=fmt, interpret=interp)


def _quant_det_fwd(x, alpha, fmt):
    return _quant_det_kernel_ste(x, alpha, fmt), (x, alpha)


def _quant_det_bwd(fmt, res, g):
    x, alpha = res
    _, interp = _pallas_opts()
    gx, ga = fp8_quant.quant_det_bwd(x, alpha, g, fmt=fmt, interpret=interp)
    return gx, ga.astype(jnp.float32)


_quant_det_kernel_ste.defvjp(_quant_det_fwd, _quant_det_bwd)


def quantize_det(x: Array, alpha: Array, fmt: FP8Format = E4M3) -> Array:
    """Deterministic FP8 fake-quant, dispatched (see module docstring)."""
    use, _ = _pallas_opts()
    if use and _quant_kernel_ok(x, alpha, "quantize_det"):
        return _quant_det_kernel_ste(x, alpha, fmt)
    return fp8.quantize_det(x, alpha, fmt)


# ---------------------------------------------------------------------------
# Q_rand — kernel-backed custom VJP over explicit random bits
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _quant_rand_kernel_ste(x, alpha, bits, fmt):
    _, interp = _pallas_opts()
    return fp8_quant.quant_rand(x, alpha, bits, fmt=fmt, interpret=interp)


def _quant_rand_fwd(x, alpha, bits, fmt):
    return _quant_rand_kernel_ste(x, alpha, bits, fmt), (x, alpha, bits)


def _quant_rand_bwd(fmt, res, g):
    x, alpha, bits = res
    _, interp = _pallas_opts()
    gx, ga = fp8_quant.quant_rand_bwd(
        x, alpha, bits, g, fmt=fmt, interpret=interp
    )
    return gx, ga.astype(jnp.float32), _zero_bits_cotangent(bits)


_quant_rand_kernel_ste.defvjp(_quant_rand_fwd, _quant_rand_bwd)


def quantize_rand(
    x: Array, alpha: Array, key: Array, fmt: FP8Format = E4M3
) -> Array:
    """Stochastic (unbiased) FP8 quantization, dispatched.

    Randomness is drawn from ``jax.random`` *outside* any kernel, so the
    kernel stays deterministic given its inputs. NOTE: the kernel path and
    ``fp8.quantize_rand`` derive their uniforms differently from ``key``
    (raw bits vs ``jax.random.uniform``) — identically distributed, not
    bitwise identical.
    """
    use, _ = _pallas_opts()
    if use and _quant_kernel_ok(x, alpha, "quantize_rand"):
        bits = jax.random.bits(key, shape=jnp.shape(x), dtype=jnp.uint32)
        return _quant_rand_kernel_ste(x, alpha, bits, fmt)
    return fp8.quantize_rand(x, alpha, key, fmt)


# ---------------------------------------------------------------------------
# Fused QAT matmul — kernel-backed custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _qat_matmul_kernel_ste(x, w, beta, alpha, fmt):
    _, interp = _pallas_opts()
    QAT_MXU["qat_matmul:" + fp8_matmul.CODE_DTYPE.name] += 1
    return fp8_matmul.qat_matmul(x, w, beta, alpha, fmt=fmt, interpret=interp)


def _qat_matmul_fwd(x, w, beta, alpha, fmt):
    return _qat_matmul_kernel_ste(x, w, beta, alpha, fmt), (x, w, beta, alpha)


def _qat_matmul_bwd(fmt, res, g):
    x, w, beta, alpha = res
    _, interp = _pallas_opts()
    mxu = fp8_matmul.mxu_dtype(g.dtype).name
    QAT_MXU["qat_matmul_dx:" + mxu] += 1
    QAT_MXU["qat_matmul_dw:" + mxu] += 1
    gx, gb = fp8_matmul.qat_matmul_dx(
        g, x, w, beta, alpha, fmt=fmt, interpret=interp
    )
    gw, ga = fp8_matmul.qat_matmul_dw(
        g, x, w, beta, alpha, fmt=fmt, interpret=interp
    )
    return gx, gw, gb.astype(jnp.float32), ga.astype(jnp.float32)


_qat_matmul_kernel_ste.defvjp(_qat_matmul_fwd, _qat_matmul_bwd)


def qat_matmul(
    x: Array, w: Array, beta: Array, alpha: Array, fmt: FP8Format = E4M3
) -> Array:
    """``Q_det(x; beta) @ Q_det(w; alpha)`` with f32 accumulation, dispatched.

    The result has ``x``'s dtype. On the Pallas path both operand tiles
    quantize in VMEM right before the MXU, which multiplies their
    integer-valued FP8 codes in one bf16 pass and scales the f32
    accumulation by the two clips' code scales (``kernels/fp8_matmul.py``);
    the backward runs the fused dx/dw kernels, which feed the MXU the
    cotangent in its own dtype beside the codes: bf16 for a bf16 ``x``
    (whose cotangent is bf16), f32 for an f32 one. Each kernel traced is
    counted in ``QAT_MXU``. On the jnp path this is the plain composition
    in f32 with native autodiff.
    """
    use, _ = _pallas_opts()
    if use and _matmul_kernel_ok(x, w, beta, alpha):
        return _qat_matmul_kernel_ste(x, w, beta, alpha, fmt)
    out = jnp.dot(
        fp8.quantize_det(x.astype(jnp.float32), beta, fmt),
        fp8.quantize_det(w.astype(jnp.float32), alpha, fmt),
        preferred_element_type=jnp.float32,
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Grouped QAT matmul (sparse experts) — kernel-backed custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _qat_gmm_kernel_ste(x, w, beta, alpha, offsets, fmt):
    _, interp = _pallas_opts()
    QAT_MXU["qat_gmm:" + fp8_matmul.CODE_DTYPE.name] += 1
    return fp8_matmul.qat_gmm(x, w, beta, alpha, offsets, fmt=fmt,
                              interpret=interp)


def _qat_gmm_fwd(x, w, beta, alpha, offsets, fmt):
    return (_qat_gmm_kernel_ste(x, w, beta, alpha, offsets, fmt),
            (x, w, beta, alpha, offsets))


def _qat_gmm_bwd(fmt, res, g):
    x, w, beta, alpha, offsets = res
    _, interp = _pallas_opts()
    mxu = fp8_matmul.mxu_dtype(g.dtype).name
    QAT_MXU["qat_gmm_dx:" + mxu] += 1
    QAT_MXU["qat_gmm_dw:" + mxu] += 1
    gx, gb = fp8_matmul.qat_gmm_dx(g, x, w, beta, alpha, offsets, fmt=fmt,
                                   interpret=interp)
    gw, ga = fp8_matmul.qat_gmm_dw(g, x, w, beta, alpha, offsets, fmt=fmt,
                                   interpret=interp)
    return (gx, gw, gb.astype(jnp.float32), ga.astype(jnp.float32),
            _zero_bits_cotangent(offsets))


_qat_gmm_kernel_ste.defvjp(_qat_gmm_fwd, _qat_gmm_bwd)


def gmm(x: Array, w: Array, offsets: Array) -> Array:
    """Grouped matmul in jnp: row r of group e is ``x[r] @ w[e]``, rows
    past ``offsets[-1]`` zero, accumulated in f32.

    The rows are cut into tiles of ``ceil(M / 2G)``, and each group
    multiplies the tiles it covers by its own weight, masked to its rows:
    the kernels' visit schedule, at most ``2G + G - 1`` visits of one tile
    each. So the work is at most 1.5 times that of the M rows whatever the
    groups' sizes, and each visit gathers its group's weight.
    """
    m, g, n = x.shape[0], w.shape[0], w.shape[2]
    bm = -(-m // (2 * g))
    tiles = -(-m // bm)
    xt = jnp.pad(x, ((0, tiles * bm - m), (0, 0))).reshape(tiles, bm, -1)
    offsets = offsets.astype(jnp.int32)
    gids, mids, n_visits = fp8_matmul._gmm_schedule(offsets, tiles, bm)
    rows = mids[:, None] * bm + jnp.arange(bm, dtype=jnp.int32)
    own = ((jnp.arange(gids.shape[0]) < n_visits)[:, None]
           & (rows >= offsets[gids][:, None])
           & (rows < offsets[gids + 1][:, None]))
    part = jnp.einsum("vmk,vkn->vmn",
                      jnp.where(own[..., None], xt[mids], 0), w[gids],
                      preferred_element_type=jnp.float32)
    out = jnp.zeros((tiles, bm, n), jnp.float32).at[mids].add(part)
    return out.reshape(tiles * bm, n)[:m]


def qat_gmm(
    x: Array, w: Array, beta: Array, alpha: Array, offsets: Array,
    fmt: FP8Format = E4M3,
) -> Array:
    """Grouped ``Q_det(x[r]; beta) @ Q_det(w[e]; alpha[e])`` for the rows r
    of each group e (``offsets``: (G + 1,) row offsets from 0, rows sorted
    by group; rows past the last group's end give zero), on the kernels.

    One kernel per pass (``kernels/fp8_matmul.py`` ``qat_gmm``, ``_dx``,
    ``_dw``) fake-quantizes the operand tiles in VMEM as :func:`qat_matmul`
    does, with each group's own weight clip; the backward returns the
    activation clip's and every group's weight clip's cotangents. Each
    kernel traced is counted in ``QAT_MXU``. The kernels run interpreted
    off the chip; the model calls this only where :func:`use_kernels`
    holds, and composes :func:`gmm` with the quantizers elsewhere.
    """
    return _qat_gmm_kernel_ste(x, w, beta, alpha, offsets, fmt)


# ---------------------------------------------------------------------------
# Flat-buffer wire codec entry points (quantize + bit-pack fused)
# ---------------------------------------------------------------------------


def quant_pack_tiles(
    x2: Array,                   # (R, LANE) wire tile layout (see core.wire)
    a2: Array,                   # (R, LANE) per-element clipping values
    key2: Array | None = None,   # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format = E4M3,
) -> Array:
    """Quantize+pack the wire tile layout into uint8 codes, one launch.

    Stochastic rounding uses the in-kernel counter RNG
    (``fp8_quant.counter_bits``); the jnp fallback evaluates the identical
    integer hash, so codes are bit-identical across backends.
    """
    use, interp = _pallas_opts()
    if use:
        return fp8_quant.quant_pack_tiles(
            x2, a2, key2, fmt=fmt, interpret=interp
        )
    return _quant_codes_jnp(x2, a2, key2, fmt)


def _quant_codes_jnp(x2, a2, key2, fmt: FP8Format):
    """Shared jnp fallback quantize-to-codes: the ONE owner of the
    counter-RNG derivation that keeps fallback payloads bit-identical to
    the kernels, for the 1-byte and sub-byte wires alike."""
    if key2 is None:
        q = fp8.quantize_det(x2, a2, fmt)
    else:
        k2 = key2.astype(jnp.uint32)
        bits2 = fp8_quant._tile_counter_bits(
            jnp.uint32(0), x2.shape, k2[0], k2[1]
        )
        q = _rand_with_bits_jnp(x2, a2, bits2, fmt)
    return fp8.pack_fp8(q, a2, fmt)


def unpack_tiles(c2: Array, a2: Array, fmt: FP8Format = E4M3) -> Array:
    """Decode (R, LANE) uint8 code tiles back to f32 grid values."""
    use, interp = _pallas_opts()
    if use:
        return fp8_quant.unpack_tiles(c2, a2, fmt=fmt, interpret=interp)
    return fp8.unpack_fp8(c2, a2, fmt).astype(jnp.float32)


def quant_pack_sub_tiles(
    x2: Array,                   # (R, LANE) wire tile layout
    a2: Array,                   # (R, 1) or (R, LANE) clipping values
    key2: Array | None = None,   # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format | None = None,
) -> Array:
    """Quantize+pack at ``8 // fmt.bits`` codes per byte (sub-byte formats).

    :func:`quant_pack_tiles` produces the codes (same counter-RNG contract)
    and ``fold_codes`` assembles the bytes, on every backend — so packed
    payloads are bit-identical across backends.
    """
    return fp8_quant.fold_codes(quant_pack_tiles(x2, a2, key2, fmt), fmt)


def unpack_sub_tiles(c2: Array, a2: Array, fmt: FP8Format | None = None) -> Array:
    """Decode sub-byte packed code tiles back to (R, LANE) f32 grid values."""
    code = fp8_quant.unfold_codes(c2, fmt).astype(jnp.uint8)
    return unpack_tiles(code, a2, fmt)


def _rowmax_jnp(x2):
    return jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=1, keepdims=True)


def quant_pack_amax_tiles(
    x2: Array,                   # (R, LANE) wire tile layout
    a2: Array,                   # (R, 1) or (R, LANE) clipping values
    key2: Array | None = None,   # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format = E4M3,
) -> tuple[Array, Array]:
    """:func:`quant_pack_tiles` + per-row raw amax from the SAME launch.

    Delayed scaling's history update (``core.scaling.DelayedScaling``)
    consumes the ``(R, 1)`` rowmax — computed as a byproduct of the
    quantize kernel, never as a standalone reduction. Codes are
    bit-identical to :func:`quant_pack_tiles`.
    """
    use, interp = _pallas_opts()
    if use:
        return fp8_quant.quant_pack_amax_tiles(
            x2, a2, key2, fmt=fmt, interpret=interp
        )
    return _quant_codes_jnp(x2, a2, key2, fmt), _rowmax_jnp(x2)


def quant_pack_sub_amax_tiles(
    x2: Array,                   # (R, LANE) wire tile layout
    a2: Array,                   # (R, 1) or (R, LANE) clipping values
    key2: Array | None = None,   # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format | None = None,
) -> tuple[Array, Array]:
    """Sub-byte :func:`quant_pack_sub_tiles` + fused per-row raw amax."""
    codes, rowmax = quant_pack_amax_tiles(x2, a2, key2, fmt)
    return fp8_quant.fold_codes(codes, fmt), rowmax


# ---------------------------------------------------------------------------
# Parameter-plane entry points (see core.plane): fused tiled Q_det with a
# custom VJP, and a differentiable quantize-dequantize for the UQ+ server
# optimizer. Alpha is the plane's per-ROW column (R, 1); the bwd returns the
# per-row alpha cotangent, and the caller's gather transpose segment-sums it
# back to each leaf's scalar (or stacked per-layer) alpha.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _quant_det_plane_kernel_ste(x2, a_col, fmt):
    _, interp = _pallas_opts()
    return fp8_quant.quant_det_tiles(x2, a_col, fmt=fmt, interpret=interp)


def _quant_det_plane_fwd(x2, a_col, fmt):
    return _quant_det_plane_kernel_ste(x2, a_col, fmt), (x2, a_col)


def _quant_det_plane_bwd(fmt, res, g):
    x2, a_col = res
    _, interp = _pallas_opts()
    gx, ga_row = fp8_quant.quant_det_tiles_bwd(
        x2, a_col, g, fmt=fmt, interpret=interp
    )
    return gx, ga_row


_quant_det_plane_kernel_ste.defvjp(_quant_det_plane_fwd, _quant_det_plane_bwd)


def quant_det_plane(x2: Array, a_col: Array, fmt: FP8Format = E4M3) -> Array:
    """One-launch Q_det on the (R, LANE) plane with per-row alpha column.

    Kernel backends run the fused forward/backward tile pair; the jnp
    fallback broadcasts ``core.fp8.quantize_det`` over the plane, whose
    native autodiff reduces the alpha cotangent to the same (R, 1) column.
    """
    use, _ = _pallas_opts()
    if use:
        return _quant_det_plane_kernel_ste(x2, a_col, fmt)
    return fp8.quantize_det(x2, a_col, fmt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fake_quant_plane(x2, a_col, key2, fmt):
    """Differentiable one-launch Q_rand-transit on the plane (STE grads).

    Same forward as :func:`fake_quant_tiles` (counter RNG, so the draw is
    reproducible across backends); the backward applies the paper's STE —
    clip mask to the tiles, clip routing + scale term per row to the alpha
    column — computed elementwise from the saved forward output, since
    ``(q - y) * s == q_val - clip(x)`` needs no random-bit replay.
    """
    return fake_quant_tiles(x2, a_col, key2, fmt=fmt)


def _fake_quant_plane_fwd(x2, a_col, key2, fmt):
    q = fake_quant_plane(x2, a_col, key2, fmt)
    return q, (x2, a_col, key2, q)


def _fake_quant_plane_bwd(fmt, res, g):
    x2, a_col, key2, q = res
    a = jnp.maximum(a_col, fp8._ALPHA_FLOOR)
    inside = (jnp.abs(x2) <= a).astype(jnp.float32)
    xc = jnp.clip(x2, -a, a)
    gx = g * inside
    ga_row = jnp.sum(
        g * (jnp.sign(x2) * (1.0 - inside) + (q - xc) / a),
        axis=1, keepdims=True,
    )
    return gx, ga_row, _zero_bits_cotangent(key2)


fake_quant_plane.defvjp(_fake_quant_plane_fwd, _fake_quant_plane_bwd)


def fake_quant_tiles(
    x2: Array,                   # (R, LANE) wire tile layout
    a2: Array,                   # (R, LANE) per-element clipping values
    key2: Array | None = None,   # (2,) u32 key -> stochastic; None -> det
    fmt: FP8Format = E4M3,
) -> Array:
    """One-launch quantize-dequantize (simulated wire transit, f32 out).

    Equal to ``unpack_tiles(quant_pack_tiles(...))`` within 1 float32 ULP
    (same FP8 grid point either way) without materializing the codes.
    """
    use, interp = _pallas_opts()
    if use:
        return fp8_quant.fake_quant_tiles(
            x2, a2, key2, fmt=fmt, interpret=interp
        )
    return fp8_quant.fake_quant_tiles_jnp(x2, a2, key2, fmt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fake_quant_amax_plane(x2, a_col, key2, fmt):
    """:func:`fake_quant_plane` + fused per-row raw amax, differentiable.

    Forward returns ``(q (R, LANE), rowmax (R, 1))`` from one launch; the
    backward is the SAME STE as ``fake_quant_plane`` (the amax output is a
    monitoring byproduct — its cotangent is ignored, matching TE's
    non-differentiable amax history).
    """
    use, interp = _pallas_opts()
    if use:
        return fp8_quant.fake_quant_amax_tiles(
            x2, a_col, key2, fmt=fmt, interpret=interp
        )
    return fp8_quant.fake_quant_amax_tiles_jnp(x2, a_col, key2, fmt)


def _fake_quant_amax_plane_fwd(x2, a_col, key2, fmt):
    q, mx = fake_quant_amax_plane(x2, a_col, key2, fmt)
    return (q, mx), (x2, a_col, key2, q)


def _fake_quant_amax_plane_bwd(fmt, res, g):
    x2, a_col, key2, q = res
    gq, _g_amax = g
    a = jnp.maximum(a_col, fp8._ALPHA_FLOOR)
    inside = (jnp.abs(x2) <= a).astype(jnp.float32)
    xc = jnp.clip(x2, -a, a)
    gx = gq * inside
    ga_row = jnp.sum(
        gq * (jnp.sign(x2) * (1.0 - inside) + (q - xc) / a),
        axis=1, keepdims=True,
    )
    return gx, ga_row, _zero_bits_cotangent(key2)


fake_quant_amax_plane.defvjp(_fake_quant_amax_plane_fwd,
                             _fake_quant_amax_plane_bwd)
