"""FP8 quantization core (paper Eq. 2-3, Kuzmin et al. flexible exponent bias).

Implements the two quantizers the paper is built on:

* ``quantize_det``  — deterministic round-to-nearest onto the FP8 grid.
  Used for on-device QAT (Remark 4: smaller error norm).
* ``quantize_rand`` — stochastic rounding, *unbiased* (Lemma 3).
  Used for all client<->server model communication (Remark 3).

Both take a per-tensor clipping value ``alpha`` (the max representable
magnitude) and derive the flexible exponent bias ``b`` from it:

    b = 2^e - log2(alpha) + log2(2 - 2^-m) - 1            (paper, after Eq. 2)

and the per-element scale (paper Eq. 2):

    log2 s_i = ( floor(log2|x_i| + b)  if floor(log2|x_i| + b) > 1
                 1                     otherwise )  - b - m

Gradients follow the straight-through estimator: ``round``/``floor`` of the
mantissa pass gradient 1; the exponent term ``floor(log2|x_i| + b)`` is
treated as a *constant* (stop_gradient), per Kuzmin et al.; clipping routes
gradient to ``alpha`` on saturated elements (via ``jnp.clip`` autodiff).

Everything is expressible with plain jnp + ``stop_gradient`` so normal JAX
autodiff produces exactly the paper's STE — no custom_vjp required.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
PyTree = Any

_ALPHA_FLOOR = 1e-12  # numerical guard: alpha must stay strictly positive


@dataclasses.dataclass(frozen=True)
class FP8Format:
    """A short float format: 1 sign bit, ``exp`` exponent bits, ``mant`` mantissa bits."""

    exp: int = 4
    mant: int = 3

    @property
    def bits(self) -> int:
        return 1 + self.exp + self.mant

    @property
    def mant_scale(self) -> float:
        """2 - 2^-m : ratio of the max mantissa value to 2^m."""
        return 2.0 - 2.0 ** (-self.mant)

    @property
    def max_exp_code(self) -> int:
        """Largest biased-exponent value p = floor(log2|x|+b) on the grid."""
        return 2 ** self.exp - 1


E4M3 = FP8Format(exp=4, mant=3)
E5M2 = FP8Format(exp=5, mant=2)

# Sub-byte ExMy formats (Noune et al., *8-bit Numerical Formats for DNNs*,
# sweep the exponent/mantissa split below 8 bits). Every function in this
# module is parameterized by (exp, mant), so the 4-bit grids come for free;
# the *wire* packing of 2 codes/byte lives in
# ``kernels.dispatch.quant_pack_sub_tiles``, behind
# ``core.codec.PackedFpCodec``.
FP4_E2M1 = FP8Format(exp=2, mant=1)
FP4_E3M0 = FP8Format(exp=3, mant=0)


def exponent_bias(alpha: Array, fmt: FP8Format = E4M3) -> Array:
    """Flexible exponent bias b for clipping value alpha (paper, below Eq. 2)."""
    alpha = jnp.maximum(alpha, _ALPHA_FLOOR)
    return (
        2.0 ** fmt.exp
        - jnp.log2(alpha)
        + np.log2(fmt.mant_scale)
        - 1.0
    )


def alpha_from_bias(b: Array, fmt: FP8Format = E4M3) -> Array:
    """Inverse of :func:`exponent_bias`."""
    return jnp.exp2(2.0 ** fmt.exp - 1.0 - b) * fmt.mant_scale


def _scale(x: Array, alpha: Array, fmt: FP8Format) -> Array:
    """Per-element scale s_i (paper Eq. 2). Exponent term is stop-gradded.

    ``alpha`` may be a scalar or any shape broadcastable against ``x``
    (e.g. per-layer stacked ``(L, 1, 1)`` clipping values).
    """
    b = exponent_bias(alpha, fmt)
    # |x| == 0 -> log2 = -inf -> floor = -inf -> subnormal branch; safe.
    p = jnp.floor(jnp.log2(jnp.abs(x)) + b)
    p = jax.lax.stop_gradient(jnp.where(p > 1.0, p, 1.0))
    log2_s = p - b - fmt.mant
    return jnp.exp2(log2_s)


def _round_ste(y: Array) -> Array:
    """Round-to-nearest-even with straight-through gradient."""
    return y + jax.lax.stop_gradient(jnp.round(y) - y)


def _floor_ste(y: Array) -> Array:
    return y + jax.lax.stop_gradient(jnp.floor(y) - y)


def quantize_det(x: Array, alpha: Array, fmt: FP8Format = E4M3) -> Array:
    """Deterministic FP8 fake-quant Q_det(x; alpha) (paper Eq. 2). STE-differentiable."""
    alpha = jnp.maximum(alpha, _ALPHA_FLOOR)
    x_c = jnp.clip(x, -alpha, alpha)
    s = _scale(x_c, alpha, fmt)
    return (s * _round_ste(x_c / s)).astype(x.dtype)


def quantize_rand(
    x: Array, alpha: Array, key: Array, fmt: FP8Format = E4M3
) -> Array:
    """Stochastic FP8 quantization Q_rand(x; alpha) (paper Eq. 3). Unbiased.

    Rounds up with probability equal to the fractional position between the
    two neighbouring grid points, so ``E[Q_rand(x)] == clip(x, -a, a)``.
    """
    alpha = jnp.maximum(alpha, _ALPHA_FLOOR)
    x_c = jnp.clip(x, -alpha, alpha)
    s = _scale(x_c, alpha, fmt)
    y = x_c / s
    fl = jnp.floor(y)
    frac = y - fl
    u = jax.random.uniform(key, shape=jnp.shape(y), dtype=jnp.float32)
    up = (u < frac.astype(jnp.float32)).astype(y.dtype)
    q = fl + up
    # NOTE (grid containment): for x exactly at +alpha, frac == 0 so we never
    # round above the max representable value.
    out = s * (y + jax.lax.stop_gradient(q - y))
    return out.astype(x.dtype)


def quantization_grid(alpha: float, fmt: FP8Format = E4M3) -> np.ndarray:
    """All non-negative representable values for clipping value ``alpha``.

    Used by tests (grid membership, Lemma 5 monotone-bin property) and by
    the wire codec below. Returned sorted ascending, starting at 0.
    """
    b = float(2.0 ** fmt.exp - np.log2(max(alpha, _ALPHA_FLOOR))
              + np.log2(fmt.mant_scale) - 1.0)
    vals = {0.0}
    # Subnormals + exponent code 1 share the scale 2^(1 - b - m).
    s_sub = 2.0 ** (1.0 - b - fmt.mant)
    for v in range(1, 2 ** (fmt.mant + 1)):
        vals.add(v * s_sub)
    for p in range(2, fmt.max_exp_code + 1):
        s = 2.0 ** (p - b - fmt.mant)
        for v in range(2 ** fmt.mant, 2 ** (fmt.mant + 1)):
            vals.add(v * s)
    return np.asarray(sorted(vals))


# ---------------------------------------------------------------------------
# Wire codec: pack FP8-grid values into uint8 for exact byte accounting,
# checkpoint compression and (in a real deployment) DCN transfer buffers.
# ---------------------------------------------------------------------------


def pack_fp8(x: Array, alpha: Array, fmt: FP8Format = E4M3) -> Array:
    """Encode values *already on the FP8 grid* into uint8 codes.

    Layout: [sign:1][exponent:fmt.exp][mantissa:fmt.mant] (MSB first).
    Exponent field f=0,1 share the subnormal scale (IEEE-style); the paper's
    Eq. 2 threshold ``p > 1`` corresponds exactly to f >= 2 being "normal".
    """
    alpha = jnp.maximum(alpha, _ALPHA_FLOOR)
    b = exponent_bias(alpha, fmt)
    sign = (x < 0).astype(jnp.uint8)
    ax = jnp.abs(x)
    p = jnp.floor(jnp.log2(jnp.where(ax > 0, ax, 1.0)) + b)
    p = jnp.where(ax > 0, p, 1.0)
    p_eff = jnp.clip(p, 1.0, float(fmt.max_exp_code))
    s = jnp.exp2(p_eff - b - fmt.mant)
    v = jnp.round(ax / s).astype(jnp.int32)  # in [0, 2^(m+1)-1]
    # v may equal 2^(m+1) due to float fuzz at bin edges; renormalize into
    # the next bin — or saturate the mantissa when the exponent is already
    # at max (halving v without bumping p would decode at half the value).
    overflow = v >= 2 ** (fmt.mant + 1)
    at_max = p_eff >= float(fmt.max_exp_code)
    v = jnp.where(overflow & at_max, 2 ** (fmt.mant + 1) - 1,
                  jnp.where(overflow, v // 2, v))
    p_eff = jnp.where(overflow & ~at_max, p_eff + 1, p_eff)
    is_normal = v >= 2 ** fmt.mant
    f = jnp.where(is_normal, p_eff, 0.0).astype(jnp.int32)
    m_field = jnp.where(is_normal, v - 2 ** fmt.mant, v).astype(jnp.int32)
    code = (
        (sign.astype(jnp.int32) << (fmt.exp + fmt.mant))
        | (f << fmt.mant)
        | m_field
    )
    return code.astype(jnp.uint8)


def unpack_fp8(code: Array, alpha: Array, fmt: FP8Format = E4M3,
               dtype: jnp.dtype = jnp.float32) -> Array:
    """Decode uint8 codes produced by :func:`pack_fp8` back to real values."""
    alpha = jnp.maximum(alpha, _ALPHA_FLOOR)
    b = exponent_bias(alpha, fmt)
    code = code.astype(jnp.int32)
    sign = (code >> (fmt.exp + fmt.mant)) & 0x1
    f = (code >> fmt.mant) & (2 ** fmt.exp - 1)
    m_field = code & (2 ** fmt.mant - 1)
    is_normal = f >= 1
    v = jnp.where(is_normal, m_field + 2 ** fmt.mant, m_field)
    p_eff = jnp.where(is_normal, f, 1)
    s = jnp.exp2(p_eff.astype(dtype) - b.astype(dtype) - fmt.mant)
    mag = v.astype(dtype) * s
    return jnp.where(sign == 1, -mag, mag)


# ---------------------------------------------------------------------------
# PyTree helpers
# ---------------------------------------------------------------------------


def tree_quantize_det(tree: PyTree, alphas: PyTree, fmt: FP8Format = E4M3) -> PyTree:
    """Apply Q_det leaf-wise; ``alphas`` mirrors ``tree`` (scalars per tensor).

    Routed through the backend-aware dispatcher (``kernels.dispatch``) so a
    TPU lowering hits the fused Pallas quantizer per leaf. For federated
    communication prefer the flat-buffer codec in ``core.wire`` — one fused
    launch for the whole tree.
    """
    from ..kernels import dispatch  # lazy: kernels imports this module

    return jax.tree.map(
        lambda x, a: dispatch.quantize_det(x, a, fmt), tree, alphas
    )


def tree_quantize_rand(
    tree: PyTree, alphas: PyTree, key: Array, fmt: FP8Format = E4M3
) -> PyTree:
    """Apply Q_rand leaf-wise with independent randomness per leaf.

    Same dispatch note as :func:`tree_quantize_det`.
    """
    from ..kernels import dispatch  # lazy: kernels imports this module

    leaves, treedef = jax.tree.flatten(tree)
    a_leaves = treedef.flatten_up_to(alphas)
    keys = jax.random.split(key, len(leaves))
    out = [
        dispatch.quantize_rand(x, a, k, fmt)
        for x, a, k in zip(leaves, a_leaves, keys)
    ]
    return jax.tree.unflatten(treedef, out)


def tree_max_abs(tree: PyTree) -> PyTree:
    """Per-tensor max-|x| — the paper's alpha initialisation."""
    return jax.tree.map(lambda x: jnp.max(jnp.abs(x)), tree)
