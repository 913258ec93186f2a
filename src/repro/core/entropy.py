"""Static-table entropy coding over the grid codecs' code streams.

The FP8/FP4 wire charges every code at its full bit width, but the codes
are far from uniform: weights are bell-shaped around zero and delta-coded
residuals are *heavily* peaked there, so most of each byte's entropy is
unused. Since the grids are tiny static code books (Micikevicius et al.,
*FP8 Formats for Deep Learning*), the symbol distribution under a
Gaussian value model is computable at TRACE time from the quantization
grid alone — no per-payload table, nothing about the table crosses the
wire. :class:`RansCodec` range-codes the inner codec's code stream
against that static table with the 16-lane interleaved rANS coder in
``kernels.rans``.

Table model
===========
Codes are quantization-bin indices relative to the clip value, so their
distribution is alpha-invariant: for values ``x ~ N(0, (sigma * alpha)^2)``
the probability of each code is the Gaussian mass of its rounding bin
(bin edges = midpoints between adjacent grid magnitudes, the two signed
codes of a magnitude splitting the one-sided mass evenly). ``sigma`` is
the value scale in units of the clip — for trained weights the clip
sits near ``max|w|`` of a roughly-Gaussian tensor (``sigma ~ 0.25``);
delta-coded residuals are heavy-tailed with the clip at the outlier, so
the bulk is much more peaked (``sigma ~ 0.08``). A mismatched sigma only
costs compression ratio, never correctness — rANS decodes exactly
against whatever table both ends computed. Sub-byte formats code the
PACKED byte stream; two independent nibbles make the byte distribution
the product of the nibble marginals (``fold_codes`` is little-endian:
low nibble = first code).

Frequencies are normalized to sum to ``2**SCALE_BITS`` with every symbol
kept at >= 1 (any inner payload stays decodable, even one hitting codes
the model finds improbable); the floor also caps the largest frequency
at ``4096 - 255``, which is what keeps the int32 coder overflow-free
(see ``kernels.rans``).

Dynamic payloads
================
Entropy-coded size is data-dependent, so RansCodec is the codec that
forces the two-lane byte accounting (``codec.WireCodec`` docstring):
``payload_nbytes`` stays the static structural bound (2 bytes/symbol/lane
+ 8 bytes/lane of state, what buffers are sized to) and
``payload_nbytes_traced`` charges the true coded bytes
(``sum(lens) + 8 * LANES`` + the inner codec's FP32 riders) from inside
the jitted round. Bound >= traced holds by construction and is asserted
in tests/test_entropy.py.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from . import fp8
from .codec import DeltaCodec, Fp8Codec, WireCodec
from .fp8 import FP8Format
from ..kernels import rans as rans_kernel
from ..kernels.fp8_quant import codes_per_byte

Array = jax.Array

# the default value-scale priors (in units of the clip value), per inner
# stream shape — see module docstring; override with RansCodec(sigma=...).
# Fitted against REAL federated payloads (format-ablation MLP task,
# min-cross-entropy over a sigma grid at several training stages): plain
# weight streams sit near 0.28 x clip, delta streams near 0.14 (the
# auto-ranged delta clip tracks the outlier update, so the bulk is ~7x
# tighter than the clip).
SIGMA_PLAIN = 0.28
SIGMA_DELTA = 0.14


def _one_sided_mass(z: np.ndarray) -> np.ndarray:
    """P(|X| <= z) for standard normal X (vectorized erf, no scipy)."""
    out = np.empty(z.shape, np.float64)
    for i, v in enumerate(z.reshape(-1)):
        out.reshape(-1)[i] = 1.0 if math.isinf(v) else math.erf(
            v / math.sqrt(2.0)
        )
    return out


def _unpack_np(codes: np.ndarray, fmt: FP8Format) -> np.ndarray:
    """Pure-numpy twin of ``fp8.unpack_fp8`` at alpha=1 — the table is
    built inside ``lru_cache`` at trace time, where jnp ops would leak
    tracers. Grid-point agreement with the jnp decoder is asserted in
    tests/test_entropy.py."""
    b = 2.0 ** fmt.exp + np.log2(fmt.mant_scale) - 1.0
    sign = (codes >> (fmt.exp + fmt.mant)) & 0x1
    f = (codes >> fmt.mant) & (2 ** fmt.exp - 1)
    m_field = codes & (2 ** fmt.mant - 1)
    is_normal = f >= 1
    v = np.where(is_normal, m_field + 2 ** fmt.mant, m_field)
    p_eff = np.where(is_normal, f, 1)
    s = 2.0 ** (p_eff.astype(np.float64) - b - fmt.mant)
    return np.where(sign == 1, -1.0, 1.0) * v * s


@functools.lru_cache(maxsize=None)
def code_probabilities(fmt: FP8Format, sigma: float) -> np.ndarray:
    """(2**bits,) probability of each grid code under the Gaussian value
    model ``x ~ N(0, (sigma * alpha)^2)`` (alpha-invariant, see module
    docstring). Sums to 1 exactly up to float64 rounding."""
    n_codes = 1 << (fmt.exp + fmt.mant + 1)
    vals = _unpack_np(np.arange(n_codes), fmt)
    grid = np.asarray(fp8.quantization_grid(1.0, fmt), np.float64)
    # each code -> its magnitude's grid index (nearest: the unpacked
    # values ARE grid points, the argmin only absorbs float noise)
    gidx = np.abs(grid[None, :] - np.abs(vals)[:, None]).argmin(axis=1)
    mids = 0.5 * (grid[1:] + grid[:-1])
    lo = np.concatenate([[0.0], mids])
    hi = np.concatenate([mids, [np.inf]])
    mass = _one_sided_mass(hi / sigma) - _one_sided_mass(lo / sigma)
    counts = np.bincount(gidx, minlength=len(grid)).astype(np.float64)
    return mass[gidx] / counts[gidx]


def _normalize_freqs(p: np.ndarray, tab: int) -> np.ndarray:
    """Real probabilities -> integer frequencies summing to ``tab`` with
    every entry >= 1 (largest-remainder apportionment)."""
    scaled = p * tab
    f = np.maximum(1, np.floor(scaled).astype(np.int64))
    diff = tab - int(f.sum())
    if diff > 0:
        order = np.argsort(-(scaled - np.floor(scaled)))
        i = 0
        while diff > 0:
            f[order[i % len(f)]] += 1
            diff -= 1
            i += 1
    elif diff < 0:
        order = np.argsort(-f)
        i = 0
        while diff < 0:
            j = order[i % len(f)]
            if f[j] > 1:
                f[j] -= 1
                diff += 1
            i += 1
    return f


@functools.lru_cache(maxsize=None)
def byte_table(fmt: FP8Format, sigma: float):
    """The static rANS table for ``fmt``'s BYTE code stream at value
    scale ``sigma``: ``(freq, cum, slot2sym)`` int32 numpy arrays of
    shapes (256,), (256,), (4096,). Sub-byte formats pack
    ``codes_per_byte`` independent codes per byte, so the byte
    probability is the product of the per-code marginals."""
    p = code_probabilities(fmt, float(sigma))
    k = codes_per_byte(fmt)
    if k > 1:
        mask = (1 << fmt.bits) - 1
        b = np.arange(256)
        pb = np.ones(256, np.float64)
        for j in range(k):
            pb = pb * p[(b >> (fmt.bits * j)) & mask]
    else:
        pb = p
    freq = _normalize_freqs(pb, rans_kernel.TAB)
    assert freq.sum() == rans_kernel.TAB and freq.min() >= 1
    # the >=1 floor over 256 symbols caps any frequency at 4096 - 255,
    # keeping the encoder threshold f << 19 inside int32 (kernels.rans)
    assert freq.max() <= rans_kernel.TAB - 255
    cum = np.concatenate([[0], np.cumsum(freq)[:-1]])
    slot2sym = np.repeat(np.arange(256), freq)
    return (freq.astype(np.int32), cum.astype(np.int32),
            slot2sym.astype(np.int32))


@dataclasses.dataclass(frozen=True)
class RansCodec(WireCodec):
    """Entropy-coded wrapper: rANS over the inner codec's code stream.

    Lossless on the codes — ``decode(encode(x))`` reconstructs the inner
    payload bit-exactly, so values, convergence, and ``fake_quant`` are
    the inner codec's verbatim; only the wire size changes. ``sigma``
    overrides the table's value-scale prior (0 = auto: ``SIGMA_DELTA``
    for a delta inner, ``SIGMA_PLAIN`` otherwise).

    The payload grows a third entry: ``{"codes": coded byte planes,
    "other": inner riders, "rans": (state (LANES,) i32, lens (LANES,)
    i32)}``. See the module docstring for the two-lane byte accounting
    this codec introduces.
    """

    inner: WireCodec = Fp8Codec()
    sigma: float = 0.0

    quantized: ClassVar[bool] = True
    dynamic: ClassVar[bool] = True

    def __post_init__(self):
        inner = self.inner
        grid = inner.inner if isinstance(inner, DeltaCodec) else inner
        if not isinstance(grid, Fp8Codec):  # includes PackedFpCodec
            raise ValueError(
                "RansCodec range-codes a grid codec's byte stream: inner "
                "must be Fp8Codec/PackedFpCodec or DeltaCodec over one; "
                f"got {type(inner).__name__}"
            )
        if self.sigma < 0:
            raise ValueError(f"RansCodec.sigma must be >= 0 (0 = auto), "
                             f"got {self.sigma}")

    @property
    def tag(self) -> str:
        return f"rans:{self.inner.tag}"

    @property
    def grid_fmt(self) -> FP8Format:
        inner = self.inner
        return (inner.inner.fmt if isinstance(inner, DeltaCodec)
                else inner.fmt)

    @property
    def table_sigma(self) -> float:
        if self.sigma > 0:
            return float(self.sigma)
        return (SIGMA_DELTA if isinstance(self.inner, DeltaCodec)
                else SIGMA_PLAIN)

    def _table(self):
        freq, cum, s2s = byte_table(self.grid_fmt, self.table_sigma)
        return (jnp.asarray(freq), jnp.asarray(cum), jnp.asarray(s2s))

    def encode(self, params, spec, key, ref=None):
        p = self.inner.encode(params, spec, key, ref=ref)
        freq, cum, _ = self._table()
        buf, state, lens = rans_kernel.rans_encode(
            p["codes"].astype(jnp.int32), freq, cum
        )
        return {"codes": buf.reshape(-1), "other": p["other"],
                "rans": (state, lens)}

    def decode(self, payload, spec, ref=None):
        n = self.inner.code_nbytes(spec)
        buf = payload["codes"].reshape(rans_kernel.LANES, -1)
        state, lens = payload["rans"]
        freq, cum, s2s = self._table()
        syms = rans_kernel.rans_decode(buf, state, lens, n, freq, cum, s2s)
        return self.inner.decode(
            {"codes": syms.astype(jnp.uint8), "other": payload["other"]},
            spec, ref=ref,
        )

    def fake_quant(self, params, spec, key, ref=None):
        # entropy coding is lossless on the codes: the observed values
        # are exactly the inner codec's
        return self.inner.fake_quant(params, spec, key, ref=ref)

    def payload_nbytes(self, spec):
        # static worst-case bound: full coded planes + per-lane state and
        # length + the inner codec's FP32 riders
        return (self.code_nbytes(spec) + 8 * rans_kernel.LANES
                + self._rider_nbytes(spec))

    def code_nbytes(self, spec):
        return rans_kernel.LANES * rans_kernel.buf_cols(
            self.inner.code_nbytes(spec)
        )

    def _rider_nbytes(self, spec) -> int:
        return (self.inner.payload_nbytes(spec)
                - self.inner.code_nbytes(spec))

    def payload_nbytes_traced(self, payload, spec):
        _, lens = payload["rans"]
        return (jnp.sum(lens).astype(jnp.int32)
                + jnp.int32(8 * rans_kernel.LANES
                            + self._rider_nbytes(spec)))
