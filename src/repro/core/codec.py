"""First-class wire codecs — pluggable compression for the federated wire.

The communication layer used to thread a ``(FP8Format, mode-string)`` pair
through ``WireLink`` / ``FedConfig`` / ``core.wire`` with a "quantized ==
exactly 1 byte/element" assumption baked into ``core.metrics``. This module
promotes *how bytes cross the wire* to a first-class object:

``WireCodec`` protocol
======================
* ``encode(params, spec, key, ref=None)``  -> ``{"codes": u8[n], "other":
  (leaf, ...)}`` — the exact payload a transmitter ships. ``codes`` is the
  compressed weight buffer (its length is the codec's business); ``other``
  holds the FP32 ride-along leaves.
* ``decode(payload, spec, ref=None)``      -> the param pytree a receiver
  reconstructs.
* ``fake_quant(params, spec, key, ref=None)`` -> what a receiver *observes*
  (decode∘encode) without materializing the codes — the simulator's
  one-launch transit.
* ``payload_nbytes(spec)`` / ``code_nbytes(spec)`` — exact static wire
  bytes of one model copy / of the codes buffer alone. The engine's traced
  ``wire_bytes`` metric and ``core.metrics`` both delegate here, so static
  == traced stays exact per codec (including sub-byte and delta payloads).
* ``tag`` — registry name; ``quantized`` — False only for the FP32 leg.

``ref`` is the round's *reference model* (known to both ends of a leg);
only :class:`DeltaCodec` uses it. Implementations:

* :class:`Fp8Codec`    — today's flat-buffer FP8 wire (``core.wire``),
  bit-for-bit: 1 byte/element, ``rounding`` 'rand' (the paper's unbiased
  SR, Lemma 3) or 'det' (the biased Table-2 ablation).
* :class:`Fp32Codec`   — the 'none' leg: 4 bytes/element passthrough.
* :class:`PackedFpCodec` — sub-byte ExMy formats (Noune et al.): FP4
  E2M1/E3M0 at 2 codes/byte (``kernels.dispatch.quant_pack_sub_tiles``:
  the FP8 pack kernel's codes, folded in pairs). Halves the quantized-leg
  payload vs FP8.
* :class:`DeltaCodec(inner)` — transmits the quantized *residual* against
  ``ref``; with a stochastic inner rounding the leg stays unbiased (SR of
  the delta — the Lemma 3 machinery applied to ``params - ref``). Each
  leaf's fresh residual clipping value rides as one extra FP32 scalar.
* :class:`CodecSchedule` — per-round codec (e.g. E5M2 -> E4M3 -> FP4
  precision annealing), resolved inside the jitted round via a
  round-index operand (``lax.switch``); see ``engine.WireLink``.

Registry: :func:`get_codec` maps names (``e4m3``, ``e5m2_det``, ``fp4``,
``fp4_e3m0``, ``delta:e4m3``, ``fp32``/``none``, ...) to codec objects;
:func:`codec_for` is the deprecation shim from the legacy ``(fmt, mode)``
knobs. All codecs are frozen dataclasses — hashable, usable as static
config fields.

Scaling policies (``core.scaling``)
===================================
*How the per-leaf clip scales are derived* is orthogonal to *which grid
the codec quantizes onto*, so it lives in a separate policy object
(``ScalingPolicy``) threaded by ``engine.WireLink``:

* ``current`` — the deprecation map: every no-policy call site (plain
  ``encode``/``decode`` below) IS current scaling, bit-identical to the
  historical behavior. The trained ``_qa`` alphas ride in ``other``.
* ``delayed(H, M)`` — scales come from a rolling per-leaf amax history
  carried in ``engine.ServerState.scales`` (a ``(down, up)`` state
  tuple); the grid codecs' :meth:`Fp8Codec.encode_scaled` with
  ``with_amax=True`` emits next round's amax row as a fused byproduct of
  the quantize launch (``dispatch.quant_pack_amax_tiles``) — no
  standalone reduction in the encode hot path. The effective scales ride
  the payload as one extra ``(n_q,)`` FP32 rider.
* ``frozen`` — downlink-only: the receiver derives the scales from the
  broadcast model's own trained alphas, so ``encode_scaled(...,
  drop_alphas=True)`` ships NO alpha riders (−4 bytes/quantized leaf)
  and :meth:`decode_scaled` splices them back from the scale vector —
  values bitwise-equal to ``current``, bytes strictly smaller.

:func:`leg_nbytes` takes the policy and adds its exact payload delta, so
static byte accounting == the engine's traced ``wire_bytes`` for every
policy.

Dynamic payloads (the two-lane byte protocol)
=============================================
Entropy-coded payloads (``core.entropy.RansCodec``, and
``core.ef.ErrorFeedbackCodec`` over one) have DATA-DEPENDENT size, so
"static == traced" splits into two lanes with an invariant between them:

* ``payload_nbytes(spec)`` — the static lane — becomes the worst-case
  structural BOUND: what wire buffers are sized to, what
  ``engine.round_bytes`` / ``metrics.round_bytes`` / FedSim's
  ``bytes_per_round`` report, and what the sub-GiB int32 guard checks.
  For every non-dynamic codec it remains the exact payload size.
* ``payload_nbytes_traced(payload, spec)`` — the traced lane — charges
  the TRUE coded bytes of one concrete payload (int32, vmap-safe),
  computed inside the jitted round from the payload itself. The engine's
  ``wire_bytes`` metric, FedSim's cumulative byte ledger, and the fault
  path's partial accounting (P downlinks + transmitted uplinks only)
  all switch to this lane when a leg's codec has ``dynamic = True``.
  The base-class default returns the static bound, so the two lanes
  coincide for every ordinary codec.

The invariant — static bound >= traced bytes, payload by payload — holds
by construction (rANS emits at most 2 bytes/symbol/lane into a buffer
sized exactly so) and is asserted across codecs, legs, and fault
realizations in tests/test_entropy.py and tests/test_ef.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from . import fp8, wire
from .fp8 import E4M3, E5M2, FP4_E2M1, FP4_E3M0, FP8Format
from .plane import LANE, f32 as _f32, nelem as _nelem, tiles as _tiles
from ..kernels import dispatch
from ..kernels.fp8_quant import codes_per_byte

Array = jax.Array
PyTree = Any


def _fp32_nbytes(spec: wire.WireSpec) -> int:
    """Bytes of one uncompressed model copy (every element at 4 bytes)."""
    return 4 * (spec.total + spec.n_other_elems)


def _key_words(key: Array) -> Array:
    """(2,) u32 words seeding the in-kernel counter RNG (same derivation as
    ``wire._prep_tiles`` so codec and wire draws agree)."""
    kd = key if key.dtype == jnp.uint32 else jax.random.key_data(key)
    return kd.reshape(-1)[:2]


def _slice_rows(buf2: Array, spec: wire.WireSpec, sizes) -> Array:
    """Per-leaf row blocks -> one flat buffer of exactly ``sum(sizes)``.

    ``buf2`` is a (n_rows, width) tile buffer whose leaf ``qi`` occupies
    rows ``q_row_offsets[qi] .. +q_rows[qi]``; ``sizes[qi]`` is the number
    of real entries to keep from that block (tile padding sliced off)."""
    return jnp.concatenate([
        buf2[r0:r0 + rows].reshape(-1)[:n]
        for r0, rows, n in zip(spec.q_row_offsets, spec.q_rows, sizes)
    ])


def _rows_from_flat(flat: Array, spec: wire.WireSpec, sizes,
                    width: int) -> Array:
    """Inverse of :func:`_slice_rows`: flat buffer -> (n_rows, width) tiles
    (zero padding in the tile tails, exactly where encode sliced it off)."""
    pieces = []
    off = 0
    for rows, n in zip(spec.q_rows, sizes):
        piece = flat[off:off + n]
        off += n
        pad = rows * width - n
        if pad:
            piece = jnp.concatenate(
                [piece, jnp.zeros((pad,), piece.dtype)]
            )
        pieces.append(piece.reshape(rows, width))
    return jnp.concatenate(pieces, axis=0)


def _leaf_alpha_column(alphas: Array, spec: wire.WireSpec) -> Array:
    """(n_q_leaves,) per-leaf scalars -> (n_rows, 1) per-row column."""
    cols = [
        jnp.broadcast_to(alphas[qi].reshape(()), (rows, 1))
        for qi, rows in enumerate(spec.q_rows)
    ]
    return jnp.concatenate(cols, axis=0)


def _plane_segment_amax(rowmax: Array, spec: wire.WireSpec) -> Array:
    """Per-row |x| maxima -> per-quantized-leaf (n_q,) amax, one gather.

    ``rowmax`` is the (n_rows,) column a fused plane launch emitted; the
    row->leaf segment ids are static (``spec.q_rows``), so this is a
    single sorted ``segment_max`` — no per-leaf Python loop, no extra
    pass over the model. Bitwise-equal to a per-leaf flat ``max|x|``:
    the plane's zero fill never exceeds a row's abs-max and float max is
    exactly associative.
    """
    seg = np.repeat(np.arange(len(spec.q_slots)), spec.q_rows)
    return jax.ops.segment_max(
        rowmax.reshape(-1), jnp.asarray(seg),
        num_segments=len(spec.q_slots), indices_are_sorted=True,
    )


def _scaled_alpha_col(alphas: Array, spec: wire.WireSpec) -> Array:
    """Explicit (n_q,) scale vector -> floored (n_rows, 1) clip column."""
    a = jnp.maximum(_f32(alphas).reshape(-1), fp8._ALPHA_FLOOR)
    return _leaf_alpha_column(a, spec)


class WireCodec:
    """Protocol base: one leg's wire compression (see module docstring).

    Subclasses are frozen dataclasses. ``ref`` (the round's reference
    model) is accepted everywhere and ignored by every codec except
    :class:`DeltaCodec`.
    """

    # NOTE: deliberately un-annotated — an annotated class attribute here
    # would become a dataclass *field* in every frozen subclass and clash
    # with their `tag` properties.
    tag = "?"
    quantized: ClassVar[bool] = True
    # True when payload size is data-dependent (see "Dynamic payloads"
    # in the module docstring): payload_nbytes is then a static BOUND
    # and payload_nbytes_traced the true coded size
    dynamic: ClassVar[bool] = False

    def encode(self, params: PyTree, spec: wire.WireSpec, key: Array,
               ref: PyTree | None = None) -> dict:
        raise NotImplementedError

    def decode(self, payload: dict, spec: wire.WireSpec,
               ref: PyTree | None = None) -> PyTree:
        raise NotImplementedError

    def fake_quant(self, params: PyTree, spec: wire.WireSpec, key: Array,
                   ref: PyTree | None = None) -> PyTree:
        raise NotImplementedError

    def payload_nbytes(self, spec: wire.WireSpec) -> int:
        raise NotImplementedError

    def code_nbytes(self, spec: wire.WireSpec) -> int:
        raise NotImplementedError

    def payload_nbytes_traced(self, payload: dict,
                              spec: wire.WireSpec) -> Array:
        """True wire bytes of ONE concrete payload, traced (int32).

        Defaults to the static ``payload_nbytes`` — exact for every
        codec with ``dynamic = False``; dynamic codecs override it with
        the data-dependent count (always <= the static bound)."""
        return jnp.int32(self.payload_nbytes(spec))


@dataclasses.dataclass(frozen=True)
class Fp32Codec(WireCodec):
    """FP32 passthrough — the FedAvg baseline leg (legacy ``mode='none'``).

    ``encode`` ships every leaf as an FP32 rider (``codes`` is empty) so
    the payload schema stays uniform for gather-based collectives; links
    skip the transit entirely (``quantized`` is False)."""

    quantized: ClassVar[bool] = False

    @property
    def tag(self) -> str:
        return "fp32"

    def encode(self, params, spec, key, ref=None):
        return {
            "codes": jnp.zeros((0,), jnp.uint8),
            "other": tuple(jax.tree_util.tree_leaves(params)),
        }

    def decode(self, payload, spec, ref=None):
        return jax.tree_util.tree_unflatten(
            spec.treedef, list(payload["other"])
        )

    def fake_quant(self, params, spec, key, ref=None):
        return params

    def payload_nbytes(self, spec):
        return _fp32_nbytes(spec)

    def code_nbytes(self, spec):
        return 0


@dataclasses.dataclass(frozen=True)
class Fp8Codec(WireCodec):
    """The paper's FP8 wire (1 byte/element + FP32 riders) — a thin,
    bit-for-bit delegation to the flat-buffer codec in ``core.wire``.
    ``rounding='rand'`` is the unbiased SR uplink/downlink quantizer
    (Lemma 3); ``'det'`` the biased Table-2 ablation."""

    fmt: FP8Format = E4M3
    rounding: str = "rand"

    def __post_init__(self):
        if self.rounding not in ("rand", "det"):
            raise ValueError(f"rounding {self.rounding!r}: 'rand' or 'det'"
                             " (the FP32 leg is Fp32Codec, not a mode)")
        if self.fmt.bits != 8:
            raise ValueError(
                f"Fp8Codec packs 1 code/byte; {self.fmt.bits}-bit formats "
                "go through PackedFpCodec"
            )

    @property
    def tag(self) -> str:
        t = f"e{self.fmt.exp}m{self.fmt.mant}"
        return t if self.rounding == "rand" else t + "_det"

    def encode(self, params, spec, key, ref=None):
        return wire.encode(params, spec, key, fmt=self.fmt,
                           mode=self.rounding)

    def decode(self, payload, spec, ref=None):
        return wire.decode(payload, spec, fmt=self.fmt)

    def fake_quant(self, params, spec, key, ref=None):
        return wire.roundtrip(params, key, fmt=self.fmt,
                              mode=self.rounding, spec=spec)

    def payload_nbytes(self, spec):
        return spec.total + 4 * spec.n_other_elems

    def code_nbytes(self, spec):
        return spec.total

    # --- tile-level hooks (DeltaCodec composes over these) ---------------
    def _encode_tiles(self, x2, a2, key2):
        return dispatch.quant_pack_tiles(x2, a2, key2, fmt=self.fmt)

    def _encode_tiles_amax(self, x2, a2, key2):
        return dispatch.quant_pack_amax_tiles(x2, a2, key2, fmt=self.fmt)

    def _decode_tiles(self, c2, a2):
        return dispatch.unpack_tiles(c2, a2, fmt=self.fmt)

    # --- explicit-scale encode/decode (core.scaling policies) ------------
    def encode_scaled(self, params, spec, key, alphas, *,
                      drop_alphas: bool = False, with_amax: bool = False):
        """Encode with an explicit (n_q,) scale vector instead of the
        tree's trained alphas.

        ``alphas`` replaces the per-leaf clip values for quantization
        (floored at ``fp8._ALPHA_FLOOR``); the codes math is the SAME
        fused kernel as :meth:`encode`. Payload layout per policy:

        * default — ``alphas`` rides as one extra (n_q,) FP32 rider
          appended to ``other`` (delayed scaling: the receiver holds no
          history, so the effective scales must cross the wire).
        * ``drop_alphas=True`` — the alpha riders are removed from
          ``other`` entirely (frozen scaling: the receiver derives them
          itself); −4 bytes per quantized leaf.

        ``with_amax=True`` additionally returns the per-leaf raw amax of
        THIS encode, computed as a fused byproduct of the quantize launch
        (``dispatch.quant_pack_amax_tiles``) — delayed scaling's history
        update, with no standalone reduction in the critical path.
        """
        leaves = list(jax.tree_util.tree_leaves(params))
        other = tuple(leaves[i] for i in spec.other_slots)
        if drop_alphas:
            hidden = set(spec.alpha_pos)
            out_other = tuple(
                o for oi, o in enumerate(other) if oi not in hidden
            )
        else:
            out_other = other + (_f32(alphas).reshape(-1),)
        if not spec.q_slots:
            payload = {"codes": jnp.zeros((0,), jnp.uint8),
                       "other": out_other}
            return ((payload, jnp.zeros((0,), jnp.float32))
                    if with_amax else payload)
        x2 = _tiles([_f32(leaves[i].reshape(-1)) for i in spec.q_slots], 0.0)
        a_col = _scaled_alpha_col(alphas, spec)
        key2 = _key_words(key) if self.rounding == "rand" else None
        if with_amax:
            codes2, rowmax = self._encode_tiles_amax(x2, a_col, key2)
            amax = _plane_segment_amax(rowmax, spec)
            return ({"codes": self._slice_codes(codes2, spec),
                     "other": out_other}, amax)
        codes2 = self._encode_tiles(x2, a_col, key2)
        return {"codes": self._slice_codes(codes2, spec),
                "other": out_other}

    def decode_scaled(self, payload, spec, *, alphas=None,
                      dropped: bool = False):
        """Decode an :meth:`encode_scaled` payload.

        ``dropped=False``: the scale vector is the payload's last rider.
        ``dropped=True`` (frozen): ``alphas`` is the receiver-derived
        (n_q,) vector; the alpha leaves it encodes are spliced back into
        the tree at their recorded positions/shapes — bitwise-equal to
        shipping them, since both ends hold the same broadcast model.
        """
        other_all = tuple(payload["other"])
        if dropped:
            if alphas is None:
                raise ValueError(
                    "decode_scaled(dropped=True) needs the receiver-side "
                    "alphas= vector (core.scaling.leaf_alphas of the model "
                    "both ends hold)"
                )
            a_vec = _f32(alphas).reshape(-1)
            inv = {oi: qi for qi, oi in enumerate(spec.alpha_pos)}
            it = iter(other_all)
            other = tuple(
                a_vec[inv[oi]].reshape(spec.alpha_shapes[inv[oi]])
                if oi in inv else next(it)
                for oi in range(len(spec.other_slots))
            )
        else:
            rider, other = other_all[-1], other_all[:-1]
            a_vec = _f32(rider).reshape(-1)
        out: list = [None] * spec.n_leaves
        for slot, leaf in zip(spec.other_slots, other):
            out[slot] = leaf
        if spec.q_slots:
            c2 = self._codes_to_tiles(payload["codes"], spec)
            vals2 = self._decode_tiles(c2, _scaled_alpha_col(a_vec, spec))
            for qi, slot in enumerate(spec.q_slots):
                out[slot] = wire.tiles_to_leaf(vals2, spec, qi)
        return jax.tree_util.tree_unflatten(spec.treedef, out)

    def _leaf_code_sizes(self, spec):
        return [_nelem(s) for s in spec.q_shapes]

    def _code_width(self) -> int:
        return LANE

    def _slice_codes(self, codes2, spec):
        return _slice_rows(codes2, spec, self._leaf_code_sizes(spec))

    def _codes_to_tiles(self, codes, spec):
        return _rows_from_flat(codes, spec, self._leaf_code_sizes(spec),
                               self._code_width())


@dataclasses.dataclass(frozen=True)
class PackedFpCodec(Fp8Codec):
    """Sub-byte ExMy wire: ``8 // fmt.bits`` codes per payload byte.

    FP4 (E2M1 or E3M0) packs 2 codes/byte — half the quantized-leg payload
    of FP8 — through ``kernels.dispatch.quant_pack_sub_tiles`` /
    ``unpack_sub_tiles``, which fold the codes of the 1-byte wire kernels:
    the SAME parametric (exp, mant) grid and per-element
    counter RNG as the FP8 wire. A leaf of n elements occupies exactly
    ``ceil(n * bits / 8)`` wire bytes (an odd tail element shares its byte
    with a zero-code pad nibble — deterministic in both rounding modes, so
    payloads stay bitwise reproducible across backends)."""

    fmt: FP8Format = FP4_E2M1
    rounding: str = "rand"

    def __post_init__(self):
        if self.rounding not in ("rand", "det"):
            raise ValueError(f"rounding {self.rounding!r}: 'rand' or 'det'")
        codes_per_byte(self.fmt)  # validates bits | 8
        if self.fmt.bits >= 8:
            raise ValueError("PackedFpCodec is for sub-byte formats; "
                             "8-bit formats are Fp8Codec")

    @property
    def tag(self) -> str:
        t = f"fp{self.fmt.bits}_e{self.fmt.exp}m{self.fmt.mant}"
        return t if self.rounding == "rand" else t + "_det"

    def encode(self, params, spec, key, ref=None):
        leaves, other, x2, a2, key2 = wire._prep_tiles(
            params, spec, key, self.rounding
        )
        if not spec.q_slots:
            return {"codes": jnp.zeros((0,), jnp.uint8), "other": other}
        packed2 = dispatch.quant_pack_sub_tiles(x2, a2, key2, fmt=self.fmt)
        return {"codes": self._slice_codes(packed2, spec), "other": other}

    def decode(self, payload, spec, ref=None):
        other = tuple(payload["other"])
        out: list = [None] * spec.n_leaves
        for slot, leaf in zip(spec.other_slots, other):
            out[slot] = leaf
        if spec.q_slots:
            c2 = self._codes_to_tiles(payload["codes"], spec)
            a2 = wire._alpha_tiles(other, spec)
            vals2 = dispatch.unpack_sub_tiles(c2, a2, fmt=self.fmt)
            for qi, slot in enumerate(spec.q_slots):
                out[slot] = wire.tiles_to_leaf(vals2, spec, qi)
        return jax.tree_util.tree_unflatten(spec.treedef, out)

    def fake_quant(self, params, spec, key, ref=None):
        # wire.roundtrip is format-parametric: the transit math never packs
        return wire.roundtrip(params, key, fmt=self.fmt,
                              mode=self.rounding, spec=spec)

    def payload_nbytes(self, spec):
        return self.code_nbytes(spec) + 4 * spec.n_other_elems

    def code_nbytes(self, spec):
        return sum(self._leaf_code_sizes(spec))

    def _encode_tiles(self, x2, a2, key2):
        return dispatch.quant_pack_sub_tiles(x2, a2, key2, fmt=self.fmt)

    def _encode_tiles_amax(self, x2, a2, key2):
        return dispatch.quant_pack_sub_amax_tiles(x2, a2, key2, fmt=self.fmt)

    def _decode_tiles(self, c2, a2):
        return dispatch.unpack_sub_tiles(c2, a2, fmt=self.fmt)

    def _leaf_code_sizes(self, spec):
        k = codes_per_byte(self.fmt)
        return [-(-_nelem(s) // k) for s in spec.q_shapes]

    def _code_width(self) -> int:
        return LANE // codes_per_byte(self.fmt)


@dataclasses.dataclass(frozen=True)
class DeltaCodec(WireCodec):
    """Residual/delta encoding over an inner grid codec.

    Transmits ``inner(params - ref)`` instead of the weights themselves:
    ``ref`` is the round's reference model, held by BOTH ends of the leg
    (on the uplink: the model the server just broadcast and every client
    started local training from), so only the update crosses the wire.
    Each quantized leaf gets a fresh residual clipping value
    ``max|params - ref|`` — one extra FP32 scalar per leaf on the wire —
    which (a) keeps the residual inside the clipping range, so with
    ``inner.rounding='rand'`` the leg is exactly unbiased
    (``E[decode] == params``; SR of the delta, Lemma 3), and (b) shrinks
    the grid spacing to the residual's scale: late in training
    ``|params - ref| << |params|``, so the SAME byte count carries far
    less quantization error (or FP4 carries FP8-grade error at half the
    bytes). The model's trained clip values ride FP32 untouched, exactly
    as on the plain wire.
    """

    inner: WireCodec = Fp8Codec(E4M3, "rand")

    def __post_init__(self):
        if not isinstance(self.inner, Fp8Codec):  # includes PackedFpCodec
            raise ValueError(
                "DeltaCodec composes over a grid codec (Fp8Codec / "
                f"PackedFpCodec); got {type(self.inner).__name__}"
            )

    @property
    def tag(self) -> str:
        return f"delta:{self.inner.tag}"

    def _residual_tiles(self, params, spec, key, ref):
        if ref is None:
            raise ValueError(
                "DeltaCodec needs the leg's reference model (ref=): the "
                "receiver must already hold it — use it on the uplink "
                "(reference = the round's broadcast model) or a stateful "
                "boundary that threads the previous global model"
            )
        leaves = list(jax.tree_util.tree_leaves(params))
        rleaves = jax.tree_util.tree_leaves(ref)
        resid = [
            _f32(leaves[i].reshape(-1)) - _f32(rleaves[i].reshape(-1))
            for i in spec.q_slots
        ]
        x2 = _tiles(resid, 0.0)
        # one launch over the plane, not O(n_leaves) per-leaf reductions:
        # per-row max then a static sorted segment-max back to each leaf.
        # Bitwise-equal to the per-leaf flat max (zero fill never exceeds
        # a row's abs-max; float max is exactly associative).
        rowmax = jnp.max(jnp.abs(x2), axis=1)
        d_alpha = jnp.maximum(
            _plane_segment_amax(rowmax, spec), fp8._ALPHA_FLOOR
        )
        a_col = _leaf_alpha_column(d_alpha, spec)
        key2 = _key_words(key) if self.inner.rounding == "rand" else None
        return leaves, x2, a_col, d_alpha, key2

    def encode(self, params, spec, key, ref=None):
        leaves = jax.tree_util.tree_leaves(params)
        other = tuple(leaves[i] for i in spec.other_slots)
        if not spec.q_slots:
            return {"codes": jnp.zeros((0,), jnp.uint8),
                    "other": other + (jnp.zeros((0,), jnp.float32),)}
        _, x2, a_col, d_alpha, key2 = self._residual_tiles(
            params, spec, key, ref
        )
        codes2 = self.inner._encode_tiles(x2, a_col, key2)
        # the residual clipping values ride as ONE extra (n_q,) FP32 rider
        return {"codes": self.inner._slice_codes(codes2, spec),
                "other": other + (d_alpha,)}

    def decode(self, payload, spec, ref=None):
        if ref is None:
            raise ValueError("DeltaCodec.decode needs ref= (see encode)")
        other_all = tuple(payload["other"])
        d_alpha, other = other_all[-1], other_all[:-1]
        out: list = [None] * spec.n_leaves
        for slot, leaf in zip(spec.other_slots, other):
            out[slot] = leaf
        if spec.q_slots:
            rleaves = jax.tree_util.tree_leaves(ref)
            c2 = self.inner._codes_to_tiles(payload["codes"], spec)
            a_col = _leaf_alpha_column(
                jnp.maximum(d_alpha, fp8._ALPHA_FLOOR), spec
            )
            vals2 = self.inner._decode_tiles(c2, a_col)
            for qi, slot in enumerate(spec.q_slots):
                res = wire.tiles_to_leaf(vals2, spec, qi)
                base = rleaves[slot]
                out[slot] = (
                    _f32(base) + _f32(res)
                ).astype(spec.q_dtypes[qi])
        return jax.tree_util.tree_unflatten(spec.treedef, out)

    def fake_quant(self, params, spec, key, ref=None):
        if not spec.q_slots:
            return params
        leaves, x2, a_col, _, key2 = self._residual_tiles(
            params, spec, key, ref
        )
        rleaves = jax.tree_util.tree_leaves(ref)
        vals2 = dispatch.fake_quant_tiles(x2, a_col, key2,
                                          fmt=self.inner.fmt)
        for qi, slot in enumerate(spec.q_slots):
            res = wire.tiles_to_leaf(vals2, spec, qi)
            leaves[slot] = (
                _f32(rleaves[slot]) + _f32(res)
            ).astype(spec.q_dtypes[qi])
        return jax.tree_util.tree_unflatten(spec.treedef, leaves)

    def payload_nbytes(self, spec):
        # inner codes + model riders + one fresh f32 clip scalar per leaf
        return (self.inner.code_nbytes(spec) + 4 * spec.n_other_elems
                + 4 * len(spec.q_slots))

    def code_nbytes(self, spec):
        return self.inner.code_nbytes(spec)


@dataclasses.dataclass(frozen=True)
class CodecSchedule:
    """Piecewise-constant per-round codec (e.g. precision annealing).

    ``codecs[i]`` is active for rounds ``boundaries[i-1] <= r <
    boundaries[i]`` (``boundaries`` has ``len(codecs) - 1`` strictly
    increasing round indices). The engine resolves the active codec
    *inside* the jitted round from a round-index operand
    (``jax.lax.switch`` over the phases — see ``engine.WireLink``), so a
    schedule never retraces; byte accounting switches over the same phase
    index and stays exact per round. Members must be grid codecs (Fp8 /
    PackedFp): the schedule's branches must agree on payload schema and on
    needing no reference model.
    """

    codecs: tuple
    boundaries: tuple[int, ...] = ()

    def __post_init__(self):
        object.__setattr__(
            self, "codecs", tuple(get_codec(c) for c in self.codecs)
        )
        object.__setattr__(self, "boundaries", tuple(self.boundaries))
        if len(self.boundaries) != len(self.codecs) - 1:
            raise ValueError(
                f"{len(self.codecs)} codecs need {len(self.codecs) - 1} "
                f"boundaries, got {len(self.boundaries)}"
            )
        if any(b2 <= b1 for b1, b2 in
               zip(self.boundaries, self.boundaries[1:])):
            raise ValueError(f"boundaries must increase: {self.boundaries}")
        for c in self.codecs:
            if not isinstance(c, Fp8Codec):  # Fp8Codec or PackedFpCodec
                kind = type(c).__name__
                if kind == "ErrorFeedbackCodec":
                    raise ValueError(
                        "CodecSchedule cannot hold ErrorFeedbackCodec: EF "
                        "is stateful (per-client residual memory) and must "
                        "be the leg's sole codec — wrap the whole schedule "
                        "idea as ef:<grid> on the uplink instead"
                    )
                if kind == "RansCodec":
                    raise ValueError(
                        "CodecSchedule cannot hold RansCodec: schedule "
                        "branches must agree on payload schema, and the "
                        "entropy-coded payload adds a dynamic 'rans' entry "
                        "— use rans:<grid> as the leg's sole codec instead"
                    )
                raise ValueError(
                    "CodecSchedule members must be grid codecs (Fp8Codec/"
                    f"PackedFpCodec); got {kind}"
                )

    quantized: ClassVar[bool] = True

    @property
    def tag(self) -> str:
        legs = ",".join(c.tag for c in self.codecs)
        return f"sched({legs}@{','.join(map(str, self.boundaries))})"

    def phase(self, r: Array) -> Array:
        """Traced phase index for round ``r`` (int32, in-jit)."""
        ph = jnp.zeros((), jnp.int32)
        for b in self.boundaries:
            ph = ph + (r >= b).astype(jnp.int32)
        return ph

    def at(self, r: int):
        """Static (Python) resolution: the codec active at round ``r``."""
        ph = sum(int(r) >= b for b in self.boundaries)
        return self.codecs[ph]


# ---------------------------------------------------------------------------
# Registry + legacy-knob shim
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, WireCodec] = {}


def register_codec(name: str, codec: WireCodec) -> None:
    _REGISTRY[name.lower()] = codec


for _fmt, _base in ((E4M3, "e4m3"), (E5M2, "e5m2")):
    register_codec(_base, Fp8Codec(_fmt, "rand"))
    register_codec(_base + "_det", Fp8Codec(_fmt, "det"))
for _fmt, _base in ((FP4_E2M1, "fp4_e2m1"), (FP4_E3M0, "fp4_e3m0")):
    register_codec(_base, PackedFpCodec(_fmt, "rand"))
    register_codec(_base + "_det", PackedFpCodec(_fmt, "det"))
register_codec("fp4", _REGISTRY["fp4_e2m1"])
register_codec("fp4_det", _REGISTRY["fp4_e2m1_det"])
register_codec("fp32", Fp32Codec())
register_codec("none", Fp32Codec())
register_codec("delta", DeltaCodec(Fp8Codec(E4M3, "rand")))


def get_codec(c) -> WireCodec:
    """Resolve a codec spec: a WireCodec/CodecSchedule instance passes
    through; a string looks up the registry. Prefixes compose recursively:
    ``delta:<inner>`` (residual coding), ``rans:<inner>`` (static-table
    entropy coding, ``core.entropy``), ``ef:<inner>`` (error feedback,
    ``core.ef`` — uplink only). Bare ``rans``/``ef`` default their inner
    to the registry default, mirroring bare ``delta``."""
    if isinstance(c, (WireCodec, CodecSchedule)):
        return c
    if isinstance(c, str):
        name = c.lower()
        if name.startswith("delta:"):
            return DeltaCodec(get_codec(name[len("delta:"):]))
        if name.startswith("rans:") or name == "rans":
            # imported lazily: entropy builds on this module
            from .entropy import RansCodec

            inner = name[len("rans:"):] or "e4m3"
            return RansCodec(get_codec(inner))
        if name.startswith("ef:") or name == "ef":
            from .ef import ErrorFeedbackCodec

            inner = name[len("ef:"):] or "e4m3"
            return ErrorFeedbackCodec(get_codec(inner))
        if name in _REGISTRY:
            return _REGISTRY[name]
        raise KeyError(
            f"unknown codec {c!r}; registered: {sorted(_REGISTRY)} "
            "(or composed 'delta:<name>' / 'rans:<name>' / 'ef:<name>')"
        )
    raise TypeError(f"cannot resolve a codec from {type(c).__name__}")


def registry_tags() -> list[str]:
    """Distinct registered codecs (one tag per object, aliases folded)."""
    seen, out = set(), []
    for codec in _REGISTRY.values():
        if codec.tag not in seen:
            seen.add(codec.tag)
            out.append(codec.tag)
    return out


def codec_for(fmt: FP8Format, mode: str) -> WireCodec:
    """Deprecation shim: the legacy ``(fmt, mode)`` pair -> codec.

    ``mode='none'`` -> :class:`Fp32Codec`; otherwise the grid codec for
    ``fmt`` (sub-byte formats route to :class:`PackedFpCodec`) at the
    requested rounding. This is what ``FedConfig``'s legacy
    ``fmt/down_fmt/up_fmt/comm_mode/down_mode/up_mode`` knobs resolve
    through, bit-identically to the pre-codec wire.
    """
    if mode == "none":
        return Fp32Codec()
    if fmt.bits == 8:
        return Fp8Codec(fmt, mode)
    return PackedFpCodec(fmt, mode)


def leg_nbytes(codec, spec: wire.WireSpec, r: int = 0, policy=None) -> int:
    """Exact static bytes of one model copy on a leg using ``codec``.

    A tree with no quantized leaves rides FP32 whatever the codec says
    (there is nothing to compress); schedules resolve at round ``r``.
    ``policy`` (a ``core.scaling.ScalingPolicy``) adds its exact payload
    delta — +4 bytes/leaf for delayed's scale riders, −4 for frozen's
    dropped alpha columns, 0 for current/None.
    """
    if isinstance(codec, CodecSchedule):
        codec = codec.at(r)
    if codec.quantized and spec.q_slots:
        n = codec.payload_nbytes(spec)
        if policy is not None:
            n += policy.payload_delta(spec)
        return n
    return _fp32_nbytes(spec)
