"""Interleaved rANS range coder over the wire codecs' byte code streams.

This is the entropy layer under :class:`repro.core.entropy.RansCodec`: a
16-lane interleaved rANS coder (Duda, *Asymmetric numeral systems*; the
byte-renormalized variant of ryg's ``rans_byte``) specialized to a STATIC
frequency table — the table is a trace-time constant computed from the
quantization grid, never transmitted, so the only dynamic payload is the
coded byte stream itself plus the per-lane final states and lengths.

Coder parameters (the ``rans_byte`` configuration, int32-safe):

* ``SCALE_BITS = 12`` — frequencies are 12-bit (sum to 4096). Table
  construction guarantees every frequency is >= 1 and hence <= 4096-255,
  so the encoder threshold ``f << 19`` stays below 2**31.
* ``L = 1 << 23`` — the state invariant is ``x in [L, 2**31)``; with
  byte renormalization each symbol emits at most ``RENORMS = 2`` bytes.
* ``LANES = 16`` — symbols are interleaved round-robin over 16
  independent states so each scan step is a (16,)-vector op. Every lane
  carries its own byte stream, final state, and length.

Layout: symbols (the inner codec's u8 code stream, alphabet 256) are
padded with symbol 0 to a multiple of LANES and reshaped ``(steps,
LANES)``; lane ``l`` codes symbols ``t*LANES + l``. The encoder scans
rows in REVERSE (rANS encodes last-symbol-first), emitting low byte
first; the decoder scans forward, reading each lane's stream backward —
exactly the stack discipline rANS requires, verified bit-exact by
roundtrip in tests/test_entropy.py.

The decoder is one ``lax.scan`` (:func:`rans_decode`) on every backend. It
has no Pallas form: each step gathers from the 4096-slot table and from
per-lane byte positions, and Mosaic lowers only 2-D gathers, so a fused
kernel is refused on TPU. Decode is one sequential pass per downlink
payload, off the training hot path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

SCALE_BITS = 12           # frequency resolution: sum(freq) == 1 << SCALE_BITS
TAB = 1 << SCALE_BITS
L = 1 << 23               # lower bound of the state interval [L, 2**31)
LANES = 16                # interleaved independent coder states
RENORMS = 2               # max bytes emitted/consumed per symbol per lane
# encoder renorm threshold shift: x must drop below f * 2**_THRESH_SHIFT
# before encoding f; equals ((L >> SCALE_BITS) << 8) = 2**19
_THRESH_SHIFT = 23 - SCALE_BITS + 8

def _lane_ids():
    # rebuilt per call: a cached module-level constant would leak tracers
    # out of whatever trace first materialized it
    return jnp.arange(LANES, dtype=jnp.int32)


def n_steps(n_syms: int) -> int:
    """Scan rows for an n-symbol stream (>=1 so scans never degenerate)."""
    return max(1, -(-int(n_syms) // LANES))


def buf_cols(n_syms: int) -> int:
    """Per-lane byte capacity: RENORMS bytes per row is an airtight
    structural bound (each renorm emits one byte, at most RENORMS fire),
    so the static buffer can never overflow."""
    return RENORMS * n_steps(n_syms)


def _sym_rows(syms: Array) -> Array:
    """(n,) symbols -> (steps, LANES) rows, zero-padded at the tail."""
    n = syms.shape[0]
    steps = n_steps(n)
    pad = steps * LANES - n
    return jnp.pad(syms.astype(jnp.int32), (0, pad)).reshape(steps, LANES)


def rans_encode(syms: Array, freq: Array, cum: Array):
    """Encode a symbol stream against a static table.

    ``syms`` — (n,) integer symbols in [0, 256); ``freq``/``cum`` —
    (256,) int32 frequency table and its exclusive cumsum (sum(freq) ==
    4096, every entry >= 1). Returns ``(buf, state, lens)``: the coded
    byte planes (LANES, buf_cols(n)) u8 (lane ``l``'s stream is
    ``buf[l, :lens[l]]``), the per-lane final states (LANES,) i32, and
    the per-lane byte counts (LANES,) i32. True coded size is
    ``sum(lens)`` + 8 bytes/lane of state+length — always <= the static
    buffer bound.
    """
    rows = _sym_rows(syms)
    cols = buf_cols(syms.shape[0])
    lane = _lane_ids()
    freq = freq.astype(jnp.int32)
    cum = cum.astype(jnp.int32)

    def step(carry, row):
        x, buf, ptr = carry
        f = freq[row]
        c = cum[row]
        thresh = f << _THRESH_SHIFT
        # byte renormalization: emit low bytes until x < f * 2**19.
        # RENORMS iterations bound the loop statically (see module doc).
        for _ in range(RENORMS):
            emit = x >= thresh
            byte = (x & 0xFF).astype(jnp.uint8)
            # masked scatter: lanes not emitting write to column `cols`,
            # which mode='drop' discards
            col = jnp.where(emit, ptr, cols)
            buf = buf.at[lane, col].set(byte, mode="drop")
            x = jnp.where(emit, x >> 8, x)
            ptr = ptr + emit.astype(jnp.int32)
        x = ((x // f) << SCALE_BITS) + (x % f) + c
        return (x, buf, ptr), None

    x0 = jnp.full((LANES,), L, jnp.int32)
    buf0 = jnp.zeros((LANES, cols), jnp.uint8)
    ptr0 = jnp.zeros((LANES,), jnp.int32)
    # reverse scan: rANS is a stack — encode last symbol first so the
    # forward decoder pops them in order
    (x, buf, lens), _ = jax.lax.scan(step, (x0, buf0, ptr0), rows,
                                     reverse=True)
    return buf, x, lens


def _decode_step(x, rpos, buf, freq, cum, slot2sym, cols):
    """One row of the forward decode: pop LANES symbols, renorm by
    reading each lane's stream backward."""
    lane = _lane_ids()
    slot = x & (TAB - 1)
    sym = slot2sym[slot]
    f = freq[sym]
    c = cum[sym]
    x = f * (x >> SCALE_BITS) + slot - c
    for _ in range(RENORMS):
        need = x < L
        byte = buf[lane, jnp.clip(rpos, 0, cols - 1)].astype(jnp.int32)
        x = jnp.where(need, (x << 8) | byte, x)
        rpos = rpos - need.astype(jnp.int32)
    return x, rpos, sym


def rans_decode(buf: Array, state: Array, lens: Array, n: int,
                freq: Array, cum: Array, slot2sym: Array) -> Array:
    """``lax.scan`` inverse of :func:`rans_encode`.
    ``n`` is the static symbol count; returns (n,) int32 symbols."""
    steps = n_steps(n)
    cols = buf.shape[1]
    freq = freq.astype(jnp.int32)
    cum = cum.astype(jnp.int32)
    slot2sym = slot2sym.astype(jnp.int32)

    def step(carry, _):
        x, rpos = carry
        x, rpos, sym = _decode_step(x, rpos, buf, freq, cum, slot2sym,
                                    cols)
        return (x, rpos), sym

    x0 = state.astype(jnp.int32)
    rpos0 = lens.astype(jnp.int32) - 1
    _, rows = jax.lax.scan(step, (x0, rpos0), None, length=steps)
    return rows.reshape(-1)[:n]
