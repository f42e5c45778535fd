"""Canonical 1-bit sign convention + the 32-per-uint32 packed codec.

This module is THE definition of sign(0) for the whole repo (DESIGN.md §13):
``sign_pm1`` maps 0 to +1 (``x >= 0``), as required for the
gradient-independent power constraint (paper eq. 11) — every transmitted
symbol must be ±1, never 0. The Pallas epilogues (kernels/cs_project.py),
the jnp oracles (kernels/ref.py) and the quantizer (core/quantize.py) all
import it from here; with packed words a convention mismatch would corrupt
a whole 32-lane word, not one symbol, so there is exactly one definition.

Packed codec contract (DESIGN.md §13):
- 32 signs per uint32 word along the LAST axis; the last axis length must
  be a multiple of ``PACK`` (= 32).
- Word ``j`` covers lanes ``[32j, 32j+32)``; bit ``b`` (LSB-first) is lane
  ``32j + b``.
- bit = 1  ⇔  sign = +1  ⇔  the pre-sign value was >= 0.

``pack_signs`` applies ``x >= 0`` directly, so it both packs ±1 symbol
arrays exactly AND acts as a fused sign+pack on raw projections (eq. 7) —
the two uses agree bit for bit because ``sign_pm1`` uses the same
predicate. ``unpack_signs`` reproduces the exact ±1.0 floats ``sign_pm1``
would have produced, which is what makes the packed kernel paths
bit-for-bit equal to the f32 sign paths: identical values into identical
``dot_general``/einsum contractions.

Everything here is plain jnp so it is usable both outside kernels and
inside Pallas kernel bodies (interpret mode on CPU, Mosaic on TPU). The
bit arithmetic runs in int32 and bitcasts to uint32 at the edges: Mosaic
neither reduces nor converts unsigned integers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PACK = 32  # signs per uint32 word


def sign_pm1(x: jnp.ndarray) -> jnp.ndarray:
    """Strict ±1 sign, sign(0) := +1 (paper eq. 7/11). Never returns 0."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _shifts() -> jnp.ndarray:
    return jnp.arange(PACK, dtype=jnp.uint32)


def packed_width(n_lanes: int) -> int:
    """Words needed for ``n_lanes`` signs (must divide exactly)."""
    if n_lanes % PACK:
        raise ValueError(
            f"packed codec needs the sign axis to be a multiple of "
            f"{PACK}; got {n_lanes} (DESIGN.md §13)")
    return n_lanes // PACK


def pack_signs(x: jnp.ndarray) -> jnp.ndarray:
    """(..., S) real -> (..., S//32) uint32; bit = 1 ⇔ x >= 0 (sign +1).

    Exact on ±1 symbol arrays and equally valid on raw projections (the
    fused sign+pack of eq. 7): both reduce to the ``x >= 0`` predicate."""
    w = packed_width(x.shape[-1])
    bits = (x >= 0).reshape(x.shape[:-1] + (w, PACK)).astype(jnp.uint32)
    return jnp.sum(bits << _shifts(), axis=-1, dtype=jnp.uint32)


def pack_bool(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., S) bool -> (..., S//32) uint32 (kernel-epilogue helper).

    The sign axis is moved to the sublanes before it is split into words:
    Mosaic cannot split the lane axis of a (rows, S) tile. The 32 shifted
    bits of a word are distinct powers of two, so their int32 sum is the
    word's bit pattern exactly (bit 31 wraps to the sign bit)."""
    s = bits.shape[-1]
    w = packed_width(s)
    rows = bits.reshape((-1, s)).astype(jnp.int32).T      # (S, m)
    b = rows.reshape((w, PACK, rows.shape[-1]))
    shifts = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    words = jnp.sum(b << shifts, axis=1).T                # (m, w)
    return jax.lax.bitcast_convert_type(
        words.reshape(bits.shape[:-1] + (w,)), jnp.uint32)


def unpack_bits(packed: jnp.ndarray, dtype=jnp.int32) -> jnp.ndarray:
    """(..., W) uint32 -> (..., W*32) {0, 1} in ``dtype``."""
    words = jax.lax.bitcast_convert_type(packed, jnp.int32)
    bits = (words[..., None] >> jnp.arange(PACK, dtype=jnp.int32)) & 1
    return bits.reshape(packed.shape[:-1] + (-1,)).astype(dtype)


def unpack_signs(packed: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """(..., W) uint32 -> (..., W*32) exact ±1 in ``dtype``.

    Bit-for-bit inverse of ``pack_signs`` on ±1 data: reproduces the same
    float values ``sign_pm1`` produces, so downstream contractions match
    the f32 sign path exactly."""
    bits = unpack_bits(packed, jnp.float32)
    return (2.0 * bits - 1.0).astype(dtype)
