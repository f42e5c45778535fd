"""Fused Φ-projection + 1-bit sign kernel (the OBCSAA compression hot spot).

Computes sign(chunks @ Φᵀ) with MXU-aligned VMEM tiles. The sign epilogue is
fused into the final accumulation step, so on TPU the dense (n, S) projection
never round-trips HBM — only the ±1 symbols are written out.

Variants (shared kernel body, different epilogues):
- ``mode="sign"``:           sign(x Φᵀ)           (eq. 7 compression)
- ``mode="pack"``:           pack32(sign(x Φᵀ))   (packed codec, uint32 out)
- ``mode="sign_residual"``:  y − sign(x Φᵀ)       (BIHT residual step)
- ``mode="pack_sign_residual"``: the BIHT residual as TWO packed uint32
  bit-planes (plus, minus) with resid = 2·(plus − minus) — y arrives packed,
  the fresh signs are consumed in-kernel, and only 1/16 of the f32 residual
  bytes leave for the back-projection (DESIGN.md §13)
- ``mode="residual"``:       y − x Φᵀ             (IHT residual step, eq. 43)
- ``mode="none"``:           x Φᵀ                 (plain projection)

The residual epilogues are the decode-loop fusion boundary (DESIGN.md §9):
the dense (n, S) projection is consumed inside the kernel and never
round-trips HBM — only the residual leaves. sign(0) comes from the one
shared predicate in ``kernels/sign.py`` (DESIGN.md §13): the packed and f32
epilogues share ``acc >= 0``, which is what makes them bit-for-bit
interchangeable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sign import PACK, pack_bool, sign_pm1, unpack_bits

BN = 128   # chunk-rows per tile (MXU sublane-aligned)
BS = 128   # measurement rows per tile (lane-aligned)
BD = 512   # contraction tile: BN*BD + BS*BD + BN*BS f32 ≈ 0.6 MB VMEM

MODES = ("sign", "pack", "sign_residual", "pack_sign_residual", "residual",
         "none")
_PACKED_MODES = ("pack", "pack_sign_residual")
_Y_MODES = ("sign_residual", "pack_sign_residual", "residual")


def _epilogue(acc, mode, y_blk, dtype):
    if mode == "sign":
        return sign_pm1(acc).astype(dtype)
    if mode == "sign_residual":
        sgn = sign_pm1(acc)
        return (y_blk.astype(jnp.float32) - sgn).astype(dtype)
    if mode == "residual":
        return (y_blk.astype(jnp.float32) - acc).astype(dtype)
    return acc.astype(dtype)


def _proj_kernel(x_ref, phi_ref, out_ref, acc_ref, *, n_bd, mode):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], phi_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_bd - 1)
    def _():
        if mode == "pack":
            # fused sign+pack: same `acc >= 0` predicate as mode="sign",
            # 32 lanes per uint32 word (DESIGN.md §13)
            out_ref[...] = pack_bool(acc_ref[...] >= 0)
        else:
            out_ref[...] = _epilogue(acc_ref[...], mode, None, out_ref.dtype)


def _proj_resid_kernel(x_ref, phi_ref, y_ref, out_ref, acc_ref, *, n_bd,
                       mode):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], phi_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_bd - 1)
    def _():
        out_ref[...] = _epilogue(acc_ref[...], mode, y_ref[...],
                                 out_ref.dtype)


def _proj_pack_resid_kernel(x_ref, phi_ref, y_ref, plus_ref, minus_ref,
                            acc_ref, *, n_bd):
    """Packed BIHT residual: y packed in, (plus, minus) bit-planes out.

    resid = y − sign(x Φᵀ) ∈ {−2, 0, +2} when y is ±1; plus marks the +2
    lanes (y=+1, sign=−1), minus the −2 lanes. The fresh sign vector is
    consumed in-VMEM — it never exists in HBM in any dtype."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], phi_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_bd - 1)
    def _():
        sb = acc_ref[...] >= 0                       # shared sign predicate
        yb = unpack_bits(y_ref[...]) != 0
        plus_ref[...] = pack_bool(yb & ~sb)
        minus_ref[...] = pack_bool(sb & ~yb)


def validate_tiling(name: str, n: int, s: int, d: int, bn: int, bs: int,
                    bd: int, *, packed: bool = False):
    """Explicit shape/tile validation (DESIGN.md §13) — a silent mis-tile
    would corrupt output blocks, and a packed word straddling a tile edge
    would corrupt 32 lanes at once, so both are hard errors."""
    if n % bn or s % bs or d % bd:
        raise ValueError(
            f"{name}: shapes (n={n}, S={s}, D={d}) do not tile by "
            f"(bn={bn}, bs={bs}, bd={bd}). Pad n to a row-tile multiple "
            f"(the ops.py wrappers do), keep S and D multiples of the "
            f"module tiles, or pass tiles= explicitly (DESIGN.md §13).")
    if packed and (s % PACK or bs % PACK):
        raise ValueError(
            f"{name}: packed codec needs S and the S-tile to be multiples "
            f"of {PACK} (32 signs per uint32 word); got S={s}, bs={bs} "
            f"(DESIGN.md §13).")


def project(phi: jnp.ndarray, chunks: jnp.ndarray, *, mode: str = "sign",
            y: jnp.ndarray = None, interpret: bool = False,
            tiles=None):
    """phi: (S, D); chunks: (n, D); returns (n, S) — except the packed
    modes: ``mode="pack"`` returns uint32 (n, S//32) and
    ``mode="pack_sign_residual"`` (packed ±1 ``y`` in) returns the two
    uint32 bit-planes ``(plus, minus)``, each (n, S//32).

    Shapes must tile by (BN, BS, BD) after the ops.py wrapper's padding —
    validated with an explicit error, never silently mis-tiled.
    ``tiles=(bn, bs, bd)`` overrides the default VMEM tiling — the fused
    decode loop (repro.decode.fused) passes full-extent contraction tiles in
    interpret mode so the single in-kernel dot matches the einsum reference
    bit for bit (DESIGN.md §9)."""
    if mode not in MODES:
        raise ValueError(f"cs_project: unknown mode {mode!r}; one of "
                         f"{MODES} (DESIGN.md §13)")
    n, d = chunks.shape
    s = phi.shape[0]
    if phi.shape[1] != d:
        raise ValueError(f"cs_project: phi {phi.shape} does not contract "
                         f"with chunks {chunks.shape} (need phi (S, D))")
    packed = mode in _PACKED_MODES
    # a packed block spans the whole packed width (S//32 words): a narrower
    # one breaks the TPU's (8, 128) block rule
    bn, bs, bd = tiles if tiles else (min(BN, n), s if packed else min(BS, s),
                                      min(BD, d))
    validate_tiling("cs_project", n, s, d, bn, bs, bd, packed=packed)
    if mode in _Y_MODES and y is None:
        raise ValueError(f"cs_project: mode {mode!r} needs y")
    n_bd = d // bd
    grid = (n // bn, s // bs, n_bd)
    in_specs = [
        pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),   # chunks
        pl.BlockSpec((bs, bd), lambda i, j, k: (j, k)),   # phi
    ]
    args = [chunks, phi]
    if mode == "pack_sign_residual":
        if y.dtype != jnp.uint32 or y.shape != (n, s // PACK):
            raise ValueError(
                f"cs_project: pack_sign_residual needs packed y uint32 "
                f"(n, S//{PACK}) = ({n}, {s // PACK}); got {y.dtype} "
                f"{y.shape} (DESIGN.md §13)")
        in_specs.append(
            pl.BlockSpec((bn, bs // PACK), lambda i, j, k: (i, j)))
        args.append(y)
        return pl.pallas_call(
            functools.partial(_proj_pack_resid_kernel, n_bd=n_bd),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((bn, bs // PACK), lambda i, j, k: (i, j)),
                pl.BlockSpec((bn, bs // PACK), lambda i, j, k: (i, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, s // PACK), jnp.uint32),
                jax.ShapeDtypeStruct((n, s // PACK), jnp.uint32),
            ],
            scratch_shapes=[pltpu.VMEM((bn, bs), jnp.float32)],
            interpret=interpret,
        )(*args)
    if mode in ("sign_residual", "residual"):
        in_specs.append(pl.BlockSpec((bn, bs), lambda i, j, k: (i, j)))
        args.append(y)
        kernel = functools.partial(_proj_resid_kernel, n_bd=n_bd, mode=mode)
    else:
        kernel = functools.partial(_proj_kernel, n_bd=n_bd, mode=mode)
    if mode == "pack":
        out_specs = pl.BlockSpec((bn, bs // PACK), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((n, s // PACK), jnp.uint32)
    else:
        out_specs = pl.BlockSpec((bn, bs), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((n, s), chunks.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bn, bs), jnp.float32)],
        interpret=interpret,
    )(*args)
