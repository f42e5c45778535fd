"""BIHT back-projection update kernel: x' = x + τ · r @ Φ.

r: (n, S) residual, Φ: (S, D); the add into x is fused into the matmul
epilogue (x tile read once, written once).

``backproject_packed`` is the packed-codec variant (DESIGN.md §13): the
BIHT residual arrives as the two uint32 bit-planes (plus, minus) emitted by
``cs_project(mode="pack_sign_residual")`` and is unpacked INSIDE the kernel
to resid = 2·(plus − minus) ∈ {−2, 0, +2} — exactly the f32 values
``y − sign(Φx)`` takes on ±1 measurements, so the identical ``dot_general``
makes the packed loop bit-for-bit equal to the f32 loop while moving 1/16
of the residual bytes through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sign import PACK, unpack_bits

BN = 128
BD = 256
BS = 256   # contraction tile over S


def _validate(name, n, s, d, bn, bd, bs, *, packed=False):
    if n % bn or d % bd or s % bs:
        raise ValueError(
            f"{name}: shapes (n={n}, S={s}, D={d}) do not tile by "
            f"(bn={bn}, bd={bd}, bs={bs}); pad n to a row-tile multiple "
            f"(the ops.py wrappers do) or pass tiles= (DESIGN.md §13).")
    if packed and (s % PACK or bs % PACK):
        raise ValueError(
            f"{name}: packed residual needs S and the S-tile to be "
            f"multiples of {PACK}; got S={s}, bs={bs} (DESIGN.md §13).")


def _backproject_kernel(r_ref, phi_ref, x_ref, out_ref, acc_ref, *, n_bs,
                        tau):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        r_ref[...], phi_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_bs - 1)
    def _():
        out_ref[...] = (x_ref[...].astype(jnp.float32)
                        + tau * acc_ref[...]).astype(out_ref.dtype)


def _backproject_packed_kernel(plus_ref, minus_ref, phi_ref, x_ref, out_ref,
                               acc_ref, *, n_bs, tau):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # unpack the residual bit-planes in-VMEM: 2·(plus − minus) reproduces
    # the exact {−2, 0, +2} floats of the f32 residual tile
    resid = 2.0 * (unpack_bits(plus_ref[...], jnp.float32)
                   - unpack_bits(minus_ref[...], jnp.float32))
    acc_ref[...] += jax.lax.dot_general(
        resid, phi_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_bs - 1)
    def _():
        out_ref[...] = (x_ref[...].astype(jnp.float32)
                        + tau * acc_ref[...]).astype(out_ref.dtype)


def backproject(x: jnp.ndarray, resid: jnp.ndarray, phi: jnp.ndarray,
                tau: float, *, interpret: bool = False,
                tiles=None) -> jnp.ndarray:
    """x: (n, D); resid: (n, S); phi: (S, D) -> x + tau * resid @ phi.

    ``tiles=(bn, bd, bs)`` overrides the default VMEM tiling (see
    cs_project.project; the fused decode loop passes full-extent tiles in
    interpret mode for bit-parity with the einsum reference)."""
    n, d = x.shape
    s = phi.shape[0]
    if resid.shape != (n, s) or phi.shape != (s, d):
        raise ValueError(f"backproject: resid {resid.shape} / phi "
                         f"{phi.shape} inconsistent with x {x.shape}")
    bn, bd, bs = tiles if tiles else (min(BN, n), min(BD, d), min(BS, s))
    _validate("backproject", n, s, d, bn, bd, bs)
    n_bs = s // bs
    grid = (n // bn, d // bd, n_bs)
    return pl.pallas_call(
        functools.partial(_backproject_kernel, n_bs=n_bs, tau=tau),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bs), lambda i, j, k: (i, k)),   # resid
            pl.BlockSpec((bs, bd), lambda i, j, k: (k, j)),   # phi
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),   # x
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
        interpret=interpret,
    )(resid, phi, x)


def backproject_packed(x: jnp.ndarray, plus: jnp.ndarray, minus: jnp.ndarray,
                       phi: jnp.ndarray, tau: float, *,
                       interpret: bool = False, tiles=None) -> jnp.ndarray:
    """Packed-residual update: x + tau * (2·(plus − minus)) @ phi.

    plus/minus: uint32 (n, S//32) bit-planes from
    ``cs_project(mode="pack_sign_residual")``; unpacked in-tile
    (DESIGN.md §13). Bit-for-bit equal to ``backproject`` on the
    equivalent f32 residual under the same tiling."""
    n, d = x.shape
    s = phi.shape[0]
    if phi.shape != (s, d):
        raise ValueError(f"backproject_packed: phi {phi.shape} inconsistent "
                         f"with x {x.shape}")
    if plus.shape != (n, s // PACK) or minus.shape != (n, s // PACK) \
            or plus.dtype != jnp.uint32 or minus.dtype != jnp.uint32:
        raise ValueError(
            f"backproject_packed: bit-planes must be uint32 "
            f"(n, S//{PACK}) = ({n}, {s // PACK}); got {plus.dtype} "
            f"{plus.shape} / {minus.dtype} {minus.shape} (DESIGN.md §13)")
    # the bit-plane blocks span the whole packed width (see cs_project)
    bn, bd, bs = tiles if tiles else (min(BN, n), min(BD, d), s)
    _validate("backproject_packed", n, s, d, bn, bd, bs, packed=True)
    n_bs = s // bs
    grid = (n // bn, d // bd, n_bs)
    return pl.pallas_call(
        functools.partial(_backproject_packed_kernel, n_bs=n_bs, tau=tau),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bs // PACK), lambda i, j, k: (i, k)),  # plus
            pl.BlockSpec((bn, bs // PACK), lambda i, j, k: (i, k)),  # minus
            pl.BlockSpec((bs, bd), lambda i, j, k: (k, j)),          # phi
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),          # x
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
        interpret=interpret,
    )(plus, minus, phi, x)
