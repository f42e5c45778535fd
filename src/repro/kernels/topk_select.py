"""Per-chunk top-κ selection kernel (the sparse_κ operator, eq. 6).

A sort-free magnitude-threshold search: 32 rounds of bisection on the
per-row threshold t such that #{|x| ≥ t} = κ, entirely in VMEM (vector unit
work, no MXU). Exact for rows with distinct magnitudes — bisection resolves
the gap between the κ-th and (κ+1)-th magnitude; ties may admit >κ entries
(measure-zero for float gradients; the jnp oracle breaks ties by index).

Each program owns a (rows, D) row-block of at most BLOCK_ELEMS elements, so
zoo chunks (D_c=16384) take 16 rows per program and stay within VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 64
N_BISECT = 32
BLOCK_ELEMS = 64 * 4096   # rows x D per program: ~1 MiB f32 (VMEM budget)


def row_tile(n: int, d: int) -> int:
    """Rows per program for an (n, D) input: all n when they fit, else the
    largest multiple of 8 that divides n within BN and the BLOCK_ELEMS
    budget; with no such divisor, the cap itself (the caller pads n)."""
    cap = min(BN, max(8, BLOCK_ELEMS // d // 8 * 8))
    if n <= cap:
        return n
    return next((bn for bn in range(cap, 7, -8) if n % bn == 0), cap)


def _topk_kernel(x_ref, val_ref, mask_ref, *, k):
    x = x_ref[...]
    a = jnp.abs(x.astype(jnp.float32))
    hi = jnp.max(a, axis=-1, keepdims=True)            # (bn, 1)
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        # too many selected -> raise threshold; too few -> lower it
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, N_BISECT, body, (lo, hi))
    # lo is the largest tested threshold with count > k; select with hi
    thr = jnp.minimum(hi, jnp.max(a, axis=-1, keepdims=True))
    # guarantee at least k selected: fall back to lo when hi overshoots.
    # The choice is made on the per-row threshold: Mosaic cannot select
    # between boolean vectors.
    cnt_hi = jnp.sum((a >= thr).astype(jnp.int32), axis=-1, keepdims=True)
    mask = a >= jnp.where(cnt_hi >= k, thr, lo)
    val_ref[...] = (x * mask).astype(val_ref.dtype)
    mask_ref[...] = mask.astype(mask_ref.dtype)


def topk_select(chunks: jnp.ndarray, k: int, *, interpret: bool = False,
                bn: int = None):
    """chunks: (n, D). Returns (masked values, int32 {0, 1} mask).

    ``bn`` overrides the rows-per-program tile (the fused decode loop keeps
    all rows in one program in interpret mode)."""
    n, d = chunks.shape
    bn = row_tile(n, d) if bn is None else bn
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)
    val, mask = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                   pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, d), chunks.dtype),
                   jax.ShapeDtypeStruct((n, d), jnp.int32)],
        interpret=interpret,
    )(chunks)
    return val, mask
