"""Greedy prefix-evaluation sweep kernel (P2 scheduling, DESIGN.md §10).

Evaluates R_t for every prefix of the channel-cap ordering — the inner
sweep of the vectorized greedy scheduler — from the sufficient-statistic
form: R depends on a prefix only through its length s1, its weight mass
s2 = ΣK_i (a running cumulative sum) and its min-cap b (the prefix's last
element under the descending sort). Sort-free and segmented: the sort
stays outside (jnp ``argsort``); the kernel tiles the sorted (B, U) arrays
over U and carries the running ΣK between grid steps in VMEM scratch, so
U ≥ 8192 sweeps stream through without materialising anything but the
(B, U) prefix-R output.

Per-batch-row scalar coefficients arrive packed as a (B, 8) f32 matrix
(``pack order: Ktot, ρ1, A, E, N``; see ``prefix_rt``) so one BlockSpec
feeds every tile. In interpret mode the default tile spans the full U
extent (padded to whole 128-lane segments), mirroring the fused-decode
tiling policy of DESIGN.md §9. The in-tile prefix sums are triangular
matmuls (Mosaic lowers no cumsum); they are exact wherever the partial
sums of K are, as with integer sample counts, and then the kernel is
bit-for-bit with the jnp cumsum sweep (tests/test_sched.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BB = 8        # batch rows per tile
BU = 512      # prefix positions per tile (lane-aligned)
LANE = 128    # prefix positions per in-tile triangular matmul
N_COEF = 8    # packed per-row scalar coefficients (5 used, lane padding)


def prefix_rt(s1, s2, b, *, ktot, rho1, A, E, N):
    """R_t from the prefix sufficient statistics (eq. 24 regrouped):

        R(s1, s2, b) = ρ1 (Ktot − s2)/Ktot + A + N/(s2·b)² + s1·E

    Shared verbatim by the jnp sweep, the batched flip-polish and this
    kernel — identical op order is what makes the full-extent interpret
    tile bit-for-bit with the jnp path (DESIGN.md §10)."""
    return rho1 * (ktot - s2) / ktot + A + N / (s2 * b) ** 2 + s1 * E


def _prefix_kernel(caps_ref, k_ref, coef_ref, out_ref, s2_ref, *, bu):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # Mosaic has no cumsum: each LANE-wide segment's inclusive prefix sum
    # is one matmul with an upper-triangular ones matrix, plus the carried
    # running sum (exact wherever the partial sums are, e.g. integer K)
    w = min(LANE, bu)
    rows = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    tri = (rows <= cols).astype(jnp.float32)
    coef = coef_ref[...]
    s2 = s2_ref[...]                                    # (bb, 1)
    for c in range(bu // w):
        seg = pl.ds(c * w, w)
        k = k_ref[:, seg].astype(jnp.float32)           # (bb, w)
        s2 = s2 + jnp.dot(k, tri, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        base = (j * bu + c * w + 1).astype(jnp.float32)
        s1 = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1).astype(
            jnp.float32) + base
        out_ref[:, seg] = prefix_rt(
            s1, s2, caps_ref[:, seg].astype(jnp.float32),
            ktot=coef[:, 0:1], rho1=coef[:, 1:2], A=coef[:, 2:3],
            E=coef[:, 3:4], N=coef[:, 4:5]).astype(out_ref.dtype)
        s2 = s2[:, -1:]
    s2_ref[...] = s2


def prefix_eval(caps_sorted: jnp.ndarray, k_sorted: jnp.ndarray,
                coefs: jnp.ndarray, *, interpret: bool = False,
                tiles=None) -> jnp.ndarray:
    """caps_sorted, k_sorted: (B, U) descending-cap order; coefs: (B, 8)
    packed [Ktot, ρ1, A, E, N, 0, 0, 0]. Returns the (B, U) prefix-R_t
    matrix (argmin stays with the caller — it is O(U) in jnp).

    ``tiles=(bb, bu)`` overrides the tiling; the interpret-mode default is
    a full-extent U tile for bitwise parity with the jnp sweep."""
    B, U = caps_sorted.shape
    assert k_sorted.shape == (B, U) and coefs.shape == (B, N_COEF)
    if tiles:
        bb, bu = tiles
    else:
        full = -(-U // LANE) * LANE        # U padded to whole segments
        bb, bu = min(BB, B), (full if interpret else min(BU, full))
    if bu > LANE and bu % LANE:
        raise ValueError(f"prefix_eval: U-tile {bu} must be <= {LANE} or a "
                         f"multiple of it")
    pad_b, pad_u = (-B) % bb, (-U) % bu
    if pad_b or pad_u:
        caps_sorted = jnp.pad(caps_sorted, ((0, pad_b), (0, pad_u)),
                              constant_values=1.0)
        k_sorted = jnp.pad(k_sorted, ((0, pad_b), (0, pad_u)),
                           constant_values=1.0)
        coefs = jnp.pad(coefs, ((0, pad_b), (0, 0)), constant_values=1.0)
    bp, up = B + pad_b, U + pad_u
    grid = (bp // bb, up // bu)
    out = pl.pallas_call(
        functools.partial(_prefix_kernel, bu=bu),
        grid=grid,
        in_specs=[pl.BlockSpec((bb, bu), lambda i, j: (i, j)),
                  pl.BlockSpec((bb, bu), lambda i, j: (i, j)),
                  pl.BlockSpec((bb, N_COEF), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((bb, bu), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, up), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, 1), jnp.float32)],
        interpret=interpret,
    )(caps_sorted, k_sorted, coefs)
    return out[:B, :U]
