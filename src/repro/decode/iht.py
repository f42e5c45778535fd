"""1-bit CS decoder family — einsum reference implementations (eq. 43).

The PS solves  min ||x||_1  s.t. ||ŷ − Φx||² ≤ ε  (eq. 43). This module is
the iterative-hard-thresholding family the paper selects (BIHT, Jacques et
al.), plus the adaptive-step and warm-start variants the registry exposes
(DESIGN.md §9):

- ``iht``: x ← η_κ(x + τ Φᵀ(ŷ − Φx)) on the REAL post-processed aggregate ŷ
  (the paper's analysis, eq. 42-44, treats the 1-bit error as bounded noise
  on real measurements).
- ``niht``: normalized IHT (Blumensath & Davies 2010) — the step size is
  recomputed every iteration as μ = ||g||²/||Φg||² with g the gradient
  restricted to the current support, removing the fixed-τ tuning knob.
- ``biht_sign``: the classic single-worker BIHT with sign-consistency
  updates x ← η_κ(x + (τ/S) Φᵀ(y_sign − sign(Φx))), unit-normalized.

All decoders accept ``x0``, the warm-start iterate: round *t* of the FL
loop can seed the decode with round *t−1*'s estimate, exploiting temporal
gradient correlation (DESIGN.md §9; state handling lives in
``repro.fl.rounds``). ``x0=None`` is the cold start from zeros (``iht``)
or from the thresholded back-projection (``biht_sign``).

Magnitude note: sign measurements are scale-invariant, so the decoders
recover direction; the aggregator transmits one extra analog scalar per
worker (the sparsified-gradient norm) to restore scale — standard "norm
estimation" in the 1-bit CS literature, recorded in DESIGN.md §4.

These are the allclose/bitwise oracles for the fused-Pallas hot loop in
``repro.decode.fused``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ref import sign_pm1


def hard_threshold(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """η_κ: keep the k largest-|.| entries along the last axis (eq. 6).
    Mask scattered from ``lax.top_k`` indices — exactly k survivors, ties
    broken by value order then lowest index (see
    ``core.sparsify.topk_sparsify`` for the cumsum-fusion perf note)."""
    absx = jnp.abs(x)
    _, idx = jax.lax.top_k(absx, k)
    mask = jnp.zeros(x.shape, bool)
    mask = jnp.put_along_axis(mask, idx, True, axis=-1, inplace=False)
    return x * mask


def hard_threshold_bisect(x: jnp.ndarray, k: int,
                          iters: int = 40) -> jnp.ndarray:
    """η_κ via magnitude-threshold bisection — the SPMD-partitionable
    variant (``jax.lax.top_k`` lowers to a sort GSPMD cannot shard).
    ``iters`` caps the passes (resolution max·2^-iters): the search stops
    once every row's top-κ set is settled, with output bitwise that of
    ``iters`` passes; under GSPMD the stop test costs one scalar
    all-reduce every two passes (``core.sparsify.topk_sparsify_bisect``)."""
    from repro.core.sparsify import topk_sparsify_bisect  # lazy: decode
    # never imports repro.core at module scope (core imports decode)
    return topk_sparsify_bisect(x, k, iters=iters)[0]


#: Divergence edge for the fixed-step update x ← η_κ(x + τΦᵀ(y − Φx)):
#: on the iterate support the map is I − τΦ_TᵀΦ_T, whose spectrum stays in
#: (−1, 1] iff τ·λ(Φ_TᵀΦ_T) < 2 — the classical gradient-descent bound.
#: Measured: at κ̄ = S_c/2 the restricted estimate λ̂ ≈ 4.4 (S=512) / 5.0
#: (S=1024), and the iterate blows up exactly where τ·λ̂ crosses 2
#: (stable at 1.75, diverged at 2.005) — see tests/test_decode.py.
IHT_STABILITY_BOUND = 2.0


def restricted_spectral_estimate(phi: jnp.ndarray, k: int,
                                 iters: int = 20) -> jnp.ndarray:
    """λ̂ ≈ max λ(Φ_TᵀΦ_T) over k-sparse supports T — the quantity that
    decides fixed-step IHT stability (DESIGN.md §13).

    Hard-thresholded power iteration from a deterministic all-ones start:
    v ← η_k(ΦᵀΦ v)/‖·‖. The fixed-step update x ← η_κ(x + τΦᵀ(y − Φx))
    contracts on the iterate support only when τ·λ̂ < 2
    (``IHT_STABILITY_BOUND``); at the default decode budget κ̄ = S_c/2 the
    estimate is ≈4.4–5.0 for Gaussian Φ with the 1/S normalization, so the
    edge sits at τ ≈ 0.4–0.46 — consistent with the conservatively pinned
    τ = 0.25 and the silent divergence beyond it (CHANGES PR-2 note,
    benchmarks/decoders_bench.py). Traceable (scan + top_k), so it also
    runs under jit for the cond-based fallback."""
    d = phi.shape[1]
    s = min(k, d)

    def step(v, _):
        w = hard_threshold(jnp.einsum("sd,s->d", phi,
                                      jnp.einsum("sd,d->s", phi, v)), s)
        nrm = jnp.linalg.norm(w)
        return w / jnp.maximum(nrm, 1e-30), None

    v0 = jnp.full((d,), 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)),
                  phi.dtype)
    v, _ = jax.lax.scan(step, v0, None, length=iters)
    pv = jnp.einsum("sd,d->s", phi, v)
    return jnp.sum(pv * pv) / jnp.maximum(jnp.sum(v * v), 1e-30)


def iht_step_stable(phi: jnp.ndarray, k: int, tau: float,
                    iters: int = 20) -> jnp.ndarray:
    """Traced bool: is the fixed step τ below the restricted stability
    edge τ·λ̂ < 2 (``IHT_STABILITY_BOUND``, DESIGN.md §13)?"""
    return (restricted_spectral_estimate(phi, k, iters) * tau
            < IHT_STABILITY_BOUND)


def iht(y: jnp.ndarray, phi: jnp.ndarray, k: int, iters: int = 10,
        tau: float = 1.0, ht_fn=None, x0=None) -> jnp.ndarray:
    """Fixed-step IHT on real measurements (eq. 43). y: (..., S);
    phi: (S, D). Returns (..., D).

    tau is scaled by 1/||Φ||² proxy = 1 (Φ has unit spectral norm in
    expectation under the 1/S normalization). ``x0`` warm-starts the
    iterate (defaults to zeros — the cold start)."""
    ht = ht_fn or hard_threshold

    def step(x, _):
        resid = y - jnp.einsum("sd,...d->...s", phi, x)
        x = x + tau * jnp.einsum("sd,...s->...d", phi, resid)
        return ht(x, k), None

    if x0 is None:
        x0 = jnp.zeros(y.shape[:-1] + (phi.shape[1],), y.dtype)
    x, _ = jax.lax.scan(step, x0, None, length=iters)
    return x


def niht(y: jnp.ndarray, phi: jnp.ndarray, k: int, iters: int = 10,
         ht_fn=None, x0=None) -> jnp.ndarray:
    """Normalized IHT (eq. 43 with an adaptive step).

    Per iteration the step μ = ||g_Λ||²/||Φ g_Λ||² is exact line search
    along the support-restricted gradient g_Λ (Λ = supp(x); the full
    gradient when the support is empty, i.e. the cold first step). Costs
    one extra projection per iteration over ``iht`` but needs no τ."""
    ht = ht_fn or hard_threshold

    def step(x, _):
        resid = y - jnp.einsum("sd,...d->...s", phi, x)
        g = jnp.einsum("sd,...s->...d", phi, resid)
        on_support = jnp.any(x != 0, axis=-1, keepdims=True)
        gs = jnp.where(on_support, g * (x != 0), g)
        num = jnp.sum(gs * gs, axis=-1, keepdims=True)
        pg = jnp.einsum("sd,...d->...s", phi, gs)
        den = jnp.sum(pg * pg, axis=-1, keepdims=True)
        mu = num / jnp.maximum(den, 1e-30)
        return ht(x + mu * g, k), None

    if x0 is None:
        x0 = jnp.zeros(y.shape[:-1] + (phi.shape[1],), y.dtype)
    x, _ = jax.lax.scan(step, x0, None, length=iters)
    return x


def biht_sign(y_sign: jnp.ndarray, phi: jnp.ndarray, k: int, iters: int = 30,
              tau: float = 1.0, ht_fn=None, x0=None) -> jnp.ndarray:
    """Classic BIHT (sign-consistency subgradient, eq. 43 on sign
    measurements), unit-norm output. ``x0`` warm-starts the iterate
    (default: the thresholded back-projection η_κ(Φᵀy/S))."""
    S = phi.shape[0]
    ht = ht_fn or hard_threshold

    def step(x, _):
        resid = y_sign - sign_pm1(jnp.einsum("sd,...d->...s", phi, x))
        x = x + (tau / S) * jnp.einsum("sd,...s->...d", phi, resid)
        x = ht(x, k)
        return x, None

    if x0 is None:
        x0 = jnp.einsum("sd,...s->...d", phi, y_sign) / S
        x0 = ht(x0, k)
    x, _ = jax.lax.scan(step, x0, None, length=iters)
    norm = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.maximum(norm, 1e-12)
