"""The early-exit top-k bisection is bitwise the fixed-trip search.

``topk_sparsify_bisect`` stops once every row's selection is settled;
``fixed_trip`` below is the search it replaced, ``iters`` passes always.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsify import _bisect_threshold, topk_sparsify_bisect
from repro.decode import biht_sign, hard_threshold_bisect

ROW = 256


def fixed_trip(g, k, iters=40):
    """The oracle: ``iters`` bisection passes, no early exit."""
    a = jnp.abs(g.astype(jnp.float32))
    hi = jnp.max(a, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    mask = a >= hi
    cnt_hi = jnp.sum(mask.astype(jnp.int32), axis=-1, keepdims=True)
    mask = jnp.where(cnt_hi >= k, mask, a >= lo)
    return g * mask, mask


def _rows(kind, k, rng):
    x = rng.standard_normal((4, ROW)).astype(np.float32)
    if kind == "ties_at_boundary":
        # the k-th and (k+1)-th largest magnitudes equal, the next one a
        # few ulps below them: such a row settles only at f32 resolution
        x = np.sort(np.abs(x), axis=-1)[:, ::-1].copy()
        x[:, k] = x[:, k - 1]
        if k + 1 < ROW:
            x[:, k + 1] = x[:, k] * np.float32(1 - 2 ** -21)
        x *= rng.choice([-1.0, 1.0], x.shape).astype(np.float32)
    elif kind == "ties_at_max":
        x[:, :min(k + 3, ROW)] = 7.0
    elif kind == "zero":
        x[:] = 0.0
    elif kind == "few_nonzero":
        x[:] = 0.0
        n = max(k - 1, 0)
        x[:, :n] = rng.standard_normal((4, n))
    elif kind == "subnormal":
        x = x * np.float32(1e-39)
    elif kind == "nan":
        x[1, 5] = np.nan
    elif kind == "mixed":
        x[0] = 0.0
        x[1, k:] = 0.0
        x[2, 3] = np.nan
        x[3] *= np.float32(1e-39)
    return jnp.asarray(x)


KINDS = ["gaussian", "ties_at_boundary", "ties_at_max", "zero",
         "few_nonzero", "subnormal", "nan", "mixed"]


def _equal(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("mode", ["eager", "jit", "vmap"])
@pytest.mark.parametrize("iters", [16, 40])
@pytest.mark.parametrize("k", [1, 8, 16, ROW - 1])
@pytest.mark.parametrize("kind", KINDS)
def test_early_exit_matches_fixed_trip(kind, k, iters, mode):
    x = _rows(kind, k, np.random.default_rng(k * 100 + iters))
    new = functools.partial(topk_sparsify_bisect, k=k, iters=iters)
    old = functools.partial(fixed_trip, k=k, iters=iters)
    if mode == "jit":
        new, old = jax.jit(new), jax.jit(old)
    elif mode == "vmap":
        # one batch element a row: each settles on its own
        x = x[:, None, :]
        new, old = jax.vmap(new), jax.vmap(old)
    # assert_array_equal holds NaN equal to NaN
    _equal(new(x), old(x))


@pytest.mark.parametrize("kind", KINDS)
def test_odd_cap_matches_fixed_trip(kind):
    """An odd ``iters`` takes its odd pass before the two-pass loop."""
    x = _rows(kind, 8, np.random.default_rng(15))
    new = jax.jit(functools.partial(topk_sparsify_bisect, k=8, iters=15))
    old = jax.jit(functools.partial(fixed_trip, k=8, iters=15))
    _equal(new(x), old(x))


def test_exit_engages_on_gaussian_rows():
    a = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (64, 2048)))
    search = jax.jit(_bisect_threshold, static_argnums=(1, 2))
    # top-1 is each row's max: settled before the first pass
    assert int(search(a, 1, 40)[3]) == 0
    for k in (8, 16):
        passes = int(search(a, k, 40)[3])
        assert 0 < passes < 40, (k, passes)


@pytest.mark.parametrize("iters", [15, 16, 40])
def test_row_with_fewer_than_k_nonzeros_runs_every_pass(iters):
    a = np.zeros((64, 2048), np.float32)
    a[:, :100] = np.abs(np.random.default_rng(0).standard_normal((64, 100)))
    a[7, 3:] = 0.0                       # three non-zeros, k = 8
    passes = int(_bisect_threshold(jnp.asarray(a), 8, iters)[3])
    assert passes == iters


def test_biht_decode_matches_fixed_trip_oracle():
    """BIHT with the bisection threshold at the zoo's ratios, scaled
    down (S 32, D_c 2048, kappa 16, 10 iterations, 64 rows)."""
    S, D, kappa, rows = 32, 2048, 16, 64
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(11), 3)
    phi = jax.random.normal(k0, (S, D)) / jnp.sqrt(S)
    g = jax.random.normal(k1, (rows, D))
    sparse, _ = fixed_trip(g, 8)
    y = jnp.sign(jnp.einsum("sd,nd->ns", phi, sparse)
                 + 0.1 * jax.random.normal(k2, (rows, S)))

    def decode(ht):
        return jax.jit(lambda y: biht_sign(y, phi, kappa, iters=10,
                                           ht_fn=ht))(y)

    want = decode(lambda x, k: fixed_trip(x, k)[0])
    got = decode(hard_threshold_bisect)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
