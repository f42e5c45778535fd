"""Plain reference of the over-the-air 1-bit uplink (arXiv:2103.16055 §II).

Written from the paper's equations, in float32, with none of the program's
code: per-chunk top-kappa (eq. 6) with error feedback, the Gaussian
projection Phi_c ~ N(0, 1/S_c) and its sign (eq. 7), block Rayleigh fading
with channel-inversion power control (eq. 10-11), the MAC sum plus AWGN and
the post-processing division (eq. 12-13), the BIHT decode (eq. 43) with
the magnitude of the sparsified gradient restored per chunk.

The shared random draws follow the system's documented protocol, so both
sides see the same Phi, fades and noise: Phi from ``PRNGKey(phi_seed)``;
per round ``k_t = fold_in(key, t)``, fades from ``fold_in(k_t, 0)`` and
noise from ``fold_in(k_t, 1)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

H_MIN = 1e-3
HIGHEST = jax.lax.Precision.HIGHEST


# -- matmul precision policies ---------------------------------------------------


def matmul(a, b):
    """float32 matmul at full precision."""
    return jnp.matmul(a, b, precision=HIGHEST)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _bf16_dot(a, b):
    # bfloat16 operands: every product is exact in float32, sums in float32
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def matmul_high(a, b):
    """float32 matmul in three bfloat16 passes, a_hi b_hi + a_hi b_lo +
    a_lo b_hi: the TPU's ``high`` precision, written out so that every
    device computes it alike. Its gradient is made the same way. 2-D
    operands."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return _bf16_dot(ah, bh) + (_bf16_dot(ah, bl) + _bf16_dot(al, bh))


def _high_fwd(a, b):
    return matmul_high(a, b), (a, b)


def _high_bwd(res, g):
    a, b = res
    return matmul_high(g, b.T), matmul_high(a.T, g)


matmul_high.defvjp(_high_fwd, _high_bwd)

MATMULS = {"highest": matmul, "high": matmul_high}


def make_phi(seed: int, measure: int, chunk: int):
    """Phi_c with i.i.d. N(0, 1/S_c) entries, shape (S_c, D_c)."""
    return (jax.random.normal(jax.random.PRNGKey(seed), (measure, chunk))
            / jnp.sqrt(jnp.float32(measure)))


def topk(x, k: int):
    """Keep the k largest-magnitude entries of each row, zero the rest."""
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    keep = jnp.zeros(x.shape, bool)
    keep = jnp.put_along_axis(keep, idx, True, axis=-1, inplace=False)
    return jnp.where(keep, x, 0.0)


def sign(x):
    """+1 where x >= 0, else -1 (the sign of zero is +1)."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(jnp.float32)


def fades(key, n_workers: int):
    """|h| of one round of i.i.d. CN(0, 1) block fading, clamped at H_MIN."""
    re, im = jax.random.split(key)
    g = (jax.random.normal(re, (n_workers,))
         + 1j * jax.random.normal(im, (n_workers,))) / jnp.sqrt(2.0)
    return jnp.maximum(jnp.abs(g.astype(jnp.complex64)), H_MIN)


def noise(key, shape, noise_var):
    return jax.random.normal(key, shape) * jnp.sqrt(jnp.float32(noise_var))


def biht(y, phi, k: int, iters: int, tau: float, mm=jnp.matmul):
    """Binary iterative hard thresholding on each row of y, unit-norm rows:
    x0 = H_k(Phi^T y / S), x <- H_k(x + tau/S Phi^T (y - sign(Phi x)))."""
    S = phi.shape[0]
    x = topk(mm(y, phi) / S, k)
    for _ in range(iters):
        x = topk(x + (tau / S) * mm(y - sign(mm(x, phi.T)), phi), k)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                           1e-12)


def decode(y, mbar, phi, k: int, iters: int, tau: float, mm=jnp.matmul):
    """BIHT direction scaled to the received mean magnitude per chunk."""
    x = biht(y, phi, k, iters, tau, mm)
    norm = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x * (mbar[:, None] / jnp.maximum(norm, 1e-12))


def uplink(sparse, phi, mm=jnp.matmul):
    """One worker's upload for its top-kappa chunks: signs and magnitudes."""
    return sign(mm(sparse, phi.T)), jnp.linalg.norm(sparse, axis=-1)


def aggregate(signs, mags, weights, b_t, noise_field):
    """MAC sum of weighted sign symbols plus noise, divided by the total
    power scale (eq. 12-13); the magnitudes are weight-averaged.

    signs (U, n, S), mags (U, n), weights (U,) = K_i beta_i."""
    y = jnp.einsum("u,uns->ns", weights * b_t, signs)
    wsum = jnp.sum(weights)
    y = (y + noise_field) / jnp.maximum(wsum * b_t, 1e-12)
    mbar = jnp.einsum("u,un->n", weights, mags) / jnp.maximum(wsum, 1e-12)
    return y, mbar


def adam(master, m, v, t, g, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step (Kingma & Ba) with bias correction; t counts from 1."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return master - lr * mh / (jnp.sqrt(vh) + eps), m, v
