"""Plain float32 reference of the paper's own round (arXiv:2103.16055 §V):
the 784-64-10 ReLU MLP, U workers with K samples each and full-batch
gradients (eq. 3), the joint schedule of problem P2 solved exactly by
enumerating every non-empty worker set (Algorithm 1, eq. 24), the 1-bit
uplink of ``reference.codec`` without error feedback, and the SGD update
(eq. 14). Also the benchmark's generator of its synthetic digits.

Nothing here imports the program.
"""
from __future__ import annotations

import itertools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import codec

FADE_INIT_FOLD = 0x7FADE


# -- data ------------------------------------------------------------------------


def _templates() -> np.ndarray:
    """One 28x28 stroke template per class (arcs and bars), fixed."""
    out = np.zeros((10, 28, 28), np.float32)
    yy, xx = np.mgrid[0:28, 0:28]
    for c in range(10):
        img = out[c]
        rng = np.random.default_rng(1000 + c)
        for s in range(2 + c % 3):
            cx, cy = rng.uniform(8, 20, 2)
            r = rng.uniform(4, 9)
            a0, a1 = sorted(rng.uniform(0, 2 * np.pi, 2))
            ang = np.arctan2(yy - cy, xx - cx)
            arc = (np.abs(np.hypot(yy - cy, xx - cx) - r) < 1.6) \
                & (ang > a0) & (ang < a1)
            img[arc] = 1.0
            if c % 2 == s % 2:
                x0 = int(rng.uniform(6, 18))
                img[6:22, x0:x0 + 2] = np.maximum(img[6:22, x0:x0 + 2], 0.9)
        out[c] = img / max(img.max(), 1e-6)
    return out


def make_digits(key, n: int):
    """n synthetic digits (n, 784) in [0, 1] and labels (n,): a class
    template rolled by up to 2 pixels each way, scaled by U(0.8, 1.2),
    plus N(0, 0.15^2) pixel noise, clipped."""
    kl, ks, ka, kn = jax.random.split(key, 4)
    y = jax.random.randint(kl, (n,), 0, 10)
    shift = jax.random.randint(ks, (n, 2), -2, 3)
    scale = jax.random.uniform(ka, (n,), minval=0.8, maxval=1.2)
    tmpl = jnp.asarray(_templates())

    def one(c, sh):
        return jnp.roll(tmpl[c], (sh[0], sh[1]), axis=(0, 1))

    x = jax.vmap(one)(y, shift) * scale[:, None, None] \
        + 0.15 * jax.random.normal(kn, (n, 28, 28))
    return jnp.clip(x, 0.0, 1.0).reshape(n, 784), y.astype(jnp.int32)


def make_data(key, n_workers: int, per_worker: int, n_test: int):
    """Worker data {"x": (U, K, 784), "y": (U, K)} and a test set."""
    x, y = make_digits(key, n_workers * per_worker + n_test)
    m = n_workers * per_worker
    return ({"x": x[:m].reshape(n_workers, per_worker, 784),
             "y": y[:m].reshape(n_workers, per_worker)},
            x[m:], y[m:])


def init_params(key, d_in=784, d_hidden=64, n_classes=10):
    k1, k2 = jax.random.split(key)
    return {"b1": jnp.zeros((d_hidden,)),
            "b2": jnp.zeros((n_classes,)),
            "w1": jax.random.normal(k1, (d_in, d_hidden))
            * math.sqrt(2.0 / d_in),
            "w2": jax.random.normal(k2, (d_hidden, n_classes))
            * math.sqrt(2.0 / d_hidden)}


# -- model -----------------------------------------------------------------------


def loss(params, x, y, keep=None, mm=codec.matmul):
    """Mean cross-entropy; ``keep`` (n,) bool limits the mean; ``mm`` is
    the matmul of a precision policy (``codec.MATMULS``)."""
    h = jnp.maximum(mm(x, params["w1"]) + params["b1"], 0.0)
    logits = mm(h, params["w2"]) + params["b2"]
    nll = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    if keep is None:
        return jnp.mean(nll)
    w = keep.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.sum(w)


def flatten(tree):
    return jnp.concatenate([x.reshape(-1)
                            for x in jax.tree_util.tree_leaves(tree)])


def unflatten(flat, like):
    leaves, td = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for x in leaves:
        out.append(flat[off:off + x.size].reshape(x.shape))
        off += x.size
    return jax.tree_util.tree_unflatten(td, out)


# -- schedule (P2, exact) ----------------------------------------------------------


class Analysis(NamedTuple):
    """Constants of the convergence analysis that weigh P2's objective."""
    rho1: float
    G: float
    delta: float

    @property
    def C(self) -> float:
        varpi = 2.0 * math.sqrt(1.0 + self.delta) / math.sqrt(1.0 - self.delta)
        varrho = math.sqrt(2.0) * self.delta / (1.0 - self.delta)
        return 2.0 * varpi / (1.0 - varrho)


def schedule(h, k, p_max, noise_var, D, S, kappa, a: Analysis):
    """Exact P2: the worker set beta and power scale b_t minimising
    R_t = rho1 sum_i K_i (1 - beta_i) / K + C^2 (1 + (1 + delta)(D - kappa)
    / (S D) G^2 + sigma^2 / (sum_i K_i beta_i b_t)^2)
    + sum_i beta_i (1 + delta)(D - kappa) / D G^2,
    with b_t = min over scheduled i of h_i sqrt(P) / K_i (eq. 10-11).
    Float64, every non-empty set."""
    h = np.asarray(h, np.float64)
    k = np.asarray(k, np.float64)
    caps = h * math.sqrt(p_max) / k
    best = (None, 0.0, math.inf)
    e = (1.0 + a.delta) * (D - kappa) / D * a.G ** 2
    floor = a.C ** 2 * (1.0 + (1.0 + a.delta) * (D - kappa) / (S * D)
                        * a.G ** 2)
    for bits in itertools.product((0.0, 1.0), repeat=len(h)):
        beta = np.asarray(bits)
        if not beta.any():
            continue
        b = caps[beta > 0].min()
        r = (a.rho1 * (k * (1.0 - beta)).sum() / k.sum() + floor
             + a.C ** 2 * noise_var / ((k * beta).sum() * b) ** 2
             + beta.sum() * e)
        if r < best[2]:
            best = (beta, b, r)
    return best[0], best[1]


# -- the round ---------------------------------------------------------------------


class Hyper(NamedTuple):
    chunk: int
    measure: int
    topk: int
    decode_topk: int
    biht_iters: int
    tau: float
    phi_seed: int
    noise_var: float
    p_max: float
    lr: float


def round_fades(key, t, n_workers: int):
    return codec.fades(jax.random.fold_in(jax.random.fold_in(key, t), 0),
                       n_workers)


def grads(params, data, keep=None, mm=codec.matmul):
    """Per-worker full-batch gradients, flat (U, D), and losses (U,)."""
    def one(x, y):
        l, g = jax.value_and_grad(loss)(params, x, y, keep, mm)
        return l, flatten(g)

    return jax.vmap(one)(data["x"], data["y"])


def codec_step(params, g, weights, b_t, t, key, hp: Hyper,
               mm=codec.matmul):
    """Top-kappa per chunk, uplink, MAC with noise, decode, SGD. g (U, D);
    weights (U,) = K_i beta_i. Returns (params', ghat (D,))."""
    U, D = g.shape
    n = -(-D // hp.chunk)
    gc = jnp.pad(g, ((0, 0), (0, n * hp.chunk - D))).reshape(U, n,
                                                              hp.chunk)
    phi = codec.make_phi(hp.phi_seed, hp.measure, hp.chunk)
    signs, mags = jax.vmap(lambda x: codec.uplink(codec.topk(x, hp.topk),
                                                  phi, mm))(gc)
    field = codec.noise(jax.random.fold_in(jax.random.fold_in(key, t), 1),
                        (n, hp.measure), hp.noise_var)
    y, mbar = codec.aggregate(signs, mags, weights, b_t, field)
    ghat = codec.decode(y, mbar, phi, hp.decode_topk, hp.biht_iters,
                        hp.tau, mm).reshape(-1)[:D]
    return unflatten(flatten(params) - hp.lr * ghat, params), ghat
