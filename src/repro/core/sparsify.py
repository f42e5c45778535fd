"""Top-κ sparsification (paper eq. 6).

``sparse_κ(g)`` keeps the κ largest-magnitude entries of g and zeroes the
rest. The chunked variant applies top-κ_c per chunk of D_c entries — the
TPU-native block formulation (DESIGN.md §4) that keeps selection local to a
VMEM tile and composes with the block-diagonal measurement operator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_sparsify(g: jnp.ndarray, k: int):
    """Dense top-k over the last axis. Returns (sparse_g, mask).

    Exactly k entries survive per row: the mask is scattered from
    ``lax.top_k``'s indices, which break exact-magnitude ties by value
    order then lowest index (measure-zero for float gradients). The
    scatter replaces the old threshold + cumsum tie-break — XLA CPU fused
    that cumsum into an O(chunk²) reduce-window, ~40× slower than the
    top_k itself (DESIGN.md §11 perf note)."""
    absg = jnp.abs(g)
    _, idx = jax.lax.top_k(absg, k)
    mask = jnp.zeros(g.shape, bool)
    mask = jnp.put_along_axis(mask, idx, True, axis=-1, inplace=False)
    return g * mask, mask


def _bisect_threshold(a: jnp.ndarray, k: int, iters: int):
    """Row thresholds of the top-k bisection over ``a = |g|`` (f32).

    Each pass halves ``[lo, hi]`` at ``mid``: ``lo`` moves up while more
    than k entries are ``>= mid``, else ``hi`` moves down; ``cnt_hi``
    counts the entries ``>= hi``. A row is settled once ``cnt_hi >= k``
    (every later ``hi`` then selects the same set, its top-k) or once
    ``mid`` rounds to ``lo`` or ``hi`` (a fixed point, e.g. an all-zero
    row); later passes leave its mask as it is. The loop stops when every
    row is settled, ``iters`` passes at most, so the result is bitwise
    that of ``iters`` passes. Rows with ``0 < nnz < k`` never settle and
    take all ``iters``.

    The stop test runs once every two passes, computed in the loop body:
    on a TPU v5e, waiting on it after every pass cost more than the pass
    (PERF.md §6). An odd ``iters`` takes its odd pass before the loop.

    Returns ``(lo, hi, cnt_hi, passes)``, each threshold without the row
    axis; ``passes`` counts the passes made."""
    hi = jnp.max(a, axis=-1)
    lo = jnp.zeros_like(hi)
    cnt_hi = jnp.sum((a >= hi[..., None]).astype(jnp.int32), axis=-1)

    def halve(lo, hi, cnt_hi):
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid[..., None]).astype(jnp.int32), axis=-1)
        up = cnt > k
        return (jnp.where(up, mid, lo), jnp.where(up, hi, mid),
                jnp.where(up, cnt_hi, cnt))

    def unsettled(lo, hi, cnt_hi):
        mid = 0.5 * (lo + hi)
        return jnp.any((cnt_hi < k) & (lo < mid) & (mid < hi))

    def body(state):
        i, lo, hi, cnt_hi, _ = state
        lo, hi, cnt_hi = halve(*halve(lo, hi, cnt_hi))
        return i + 2, lo, hi, cnt_hi, unsettled(lo, hi, cnt_hi)

    i = iters % 2
    if i:
        lo, hi, cnt_hi = halve(lo, hi, cnt_hi)
    passes, lo, hi, cnt_hi, _ = jax.lax.while_loop(
        lambda s: s[4] & (s[0] < iters), body,
        (jnp.int32(i), lo, hi, cnt_hi, unsettled(lo, hi, cnt_hi)))
    return lo, hi, cnt_hi, passes


def topk_sparsify_bisect(g: jnp.ndarray, k: int, iters: int = 40):
    """SPMD-friendly top-k: bisection on the magnitude threshold.

    ``jax.lax.top_k`` lowers to a sort that GSPMD cannot partition — at
    production scale it all-gathers the full (n_chunks, chunk) gradient
    array (180 GB/leaf for mixtral experts, §Perf iteration 6). Bisection
    uses only elementwise ops + row reductions, which shard perfectly.
    Exact for rows with distinct magnitudes (ties may admit > k entries —
    measure-zero for float gradients); same algorithm as the Pallas
    ``topk_select`` kernel.

    The search stops once every row's top-k set is settled
    (``_bisect_threshold``): ``iters`` is a cap (resolution max·2^-iters),
    and the output is bitwise that of ``iters`` fixed passes. Under GSPMD
    with rows sharded, the stop test's ``any`` over rows costs one scalar
    all-reduce every two passes. The loop has no reverse-mode rule: nothing
    differentiates through selection."""
    a = jnp.abs(g.astype(jnp.float32))
    lo, hi, cnt_hi, _ = _bisect_threshold(a, k, iters)
    mask = jnp.where((cnt_hi >= k)[..., None], a >= hi[..., None],
                     a >= lo[..., None])
    return g * mask, mask


def topk_sparsify_chunked(g: jnp.ndarray, k_per_chunk: int, chunk: int):
    """g: (..., n_chunks*chunk) or (n_chunks, chunk). Per-chunk top-k."""
    shp = g.shape
    if g.ndim == 1:
        assert g.size % chunk == 0, (g.size, chunk)
        gc = g.reshape(-1, chunk)
    else:
        gc = g
    sg, mask = topk_sparsify(gc, k_per_chunk)
    return sg.reshape(shp), mask.reshape(shp)


def sparsification_error_bound(D: int, kappa: int, G: float,
                               delta: float) -> float:
    """Paper eq. (40): E||e^s||^2 <= (1+δ) (D-κ)/D G²."""
    return (1.0 + delta) * (D - kappa) / D * G ** 2


def pad_to_chunks(flat: jnp.ndarray, chunk: int):
    """Zero-pad a flat vector to a multiple of `chunk`; returns (padded, D)."""
    D = flat.shape[0]
    rem = (-D) % chunk
    if rem:
        flat = jnp.concatenate([flat, jnp.zeros((rem,), flat.dtype)])
    return flat, D


def flatten_pytree(tree):
    """Flatten a gradient pytree to one float32 vector + unflatten closure."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves]) if leaves else jnp.zeros((0,))

    def unflatten(vec):
        out = []
        off = 0
        for shp, sz, dt in zip(shapes, sizes, dtypes):
            out.append(vec[off:off + sz].reshape(shp).astype(dt))
            off += sz
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unflatten
