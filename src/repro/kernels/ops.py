"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) kernels run with ``interpret=True`` — the kernel body
executes in Python/XLA for correctness validation; on TPU the same code lowers
to Mosaic. Wrappers pad inputs up to tile multiples and slice back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backproject as _bp
from repro.kernels import cs_project as _cs
from repro.kernels import topk_select as _tk
from repro.kernels import ref as _ref
from repro.kernels import sign as sign_codec


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x, mult):
    n = x.shape[0]
    rem = (-n) % mult
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,) + x.shape[1:], x.dtype)])
    return x, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def cs_project_sign(phi, chunks, interpret=None):
    """sign(chunks @ phiᵀ): phi (S, D), chunks (n, D) -> (n, S)."""
    interpret = _interpret() if interpret is None else interpret
    chunks, n = _pad_rows(chunks, min(_cs.BN, max(1, chunks.shape[0])))
    out = _cs.project(phi, chunks, mode="sign", interpret=interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cs_project_pack(phi, chunks, interpret=None):
    """Fused sign+pack compression (DESIGN.md §13): phi (S, D),
    chunks (n, D) -> uint32 (n, S//32); bit = 1 ⇔ projection >= 0.

    Unpacking the result reproduces ``cs_project_sign`` bit for bit —
    both epilogues share the one sign predicate (kernels/sign.py)."""
    interpret = _interpret() if interpret is None else interpret
    chunks, n = _pad_rows(chunks, min(_cs.BN, max(1, chunks.shape[0])))
    return _cs.project(phi, chunks, mode="pack", interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cs_pack_sign_residual(phi, x, y_packed, interpret=None):
    """Packed BIHT residual planes (DESIGN.md §13): the fresh sign(x Φᵀ)
    is consumed in-kernel; returns (plus, minus) uint32 (n, S//32) with
    resid = 2·(plus − minus)."""
    interpret = _interpret() if interpret is None else interpret
    bn = min(_cs.BN, max(1, x.shape[0]))
    x, n = _pad_rows(x, bn)
    y_packed, _ = _pad_rows(y_packed, bn)
    plus, minus = _cs.project(phi, x, mode="pack_sign_residual", y=y_packed,
                              interpret=interpret)
    return plus[:n], minus[:n]


@functools.partial(jax.jit, static_argnames=("tau", "interpret"))
def backproject_packed(x, plus, minus, phi, tau, interpret=None):
    """x + tau * (2·(plus − minus)) @ phi with the bit-planes unpacked
    in-tile (DESIGN.md §13)."""
    interpret = _interpret() if interpret is None else interpret
    bn = min(_bp.BN, max(1, x.shape[0]))
    x, n = _pad_rows(x, bn)
    plus, _ = _pad_rows(plus, bn)
    minus, _ = _pad_rows(minus, bn)
    return _bp.backproject_packed(x, plus, minus, phi, tau,
                                  interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cs_project(phi, chunks, interpret=None):
    interpret = _interpret() if interpret is None else interpret
    chunks, n = _pad_rows(chunks, min(_cs.BN, max(1, chunks.shape[0])))
    return _cs.project(phi, chunks, mode="none", interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select(chunks, k, interpret=None):
    """Per-row top-k by magnitude -> (values, mask)."""
    interpret = _interpret() if interpret is None else interpret
    chunks, n = _pad_rows(chunks, _tk.row_tile(max(1, chunks.shape[0]),
                                               chunks.shape[1]))
    val, mask = _tk.topk_select(chunks, k, interpret=interpret)
    return val[:n], mask[:n]


@functools.partial(jax.jit, static_argnames=("tau", "interpret"))
def backproject(x, resid, phi, tau, interpret=None):
    interpret = _interpret() if interpret is None else interpret
    bn = min(_bp.BN, max(1, x.shape[0]))
    x, n = _pad_rows(x, bn)
    resid, _ = _pad_rows(resid, bn)
    return _bp.backproject(x, resid, phi, tau, interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("k", "iters", "tau", "interpret"))
def biht(y, phi, k, iters, tau, interpret=None):
    """Full BIHT decode composed from the three kernels.

    y: (n, S) aggregated measurements; phi: (S, D). Unit-norm rows out."""
    interpret = _interpret() if interpret is None else interpret
    S = phi.shape[0]
    x0 = backproject(jnp.zeros((y.shape[0], phi.shape[1]), y.dtype), y, phi,
                     1.0 / S, interpret=interpret)
    x, _ = topk_select(x0, k, interpret=interpret)

    def step(x, _):
        resid = _cs_sign_residual(phi, x, y, interpret)
        x = backproject(x, resid, phi, tau / S, interpret=interpret)
        x, _ = topk_select(x, k, interpret=interpret)
        return x, None

    x, _ = jax.lax.scan(step, x, None, length=iters)
    norm = jnp.linalg.norm(x, axis=-1, keepdims=True)
    return x / jnp.maximum(norm, 1e-12)


def _cs_sign_residual(phi, x, y, interpret):
    bn = min(_cs.BN, max(1, x.shape[0]))
    x, n = _pad_rows(x, bn)
    y, _ = _pad_rows(y, bn)
    return _cs.project(phi, x, mode="sign_residual", y=y,
                       interpret=interpret)[:n]


# re-export oracles for tests
ref = _ref
