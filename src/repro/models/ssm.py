"""Mamba2 / SSD (state-space duality) block [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm: intra-chunk quadratic
attention-like term + inter-chunk state recurrence (lax.scan over chunks).
Decode is the O(1) recurrent update.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.dist.sharding import constrain
from repro.models.layers import he_init, rmsnorm


def ssm_dims(d_model: int, s: SSMConfig):
    d_inner = s.expand * d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, n_heads, conv_dim


def init_mamba2(key, d_model: int, s: SSMConfig, dtype=jnp.float32):
    d_inner, n_heads, conv_dim = ssm_dims(d_model, s)
    ks = jax.random.split(key, 6)
    d_in_proj = 2 * d_inner + 2 * s.n_groups * s.d_state + n_heads
    p = {
        "in_proj": he_init(ks[0], (d_model, d_in_proj), dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_dim))
                   * (1.0 / math.sqrt(s.conv_width))).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(dtype),
        "D": jnp.ones((n_heads,), dtype),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[2], (n_heads,),
                                       minval=math.log(1e-3),
                                       maxval=math.log(1e-1))))).astype(dtype),
        "gate_norm": jnp.zeros((d_inner,), dtype),
        "out_proj": he_init(ks[3], (d_inner, d_model), dtype=dtype),
    }
    return p


def gated_rmsnorm(y, z, scale, eps, groups: int = 1):
    """RMS norm of y * silu(z) over each of ``groups`` equal slices of the
    last dim, times (1 + scale). One group is the plain ``rmsnorm``."""
    g = y * jax.nn.silu(z)
    if groups == 1:
        return rmsnorm(g, scale, eps)
    return rmsnorm(g.reshape(g.shape[:-1] + (groups, -1)),
                   scale.reshape(groups, -1), eps).reshape(g.shape)


def _split_proj(zxbcdt, d_inner, n_groups, d_state, n_heads):
    z, xs, B, C, dt = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner, 2 * d_inner + n_groups * d_state,
         2 * d_inner + 2 * n_groups * d_state],
        axis=-1)
    return z, xs, B, C, dt


def _causal_conv(xbc, conv_w, conv_b, conv_state=None):
    """xbc: (B,S,conv_dim); depthwise causal conv width W.

    conv_state: (B, W-1, conv_dim) history for decode/chunked prefill."""
    W = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], W - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    xpad = jnp.concatenate([pad, xbc], axis=1)
    out = sum(xpad[:, i:i + xbc.shape[1]] * conv_w[i] for i in range(W))
    new_state = xpad[:, -(W - 1):] if W > 1 else pad
    return jax.nn.silu(out + conv_b), new_state


def _segsum(x):
    """x: (..., T). Returns (..., T, T) lower-tri cumulative sums."""
    T = x.shape[-1]
    xc = jnp.cumsum(x, axis=-1)
    d = xc[..., :, None] - xc[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, init_state=None):
    """SSD scan (Mamba2 alg. 1, einsum form).

    x: (b, s, h, p); dt: (b, s, h); A: (h,); B,C: (b, s, g, n).
    Returns y (b,s,h,p), final_state (b,h,p,n)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = s // chunk
    rep = h // g
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)
    dA = dtc * (-jnp.exp(A.astype(jnp.float32)))        # (b,nc,l,h) negative
    dA_cs = jnp.cumsum(dA, axis=2)                      # within-chunk cumsum
    # intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))      # (b,nc,h,l,l)
    Bh = jnp.repeat(Bc, rep, axis=3)                    # (b,nc,l,h,n)
    Ch = jnp.repeat(Cc, rep, axis=3)
    scores = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh)   # (b,nc,h,l,l)
    scores = scores * L
    y_diag = jnp.einsum("bchls,bcsh,bcshp->bclhp", scores, dtc, xc)
    # chunk end-states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)  # (b,nc,l,h)
    states = jnp.einsum("bclhn,bclh,bclh,bclhp->bchpn",
                        Bh, decay_states, dtc, xc)       # (b,nc,h,p,n)
    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])            # (b,nc,h)

    def step(carry, xs):
        st, dec = xs                                    # (b,h,p,n), (b,h)
        new = carry * dec[..., None, None] + st
        return new, carry                               # emit state BEFORE chunk

    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)
    final, prev_states = jax.lax.scan(
        step, init_state.astype(jnp.float32),
        (states.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
         chunk_decay.transpose(1, 0, 2).astype(jnp.float32)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (b,nc,h,p,n)
    # inter-chunk contribution
    state_decay = jnp.exp(dA_cs)                        # (b,nc,l,h)
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                       Ch.astype(jnp.float32), prev_states, state_decay)
    y = (y_diag.astype(jnp.float32) + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final


def mamba2_forward(p, x, s: SSMConfig, *, init_conv=None, init_ssm=None,
                   eps=1e-6, norm_groups: int = 1):
    """x: (B,S,d). Returns (out, (conv_state, ssm_state)). The gated norm
    runs over ``norm_groups`` groups of d_inner."""
    d_model = x.shape[-1]
    d_inner, n_heads, conv_dim = ssm_dims(d_model, s)
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    zxbcdt = constrain(zxbcdt, ("data", None, "model"))
    z, xs, B, C, dt = _split_proj(zxbcdt, d_inner, s.n_groups, s.d_state,
                                  n_heads)
    xbc = jnp.concatenate([xs, B, C], axis=-1)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"].astype(x.dtype),
                                   p["conv_b"].astype(x.dtype), init_conv)
    xs, B, C = jnp.split(xbc, [d_inner, d_inner + s.n_groups * s.d_state],
                         axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    bsz, S = x.shape[0], x.shape[1]
    xh = xs.reshape(bsz, S, n_heads, s.head_dim)
    Bg = B.reshape(bsz, S, s.n_groups, s.d_state)
    Cg = C.reshape(bsz, S, s.n_groups, s.d_state)
    chunk = min(s.chunk_size, S)
    while S % chunk:
        chunk //= 2
    # ssd_chunked expects A_log such that dA = dt * (-exp(A_log)).
    y, ssm_state = ssd_chunked(xh, dt, p["A_log"].astype(jnp.float32),
                               Bg, Cg, chunk, init_state=init_ssm)
    y = y + xh * p["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(bsz, S, d_inner)
    y = gated_rmsnorm(y, z, p["gate_norm"], eps, norm_groups)
    out = y @ p["out_proj"].astype(x.dtype)
    return constrain(out, ("data", None, None)), (conv_state, ssm_state)


def mamba2_decode(p, x, s: SSMConfig, *, conv_state, ssm_state, eps=1e-6,
                  norm_groups: int = 1):
    """Single-token recurrent step. x: (B,1,d).

    conv_state: (B, W-1, conv_dim); ssm_state: (B,h,p,n) float32."""
    d_model = x.shape[-1]
    d_inner, n_heads, conv_dim = ssm_dims(d_model, s)
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xs, B, C, dt = _split_proj(zxbcdt, d_inner, s.n_groups, s.d_state,
                                  n_heads)
    xbc = jnp.concatenate([xs, B, C], axis=-1)          # (B,1,conv_dim)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"].astype(x.dtype),
                                   p["conv_b"].astype(x.dtype), conv_state)
    xs, B, C = jnp.split(xbc, [d_inner, d_inner + s.n_groups * s.d_state],
                         axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))[:, 0]  # (B,h)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))        # (h,)
    rep = n_heads // s.n_groups
    xh = xs[:, 0].reshape(-1, n_heads, s.head_dim).astype(jnp.float32)
    Bg = B[:, 0].reshape(-1, s.n_groups, s.d_state).astype(jnp.float32)
    Cg = C[:, 0].reshape(-1, s.n_groups, s.d_state).astype(jnp.float32)
    Bh = jnp.repeat(Bg, rep, axis=1)                    # (B,h,n)
    Ch = jnp.repeat(Cg, rep, axis=1)
    decay = jnp.exp(dt * A[None])                       # (B,h)
    ssm_state = (ssm_state * decay[..., None, None]
                 + jnp.einsum("bh,bhn,bhp->bhpn", dt, Bh, xh))
    y = jnp.einsum("bhpn,bhn->bhp", ssm_state, Ch)
    y = y + xh * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(x.shape[0], 1, d_inner).astype(x.dtype)
    y = gated_rmsnorm(y, z, p["gate_norm"], eps, norm_groups)
    out = y @ p["out_proj"].astype(x.dtype)
    return out, (conv_state, ssm_state)
