"""Attention: GQA/MHA (RoPE, sliding window, logit softcap) and MLA.

Full-sequence attention (train/prefill) is blockwise over query blocks
(lax.scan) so no S x S score tensor is ever materialized — required for the
32k prefill shapes. Decode attends a single query over the KV cache; the MLA
decode path uses the absorbed-latent formulation (scores directly against the
cached latent, DeepSeek-V2 style).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.dist.sharding import constrain
from repro.models.layers import apply_rope, he_init, rmsnorm, softcap


# --- init ----------------------------------------------------------------------

def init_gqa(key, d_model: int, a: AttentionConfig, dtype=jnp.float32,
             d_out: int = 0):
    """Projections from ``d_model`` inputs; ``wo`` back to ``d_out``
    (0: d_model). Zamba2's shared block reads [h, embedding], 2 d wide,
    and writes d."""
    hd = a.head_dim if a.head_dim else d_model // a.num_heads
    d_out = d_out or d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": he_init(ks[0], (d_model, a.num_heads, hd), fan_in=d_model, dtype=dtype),
        "wk": he_init(ks[1], (d_model, a.num_kv_heads, hd), fan_in=d_model, dtype=dtype),
        "wv": he_init(ks[2], (d_model, a.num_kv_heads, hd), fan_in=d_model, dtype=dtype),
        "wo": he_init(ks[3], (a.num_heads, hd, d_out),
                      fan_in=a.num_heads * hd, dtype=dtype),
    }


def softmax_scale(a: AttentionConfig, hd: int) -> float:
    """The scores' scale: ``a.softmax_scale`` where set, else hd^-0.5."""
    return a.softmax_scale or 1.0 / math.sqrt(hd)


def init_mla(key, d_model: int, a: AttentionConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    qd = a.qk_nope_dim + a.qk_rope_dim
    p = {}
    if a.q_lora_rank:
        p["w_dq"] = he_init(ks[0], (d_model, a.q_lora_rank), dtype=dtype)
        p["w_uq"] = he_init(ks[1], (a.q_lora_rank, a.num_heads, qd),
                            fan_in=a.q_lora_rank, dtype=dtype)
        p["q_norm"] = jnp.zeros((a.q_lora_rank,), dtype)
    else:
        p["wq"] = he_init(ks[1], (d_model, a.num_heads, qd), fan_in=d_model,
                          dtype=dtype)
    p["w_dkv"] = he_init(ks[2], (d_model, a.kv_lora_rank), dtype=dtype)
    p["w_kr"] = he_init(ks[3], (d_model, a.qk_rope_dim), dtype=dtype)
    p["kv_norm"] = jnp.zeros((a.kv_lora_rank,), dtype)
    p["w_uk"] = he_init(ks[4], (a.kv_lora_rank, a.num_heads, a.qk_nope_dim),
                        fan_in=a.kv_lora_rank, dtype=dtype)
    p["w_uv"] = he_init(ks[5], (a.kv_lora_rank, a.num_heads, a.v_head_dim),
                        fan_in=a.kv_lora_rank, dtype=dtype)
    p["wo"] = he_init(ks[6], (a.num_heads, a.v_head_dim, d_model),
                      fan_in=a.num_heads * a.v_head_dim, dtype=dtype)
    return p


# --- core blockwise attention ---------------------------------------------------

def _block_attend(q, k, v, q_pos, k_pos, *, scale, causal, window, is_global,
                  cap: float, kv_valid=None):
    """One query block against all keys.

    q: (B, Tq, H, hd); k/v: (B, S, KV, hd-like). Returns (B, Tq, H, vd).
    window/is_global may be traced scalars; mask fuses (no S x S global tensor).
    """
    B, Tq, H, _ = q.shape
    KV = k.shape[2]
    rep = H // KV
    qg = q.reshape(B, Tq, KV, rep, q.shape[-1])
    # q-major score layout (b,t,k,r,s): output einsum lands directly in the
    # (B,Tq,H,hd) layout — avoids an SPMD-hostile transpose that forced
    # involuntary full rematerialization (§Perf iteration 1)
    scores = jnp.einsum("btkrh,bskh->btkrs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, cap)
    mask = jnp.ones((Tq, k.shape[1]), bool)
    delta = q_pos[:, None] - k_pos[None, :]             # (Tq, S)
    if causal:
        mask &= delta >= 0
    if window is not None:
        in_window = delta < window
        mask &= jnp.where(is_global, True, in_window) if is_global is not None \
            else in_window
    # scores layout: (B, Tq, KV, rep, S)
    if kv_valid is not None:                            # (B, S) valid entries
        mask = (mask[None, :, None, None, :]
                & kv_valid[:, None, None, None, :])     # (B,Tq,1,1,S)
    else:
        mask = mask[None, :, None, None, :]             # (1,Tq,1,1,S)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("btkrs,bskh->btkrh", w, v)
    return out.reshape(B, Tq, H, v.shape[-1])


def blockwise_attention(q, k, v, q_positions, k_positions, *, scale,
                        causal=True, window=None, is_global=None, cap=0.0,
                        block_size=512, kv_valid=None):
    """Scan over query blocks; each block sees all keys (masked)."""
    B, S, H, hd = q.shape
    bs = min(block_size, S)
    while S % bs:
        bs //= 2
    nb = S // bs
    if nb <= 1:
        return _block_attend(q, k, v, q_positions, k_positions, scale=scale,
                             causal=causal, window=window, is_global=is_global,
                             cap=cap, kv_valid=kv_valid)
    qb = q.reshape(B, nb, bs, H, hd).transpose(1, 0, 2, 3, 4)
    pb = q_positions.reshape(nb, bs)

    def step(_, xs):
        qblk, pblk = xs
        o = _block_attend(qblk, k, v, pblk, k_positions, scale=scale,
                          causal=causal, window=window, is_global=is_global,
                          cap=cap, kv_valid=kv_valid)
        return None, o

    # flash-style: recompute block scores in the backward pass — only the
    # (B, bs, H, hd) block output is ever live across blocks
    _, ob = jax.lax.scan(jax.checkpoint(step), None, (qb, pb))
    return ob.transpose(1, 0, 2, 3, 4).reshape(B, S, H, -1)


# --- GQA forward (train/prefill) -----------------------------------------------

def gqa_forward(p, x, a: AttentionConfig, *, positions, causal=True,
                is_global=None, use_rope=True, kv=None, kv_positions=None):
    """x: (B,S,d). Returns (out, (k, v)) — k/v returned for cache seeding.

    kv: optional encoder output (B, S_enc, d) for cross-attention."""
    hd = p["wq"].shape[-1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    src = kv if kv is not None else x
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    q = constrain(q, ("data", None, "model", None))
    k = constrain(k, ("data", None, "model", None))
    v = constrain(v, ("data", None, "model", None))
    k_pos = kv_positions if kv_positions is not None else positions
    if use_rope:
        q = apply_rope(q, positions, a.rope_theta)
        if kv is None:
            k = apply_rope(k, k_pos, a.rope_theta)
    window = a.window if a.window else None
    out = blockwise_attention(
        q, k, v, positions, k_pos, scale=softmax_scale(a, hd), causal=causal,
        window=window, is_global=is_global, cap=a.logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(out, ("data", None, None)), (k, v)


def decode_attention_sharded(q, k, v, pos, *, scale, window=None,
                             is_global=None, cap=0.0, n_chunks=16):
    """Flash-decoding-style single-token attention over a LENGTH-SHARDED
    cache (§Perf optimization): per-chunk partial (max, exp-sum, weighted-V)
    reduced across chunks — the cross-device traffic is the (B,H,hd)
    partials instead of an all-gather of the full K/V.

    q: (B,1,H,hd); k/v: (B,S,KV,hd) with S sharded over `data`."""
    from repro.dist.sharding import constrain
    B, S, KV, hd = k.shape
    H = q.shape[2]
    rep = H // KV
    while S % n_chunks:
        n_chunks //= 2
    cl = S // n_chunks
    kc = constrain(k.reshape(B, n_chunks, cl, KV, hd),
                   (None, "data", None, "model", None))
    vc = constrain(v.reshape(B, n_chunks, cl, KV, hd),
                   (None, "data", None, "model", None))
    qg = q.reshape(B, KV, rep, hd)
    scores = jnp.einsum("bkrh,bnskh->bnkrs", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale    # (B,nc,KV,rep,cl)
    scores = softcap(scores, cap)
    k_pos = jnp.arange(S, dtype=jnp.int32).reshape(n_chunks, cl)
    mask = k_pos <= pos                                    # causal
    if window is not None:
        in_w = (pos - k_pos) < window
        mask = mask & (jnp.where(is_global, True, in_w)
                       if is_global is not None else in_w)
    scores = jnp.where(mask[None, :, None, None, :], scores, -1e30)
    m_part = jnp.max(scores, axis=-1)                      # (B,nc,KV,rep)
    m_glob = jnp.max(m_part, axis=1, keepdims=True)        # cross-chunk
    e = jnp.exp(scores - m_glob[..., None])
    denom = jnp.sum(e, axis=(1, 4))                        # (B,KV,rep)
    num = jnp.einsum("bnkrs,bnskh->bkrh", e, vc.astype(jnp.float32))
    out = num / denom[..., None]
    return out.reshape(B, 1, H, hd).astype(v.dtype)


def gqa_decode(p, x, a: AttentionConfig, *, cache_k, cache_v, pos,
               is_global=None, use_rope=True, cross=False,
               sharded_cache_chunks: int = 0):
    """x: (B,1,d); cache_k/v: (B,S,KV,hd). Returns (out, new_k, new_v)."""
    hd = p["wq"].shape[-1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    B, S = cache_k.shape[0], cache_k.shape[1]
    q_pos = jnp.full((1,), pos, jnp.int32)
    if use_rope:
        q = apply_rope(q, q_pos, a.rope_theta)
    if cross:
        k, v = cache_k, cache_v
        kv_valid = None
        k_pos = jnp.arange(S, dtype=jnp.int32)
        causal = False
    else:
        knew = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
        vnew = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
        if use_rope:
            knew = apply_rope(knew, q_pos, a.rope_theta)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, knew.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, vnew.astype(cache_v.dtype), (0, pos, 0, 0))
        k, v = cache_k, cache_v
        k_pos = jnp.arange(S, dtype=jnp.int32)
        kv_valid = None
        causal = True
    window = a.window if a.window else None
    if sharded_cache_chunks and not cross:
        out = decode_attention_sharded(
            q, k, v, pos, scale=softmax_scale(a, hd), window=window,
            is_global=is_global, cap=a.logit_softcap,
            n_chunks=sharded_cache_chunks)
    else:
        out = _block_attend(q, k, v, q_pos, k_pos, scale=softmax_scale(a, hd),
                            causal=causal, window=window, is_global=is_global,
                            cap=a.logit_softcap, kv_valid=kv_valid)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache_k, cache_v


# --- MLA ------------------------------------------------------------------------

def _mla_q(p, x, a: AttentionConfig, positions, eps):
    if a.q_lora_rank:
        cq = x @ p["w_dq"].astype(x.dtype)
        cq = rmsnorm(cq, p["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope = q[..., :a.qk_nope_dim]
    q_rope = apply_rope(q[..., a.qk_nope_dim:], positions, a.rope_theta)
    return q_nope, q_rope


def mla_forward(p, x, a: AttentionConfig, *, positions, eps=1e-6):
    """Naive (materialized-K) MLA for train/prefill.

    Returns (out, (c_kv, k_rope)) for cache seeding."""
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, a, positions, eps)
    c_kv = rmsnorm(x @ p["w_dkv"].astype(x.dtype), p["kv_norm"], eps)
    k_rope = apply_rope((x @ p["w_kr"].astype(x.dtype))[:, :, None, :],
                        positions, a.rope_theta)        # (B,S,1,rd)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], a.qk_rope_dim))],
        axis=-1)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    out = blockwise_attention(q, k, v, positions, positions, scale=scale,
                              causal=True, cap=a.logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(out, ("data", None, None)), (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, x, a: AttentionConfig, *, cache_ckv, cache_kr, pos, eps=1e-6):
    """Absorbed-latent decode. cache_ckv: (B,S,r); cache_kr: (B,S,rd)."""
    B, S, r = cache_ckv.shape
    q_pos = jnp.full((1,), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, a, q_pos, eps)        # (B,1,H,*)
    c_new = rmsnorm(x @ p["w_dkv"].astype(x.dtype), p["kv_norm"], eps)
    kr_new = apply_rope((x @ p["w_kr"].astype(x.dtype))[:, :, None, :],
                        q_pos, a.rope_theta)[:, :, 0, :]
    cache_ckv = jax.lax.dynamic_update_slice(
        cache_ckv, c_new.astype(cache_ckv.dtype), (0, pos, 0))
    cache_kr = jax.lax.dynamic_update_slice(
        cache_kr, kr_new.astype(cache_kr.dtype), (0, pos, 0))
    # absorb: q_latent[h] = q_nope[h] @ W_uk[h]^T  -> (B,1,H,r)
    q_lat = jnp.einsum("bthk,rhk->bthr", q_nope, p["w_uk"].astype(x.dtype))
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat.astype(jnp.float32),
                         cache_ckv.astype(jnp.float32))
              + jnp.einsum("bthk,bsk->bhts", q_rope.astype(jnp.float32),
                           cache_kr.astype(jnp.float32)))
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    scores = scores * scale
    k_pos = jnp.arange(S, dtype=jnp.int32)
    mask = (k_pos[None, :] <= pos)[None, None]          # (1,1,1,S) over (B,H,1,S)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhts,bsr->bthr", w, cache_ckv.astype(jnp.float32))
    out = jnp.einsum("bthr,rhv->bthv", out_lat.astype(x.dtype),
                     p["w_uv"].astype(x.dtype))
    out = jnp.einsum("bthv,hvd->btd", out, p["wo"].astype(x.dtype))
    return out, cache_ckv, cache_kr
