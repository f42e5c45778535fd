"""Unified decoder-only LM covering dense / moe / ssm / hybrid / vlm families.

Layers are a ``lax.scan`` over stacked per-layer params (bounded HLO size and
compile time at 62+ layers), with optional ``jax.checkpoint`` remat in the
train path. Per-layer structural variation (local/global attention) is
carried by scanned flag arrays. The hybrid family (Zamba2) splits the stack
at its hybrid layers: runs of plain Mamba layers are scanned, and each
hybrid layer runs its call of a shared block, then its own Mamba layer
(``hybrid_segments``).

Decode maintains functional KV/SSM caches stacked over layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig, dtype_of
from repro.dist.sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (embed, he_init, init_embedding, init_mlp,
                                 mlp, rmsnorm, unembed)


# --- per-layer init -------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        a = cfg.attention
        p["attn_norm"] = jnp.zeros((d,), dtype)
        p["attn"] = (attn.init_mla(ks[0], d, a, dtype) if a.use_mla
                     else attn.init_gqa(ks[0], d, a, dtype))
        p["ffn_norm"] = jnp.zeros((d,), dtype)
        if fam == "moe":
            p["moe"] = moe_lib.init_moe(ks[1], d, cfg.d_ff, cfg.moe,
                                        gated=cfg.gated_mlp, dtype=dtype)
        else:
            p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.gated_mlp, dtype)
    elif fam in ("ssm", "hybrid"):
        p["ssm_norm"] = jnp.zeros((d,), dtype)
        p["ssm"] = ssm_lib.init_mamba2(ks[0], d, cfg.ssm, dtype)
    else:
        raise ValueError(fam)
    return p


def _init_shared_block(key, cfg: ModelConfig, dtype):
    """One Zamba2 shared block, its weights tied over its calls: RMS norm
    and attention over [h, embedding] (2 d_model wide) back to d_model,
    RMS norm, gated MLP (``gate_up`` d x 2 d_ff, ``down``)."""
    d, dA, F = cfg.d_model, 2 * cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "attn_norm": jnp.zeros((dA,), dtype),
        "attn": attn.init_gqa(ks[0], dA, cfg.attention, dtype, d_out=d),
        "ffn_norm": jnp.zeros((d,), dtype),
        "gate_up": he_init(ks[1], (d, 2 * F), dtype=dtype),
        "down": he_init(ks[2], (F, d), dtype=dtype),
    }


def _init_hybrid_call(key, cfg: ModelConfig, dtype):
    """What one call of a shared block owns: the LoRA adapter of the
    block's ``gate_up`` (d x r, r x 2 d_ff) and the d x d ``linear`` after
    the block."""
    d, r, F = cfg.d_model, cfg.adapter_rank, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "adapter_in": he_init(ks[0], (d, r), dtype=dtype),
        "adapter_out": he_init(ks[1], (r, 2 * F), dtype=dtype),
        "linear": he_init(ks[2], (d, d), dtype=dtype),
    }


def hybrid_segments(cfg: ModelConfig):
    """The layer stack as ``(start, stop, call)`` runs: plain layers
    (call None, scanned) and single hybrid layers (call = index into
    ``hybrid_layer_ids``, which uses shared block call % num_mem_blocks)."""
    ids = cfg.hybrid_layer_ids if cfg.family == "hybrid" else ()
    out, start = [], 0
    for c, i in enumerate(ids):
        if i > start:
            out.append((start, i, None))
        out.append((i, i + 1, c))
        start = i + 1
    if start < cfg.num_layers:
        out.append((start, cfg.num_layers, None))
    return out


def _rows(tree, start, stop, n):
    """Rows start:stop of a tree stacked over n (the tree itself when
    that is all of it)."""
    if (start, stop) == (0, n):
        return tree
    return jax.tree_util.tree_map(lambda a: a[start:stop], tree)


def _row(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def layer_flags(cfg: ModelConfig):
    """Per-layer scanned flags."""
    L = cfg.num_layers
    if cfg.local_global_period:
        pp = cfg.local_global_period
        is_global = (jnp.arange(L) % pp) == (pp - 1)
    else:
        is_global = jnp.ones((L,), bool) if (cfg.attention is None
                                             or not cfg.attention.window) \
            else jnp.zeros((L,), bool)
    return {"is_global": is_global}


def init_lm(key, cfg: ModelConfig):
    dtype = jnp.float32
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    params = {
        "embedding": init_embedding(ks[1], cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = he_init(ks[2], (cfg.d_model, cfg.vocab_size),
                                    fan_in=cfg.d_model, dtype=dtype)
    if cfg.family == "hybrid":
        kb, kc = jax.random.split(ks[3])
        params["shared_blocks"] = jax.vmap(
            lambda k: _init_shared_block(k, cfg, dtype))(
                jax.random.split(kb, cfg.num_mem_blocks))
        params["hybrid"] = jax.vmap(
            lambda k: _init_hybrid_call(k, cfg, dtype))(
                jax.random.split(kc, len(cfg.hybrid_layer_ids)))
    if cfg.family == "vlm":
        # stub projector bias marker (frontend itself is external, DESIGN §8)
        params["img_pos"] = (0.02 * jax.random.normal(
            ks[3], (cfg.num_image_tokens, cfg.d_model))).astype(dtype)
    return params


# --- layer application ------------------------------------------------------------

def gate_norm_groups(cfg: ModelConfig) -> int:
    """Groups of the Mamba gated norm: Zamba2 normalizes each of its
    n_groups slices of d_inner; the mamba2 family the whole d_inner (the
    two agree at the published Mamba-2's ngroups of 1)."""
    return cfg.ssm.n_groups if cfg.family == "hybrid" else 1


def _apply_layer_full(lp, x, cfg: ModelConfig, flags, positions, t=None):
    """Full-sequence (train/prefill) layer. Returns (x, cache_seed, aux).

    ``t`` (ssm / hybrid): a hybrid call's output, added to the layer's
    input at its Mamba norm only (the residual carries x alone)."""
    fam = cfg.family
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    cache_seed = None
    if fam in ("dense", "vlm", "moe"):
        h = rmsnorm(x, lp["attn_norm"], eps)
        if cfg.attention.use_mla:
            o, (ckv, kr) = attn.mla_forward(lp["attn"], h, cfg.attention,
                                            positions=positions, eps=eps)
            cache_seed = (ckv, kr)
        else:
            o, (k, v) = attn.gqa_forward(lp["attn"], h, cfg.attention,
                                         positions=positions,
                                         is_global=flags["is_global"])
            cache_seed = (k, v)
        x = x + o
        h = rmsnorm(x, lp["ffn_norm"], eps)
        if fam == "moe":
            o, aux = moe_lib.moe_forward(lp["moe"], h, cfg.moe,
                                         gated=cfg.gated_mlp)
        else:
            o = mlp(lp["mlp"], h, cfg.gated_mlp)
        x = x + o
    else:  # ssm / hybrid
        with obs.block("mamba"):
            h = rmsnorm(x if t is None else x + t, lp["ssm_norm"], eps)
            o, (conv_st, ssm_st) = ssm_lib.mamba2_forward(
                lp["ssm"], h, cfg.ssm, eps=eps,
                norm_groups=gate_norm_groups(cfg))
            x = x + o
        cache_seed = (conv_st, ssm_st)
    return x, cache_seed, aux


def _shared_mlp(blk, call, h, cfg: ModelConfig):
    """The shared block's gated MLP, with call's LoRA adapter added to
    ``gate_up``: gelu(gate) * up (exact GELU, Zamba2's ``hidden_act``),
    then ``down``."""
    dt = h.dtype
    gu = (h @ blk["gate_up"].astype(dt)
          + (h @ call["adapter_in"].astype(dt))
          @ call["adapter_out"].astype(dt))
    gate, up = jnp.split(gu, 2, axis=-1)
    return (jax.nn.gelu(gate, approximate=False) * up) \
        @ blk["down"].astype(dt)


def hybrid_call(blk, call, x, e, cfg: ModelConfig, positions):
    """One call of a shared block on [x, e] (train/prefill), then call's
    ``linear``. Returns (t, (k, v)); no residual inside the block."""
    eps = cfg.norm_eps
    with obs.block("shared"):
        h = rmsnorm(jnp.concatenate([x, e], axis=-1), blk["attn_norm"], eps)
        o, kv = attn.gqa_forward(blk["attn"], h, cfg.attention,
                                 positions=positions)
        h = rmsnorm(o, blk["ffn_norm"], eps)
        t = _shared_mlp(blk, call, h, cfg) @ call["linear"].astype(x.dtype)
    return t, kv


def _hybrid_call_decode(blk, call, x, e, cfg: ModelConfig, k, v, pos):
    """``hybrid_call`` for one token against call's KV cache. Returns
    (t, k, v)."""
    eps = cfg.norm_eps
    with obs.block("shared"):
        h = rmsnorm(jnp.concatenate([x, e], axis=-1), blk["attn_norm"], eps)
        o, k, v = attn.gqa_decode(blk["attn"], h, cfg.attention,
                                  cache_k=k, cache_v=v, pos=pos)
        h = rmsnorm(o, blk["ffn_norm"], eps)
        t = _shared_mlp(blk, call, h, cfg) @ call["linear"].astype(x.dtype)
    return t, k, v


def remat_wrap(body, remat):
    """Wrap a scan body per the remat knob (DESIGN.md §16).

    ``remat`` is a bool (legacy: True == "full") or a policy name:
    "off" saves every residual (scan keeps all layer activations),
    "full" saves nothing (recompute the whole block in the backward),
    "dots" / "dots_no_batch" save matmul outputs only
    (``jax.checkpoint_policies``) — the middle ground that trades one
    extra gather+norm recompute for not holding attention internals."""
    if remat in (False, None, "off"):
        return body
    if remat in (True, "full"):
        return jax.checkpoint(body)
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    if remat not in policies:
        raise ValueError(
            f"remat policy {remat!r} not in "
            f"{('off', 'full') + tuple(policies)}")
    return jax.checkpoint(body, policy=policies[remat])


def lm_forward(params, cfg: ModelConfig, tokens, *, image_embeds=None,
               remat=True, collect_cache=False, return_hidden=False,
               layer_resolver=None):
    """tokens: (B,S_text). Returns (logits_or_hidden, aux, cache or None).

    For vlm, image_embeds (B,N,d) are prepended (total seq = N + S_text).
    return_hidden=True skips the unembed (chunked-CE training path).

    ``layer_resolver`` maps the per-layer param slice to the form the
    block math consumes, INSIDE the scan body (and inside the remat
    boundary, so whatever it materializes is recomputed, not saved). The
    zoo-train path passes the all-gather resolver that turns model-axis
    weight shards into full per-layer weights one layer at a time —
    nothing dense at full model size ever exists (DESIGN.md §16)."""
    dtype = dtype_of(cfg)
    x = embed(params["embedding"], tokens, dtype)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    if cfg.family == "vlm":
        img = (image_embeds.astype(dtype)
               + params["img_pos"].astype(dtype)[None])
        x = jnp.concatenate([img, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    flags = layer_flags(cfg)
    resolve = layer_resolver or (lambda lp: lp)

    def body(carry, xs):
        x, aux_acc = carry
        lp, fl = xs
        # scan-carry layout contract: activations ride the scan sharded
        # over workers on batch, replicated over model (a soft hint; see
        # DESIGN.md §16 — inside full-manual shard_map it degrades to a
        # no-op and the body IS already per-device).
        x = constrain(x, ("data", None, None))
        x, cache_seed, aux = _apply_layer_full(resolve(lp), x, cfg, fl,
                                               positions)
        ys = cache_seed if collect_cache else None
        return (x, aux_acc + aux), ys

    def hybrid(x, lp, blk, call):
        # the block and the call's weights are resolved here, inside the
        # remat boundary, one block at a time like the layers
        x = constrain(x, ("data", None, None))
        t, kv = hybrid_call(resolve(blk), resolve(call), x, e, cfg,
                             positions)
        x, cache_seed, _ = _apply_layer_full(resolve(lp), x, cfg, None,
                                             positions, t)
        return x, cache_seed + kv

    body_fn = remat_wrap(body, remat)
    hybrid_fn = remat_wrap(hybrid, remat)
    e = x
    L = cfg.num_layers
    aux = jnp.zeros((), jnp.float32)
    caches = []
    for start, stop, call in hybrid_segments(cfg):
        if call is None:
            (x, aux), ys = jax.lax.scan(
                body_fn, (x, aux), (_rows(params["layers"], start, stop, L),
                                    _rows(flags, start, stop, L)))
        else:
            x, ys = hybrid_fn(x, _row(params["layers"], start),
                              _row(params["shared_blocks"],
                                   call % cfg.num_mem_blocks),
                              _row(params["hybrid"], call))
        caches.append((call, ys))
    if not collect_cache:
        caches = None
    elif len(caches) == 1:
        caches = caches[0][1]
    else:
        # (conv, ssm) over every layer, then (k, v) over the calls
        mamba = [ys[:2] if call is None
                 else tuple(a[None] for a in ys[:2]) for call, ys in caches]
        attn_kv = [ys[2:] for call, ys in caches if call is not None]
        caches = tuple(jnp.concatenate(a) for a in zip(*mamba)) + \
            tuple(jnp.stack(a) for a in zip(*attn_kv))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x, aux, caches
    logits = unembed(x, embedding=params.get("embedding")
                     if cfg.tie_embeddings else None,
                     lm_head=params.get("lm_head"),
                     final_softcap=cfg.final_logit_softcap)
    return logits, aux, caches


# --- decode ------------------------------------------------------------------------

def init_lm_cache(cfg: ModelConfig, batch: int, seq_len: int):
    """Zero cache pytree, stacked over layers (leading L dim; the hybrid
    family's k/v over its calls)."""
    L = cfg.num_layers
    dtype = dtype_of(cfg)
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        a = cfg.attention
        if a.use_mla:
            return {
                "ckv": jnp.zeros((L, batch, seq_len, a.kv_lora_rank), dtype),
                "kr": jnp.zeros((L, batch, seq_len, a.qk_rope_dim), dtype),
            }
        hd = cfg.head_dim
        return {
            "k": jnp.zeros((L, batch, seq_len, a.num_kv_heads, hd), dtype),
            "v": jnp.zeros((L, batch, seq_len, a.num_kv_heads, hd), dtype),
        }
    s = cfg.ssm
    d_inner, n_heads, conv_dim = ssm_lib.ssm_dims(cfg.d_model, s)
    cache = {
        "conv": jnp.zeros((L, batch, s.conv_width - 1, conv_dim), dtype),
        "ssm": jnp.zeros((L, batch, n_heads, s.head_dim, s.d_state),
                         jnp.float32),
    }
    if fam == "hybrid":
        # one KV cache per call of a shared block (leading n_calls dim)
        a = cfg.attention
        shape = (len(cfg.hybrid_layer_ids), batch, seq_len, a.num_kv_heads,
                 cfg.head_dim)
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
    return cache


def seed_cache_from_prefill(cfg: ModelConfig, cache, seeds, *,
                            start: int = 0):
    """Write prefill cache seeds into a zero decode cache at ``start``.

    ``seeds`` is the scan-stacked tuple ``lm_forward(collect_cache=True)``
    returns: per-layer ``(k, v)`` (GQA) or ``(ckv, kr)`` (MLA), each leaf
    shaped (L, B, T, ...). The forward already applies RoPE to K at the
    absolute positions 0..T-1 — identical values to what ``gqa_decode``
    would have written token-by-token — so seeding the first T slots and
    decoding from ``pos = start + T`` reproduces the full forward exactly
    (tests/test_decode_consistency.py, the vlm image-prefix path)."""
    fam = cfg.family
    if fam not in ("dense", "vlm", "moe"):
        raise NotImplementedError(
            f"prefill cache seeding is attention-only; family {fam!r} "
            "carries recurrent state that has no positional slot to seed")
    names = ("ckv", "kr") if cfg.attention.use_mla else ("k", "v")
    out = dict(cache)
    for name, seed in zip(names, seeds):
        at = (0, 0, start) + (0,) * (seed.ndim - 3)
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], seed.astype(cache[name].dtype), at)
    return out


def cache_shardings_hints():
    """Dim hints for cache leaves: length over data, heads over model."""
    return {
        "k": (None, None, "data", "model", None),
        "v": (None, None, "data", "model", None),
        "ckv": (None, "data", None, "model"),
        "kr": (None, "data", None, None),
        "conv": (None, "data", None, "model"),
        "ssm": (None, "data", "model", None, None),
    }


def lm_decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    """tokens: (B,1) int32; pos: scalar int32. Returns (logits, new_cache)."""
    dtype = dtype_of(cfg)
    eps = cfg.norm_eps
    x = embed(params["embedding"], tokens, dtype)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    flags = layer_flags(cfg)
    fam = cfg.family

    def body(carry, xs, t=None):
        x = carry
        lp, fl, cache_l = xs
        new_cache = dict(cache_l)
        if fam in ("dense", "vlm", "moe"):
            h = rmsnorm(x, lp["attn_norm"], eps)
            if cfg.attention.use_mla:
                o, ckv, kr = attn.mla_decode(lp["attn"], h, cfg.attention,
                                             cache_ckv=cache_l["ckv"],
                                             cache_kr=cache_l["kr"],
                                             pos=pos, eps=eps)
                new_cache = {"ckv": ckv, "kr": kr}
            else:
                o, k, v = attn.gqa_decode(
                    lp["attn"], h, cfg.attention, cache_k=cache_l["k"],
                    cache_v=cache_l["v"], pos=pos,
                    is_global=fl["is_global"],
                    sharded_cache_chunks=cfg.decode_sharded_chunks)
                new_cache = {"k": k, "v": v}
            x = x + o
            h = rmsnorm(x, lp["ffn_norm"], eps)
            if fam == "moe":
                o, _ = moe_lib.moe_forward(lp["moe"], h, cfg.moe,
                                           gated=cfg.gated_mlp)
            else:
                o = mlp(lp["mlp"], h, cfg.gated_mlp)
            x = x + o
        else:
            with obs.block("mamba"):
                h = rmsnorm(x if t is None else x + t, lp["ssm_norm"], eps)
                o, (conv_st, ssm_st) = ssm_lib.mamba2_decode(
                    lp["ssm"], h, cfg.ssm, conv_state=cache_l["conv"],
                    ssm_state=cache_l["ssm"], eps=eps,
                    norm_groups=gate_norm_groups(cfg))
                x = x + o
            new_cache = {"conv": conv_st.astype(cache_l["conv"].dtype),
                         "ssm": ssm_st}
        return x, new_cache

    segments = hybrid_segments(cfg)
    if len(segments) == 1:
        x, new_cache = jax.lax.scan(body, x, (params["layers"], flags,
                                              cache))
    else:
        e = x
        L = cfg.num_layers
        mamba_cache = {"conv": cache["conv"], "ssm": cache["ssm"]}
        pieces, ks, vs = [], [], []
        for start, stop, call in segments:
            if call is None:
                x, c = jax.lax.scan(body, x, (
                    _rows(params["layers"], start, stop, L),
                    _rows(flags, start, stop, L),
                    _rows(mamba_cache, start, stop, L)))
            else:
                t, k, v = _hybrid_call_decode(
                    _row(params["shared_blocks"], call % cfg.num_mem_blocks),
                    _row(params["hybrid"], call), x, e, cfg,
                    cache["k"][call], cache["v"][call], pos)
                x, c = body(x, (_row(params["layers"], start),
                                _row(flags, start),
                                _row(mamba_cache, start)), t)
                c = jax.tree_util.tree_map(lambda a: a[None], c)
                ks.append(k)
                vs.append(v)
            pieces.append(c)
        new_cache = {name: jnp.concatenate([p[name] for p in pieces])
                     for name in ("conv", "ssm")}
        new_cache["k"] = jnp.stack(ks)
        new_cache["v"] = jnp.stack(vs)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, embedding=params.get("embedding")
                     if cfg.tie_embeddings else None,
                     lm_head=params.get("lm_head"),
                     final_softcap=cfg.final_logit_softcap)
    return logits, new_cache
