"""Analytic per-device FLOP and HBM-byte estimators for the roofline.

XLA's aggregate ``cost_analysis()`` counts while-loop (scan) bodies once, so
for scanned layer stacks it undercounts by ~num_layers. Collective bytes are
recovered exactly from the HLO with trip-count scaling (dryrun.py); compute
and memory use the napkin models below (assumptions documented in
EXPERIMENTS.md §Roofline).

Conventions:
  train   : fwd+bwd = 6·N_active·T, remat adds one fwd (=> 8·N·T) +
            quadratic attention terms (full-score blockwise impl, no causal
            skip at baseline) + OBCSAA compress/decode matmuls.
  prefill : 2·N·T + attention scores/AV.
  decode  : 2·N_active·B + per-layer cache attention.
Bytes:
  train   : params read fwd+bwd (bf16) + fp32 grad write + activation
            traffic ~ 16·B·S·d per layer + OBCSAA chunk/sign/BIHT traffic.
  prefill : params + 8·B·S·d per layer + KV cache write.
  decode  : active params + full KV/state cache read + logits.
"""
from __future__ import annotations

from repro.configs.base import InputShape, ModelConfig, TrainConfig


def _attn_layers(cfg: ModelConfig):
    """(n_global, n_local, window) attention-layer split."""
    if cfg.family == "ssm":
        return 0, 0, 0
    if cfg.family == "hybrid":
        n = len(cfg.hybrid_layer_ids)
        return n, 0, 0
    L = cfg.num_layers + cfg.num_encoder_layers
    a = cfg.attention
    if cfg.local_global_period:
        ng = cfg.num_layers // cfg.local_global_period
        return ng + cfg.num_encoder_layers, cfg.num_layers - ng, a.window
    if a and a.window:
        return cfg.num_encoder_layers, cfg.num_layers, a.window
    return L, 0, 0


def active_params(cfg: ModelConfig) -> int:
    n = cfg.param_count()
    if cfg.moe is None:
        return n
    m = cfg.moe
    n_mats = 3 if cfg.gated_mlp else 2
    per_expert = n_mats * cfg.d_model * cfg.d_ff
    return (n - cfg.num_layers * m.num_experts * per_expert
            + cfg.num_layers * m.top_k * per_expert)


def _attn_flops(cfg: ModelConfig, B: int, S: int, passes: float) -> float:
    ng, nl, window = _attn_layers(cfg)
    a = cfg.attention
    if a is None:
        return 0.0
    hd = cfg.head_dim if not a.use_mla else (a.qk_nope_dim + a.qk_rope_dim)
    H = a.num_heads
    f = 0.0
    f += ng * 4.0 * B * S * S * H * hd          # full layers: QK^T + AV
    if nl:
        w = min(window or S, S)
        f += nl * 4.0 * B * S * S * H * hd      # baseline computes full S^2
        # (masked; windowed-score skipping is a §Perf optimization)
    return f * passes


def obcsaa_flops(tcfg: TrainConfig, n_params: int) -> float:
    """Per-worker compress + PS decode (BIHT) matmuls over chunked Φ."""
    d = n_params
    compress = 2.0 * d * tcfg.cs_measure          # (D/Dc) chunks x 2·Sc·Dc
    decode = (4.0 * tcfg.biht_iters + 2.0) * d * tcfg.cs_measure
    return compress + decode


def flops_per_device(cfg: ModelConfig, shape: InputShape, n_dev: int,
                     agg: str = "obcsaa",
                     tcfg: TrainConfig = None) -> float:
    B, S = shape.global_batch, shape.seq_len
    na = active_params(cfg)
    tcfg = tcfg or TrainConfig()
    if shape.kind == "train":
        T = B * S
        f = 8.0 * na * T + _attn_flops(cfg, B, S, passes=4.0)
        if agg == "obcsaa":
            # compression per worker is sharded over model axis only; decode
            # sharded over all devices
            f += obcsaa_flops(tcfg, cfg.param_count())
        return f / n_dev
    if shape.kind == "prefill":
        T = B * S
        return (2.0 * na * T + _attn_flops(cfg, B, S, passes=1.0)) / n_dev
    # decode: one token, cache length S
    f = 2.0 * na * B
    ng, nl, window = _attn_layers(cfg)
    a = cfg.attention
    if a is not None:
        hd = cfg.head_dim if not a.use_mla else a.kv_lora_rank
        f += (ng * S + nl * min(window or S, S)) * 4.0 * B * a.num_heads * hd
    if cfg.ssm is not None:
        d_in = cfg.ssm.expand * cfg.d_model
        f += cfg.num_layers * 6.0 * B * d_in * cfg.ssm.d_state
    return f / n_dev


def cache_bytes(cfg: ModelConfig, B: int, S: int) -> float:
    """KV/state cache size (bf16 entries, f32 SSM state)."""
    total = 0.0
    a = cfg.attention
    if cfg.family in ("dense", "vlm", "moe"):
        if a.use_mla:
            total += cfg.num_layers * B * S * (a.kv_lora_rank
                                               + a.qk_rope_dim) * 2
        else:
            total += cfg.num_layers * B * S * 2 * a.num_kv_heads \
                * cfg.head_dim * 2
    if cfg.family == "audio":
        total += cfg.num_layers * B * (S + cfg.encoder_seq_len) * 2 \
            * a.num_kv_heads * cfg.head_dim * 2
    if cfg.ssm is not None:
        d_in = cfg.ssm.expand * cfg.d_model
        nheads = d_in // cfg.ssm.head_dim
        total += cfg.num_layers * B * nheads * cfg.ssm.head_dim \
            * cfg.ssm.d_state * 4
        total += cfg.num_layers * B * (cfg.ssm.conv_width - 1) \
            * (d_in + 2 * cfg.ssm.n_groups * cfg.ssm.d_state) * 2
    if cfg.family == "hybrid":
        total += cfg.num_layers * B * S * 2 * a.num_kv_heads \
            * cfg.head_dim * 2
    return total


def bytes_per_device(cfg: ModelConfig, shape: InputShape, n_dev: int,
                     agg: str = "obcsaa",
                     tcfg: TrainConfig = None) -> float:
    B, S = shape.global_batch, shape.seq_len
    n = cfg.param_count()
    na = active_params(cfg)
    d = cfg.d_model
    L = cfg.num_layers + cfg.num_encoder_layers
    tcfg = tcfg or TrainConfig()
    if shape.kind == "train":
        b = 2.0 * n * 2 + 4.0 * n          # params fwd+bwd bf16, grads f32
        b += L * 16.0 * B * S * d * 2      # activation traffic (remat'd)
        if agg == "obcsaa":
            D = cfg.param_count()
            iters = tcfg.biht_iters
            b += D * 4 * (2 + 2 * iters)   # chunk reads per BIHT pass
        return b / n_dev
    if shape.kind == "prefill":
        b = na * 2 + L * 8.0 * B * S * d * 2 + cache_bytes(cfg, B, S)
        return b / n_dev
    b = na * 2 + cache_bytes(cfg, B, S) + B * cfg.vocab_size * 4
    return b / n_dev
