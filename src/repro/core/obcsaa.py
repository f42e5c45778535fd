"""OBCSAA — One-Bit Compressive-Sensing Analog Aggregation (paper §II).

End-to-end aggregator:  per worker  C(g) = sign(Φ · sparse_κ(g))  (eq. 7),
power-controlled superposition over the MAC (eq. 8-12), post-processing
(eq. 13), 1-bit CS decode via the ``repro.decode`` registry (eq. 43,
selected by ``OBCSAAConfig.decoder``; DESIGN.md §9), model update (eq. 14).

Two execution modes share the same compression core:

- ``simulate_round``: the paper's §V simulation — U workers' gradients are
  stacked on one device, the MAC sum is an einsum, channels/noise drawn from
  a PRNG. Used by the FL runtime + paper-figure benchmarks.
- ``shardmap_compress``/``shardmap_reconstruct``: the production path — each
  data-parallel shard IS a worker; the MAC superposition IS the psum over the
  worker mesh axes (DESIGN.md §3). Reconstruction is sharded over chunks.

The measurement operator is block-diagonal (chunked) per DESIGN.md §4; for
the paper's D=50,890 MLP one chunk of D_c=D reproduces the paper exactly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import channel as chan
from repro.core.measurement import make_phi
from repro.core.quantize import PACK, pack_signs, sign_pm1, unpack_signs
from repro.core.sparsify import topk_sparsify, topk_sparsify_bisect
from repro.decode import DecodeConfig
from repro.decode import decode as cs_decode
from repro.dist import collectives as coll


@dataclass(frozen=True)
class OBCSAAConfig:
    chunk: int = 4096            # D_c
    measure: int = 1024          # S_c
    topk: int = 409              # κ_c
    # Decode-side sparsity: the superposed gradient has κ̄ > κ (paper §II-B.2,
    # distinct per-worker supports). 0 -> heuristic min(4κ, S/2).
    recon_topk: int = 0
    biht_iters: int = 30
    recon_alg: str = "biht"      # BIHT (paper §V); "iht" also available
    recon_tau: float = 1.0
    # Decoder registry selection (repro.decode, DESIGN.md §9). "" keeps the
    # legacy recon_alg choice; any registered name overrides it.
    decoder: str = ""
    # Warm-start decode: the FL loop seeds round t's decode with round t−1's
    # raw estimate (temporal gradient correlation; reset on schedule change).
    warm_start: bool = False
    noise_var: float = 1e-4      # σ² (mW)
    p_max: float = 10.0          # P^Max (mW)
    phi_seed: int = 42
    magnitude_tracking: bool = True
    # SPMD-friendly top-k (bisection threshold; §Perf iteration 6):
    # jax.lax.top_k's sort cannot be partitioned by GSPMD and all-gathers the
    # full chunk array at production scale. The distributed train step turns
    # this on; the single-host simulation keeps exact sort-based top-k.
    spmd_topk: bool = False
    # Threshold-bisection cap for the spmd path (selection resolution
    # max·2^-iters; 40 over-resolves f32 — the engine bench runs 20 with a
    # selection-parity check, DESIGN.md §11). The search stops once every
    # row's top-κ set is settled, output bitwise that of `bisect_iters`
    # passes (one scalar all-reduce every two passes under GSPMD). Applies to
    # compression, error-feedback splits and the decoder's hard threshold.
    bisect_iters: int = 40
    use_kernels: bool = False    # Pallas kernels (interpret on CPU)
    # Packed 1-bit codec (DESIGN.md §13): compress emits uint32 words (32
    # signs each) instead of f32 ±1 symbols, and the shard-mapped MAC
    # accumulates them as exact int32 bit-counts before the power scale —
    # 32x less uplink signal traffic, bit-for-bit equal to the f32 path.
    # Requires measure % 32 == 0 and uniform K_i·b_t on the wire path.
    packed: bool = False
    # Fixed-step decode stability guard (DESIGN.md §13): "off" | "raise" |
    # "fallback" — checks τ against the restricted spectral estimate of Φ
    # before running the iht family (divergence would silently return NaN).
    decode_validate: str = "off"

    def __post_init__(self):
        if self.packed and self.measure % PACK:
            raise ValueError(
                f"OBCSAAConfig(packed=True) needs measure (S_c) to be a "
                f"multiple of {PACK}; got {self.measure} (DESIGN.md §13)")

    def phi(self, dtype=jnp.float32):
        return make_phi(self.phi_seed, self.measure, self.chunk, dtype)

    @property
    def decode_k(self) -> int:
        return self.recon_topk or min(4 * self.topk, self.measure // 2)

    def decode_cfg(self) -> DecodeConfig:
        """Map the aggregation knobs onto a registry DecodeConfig. The
        warm-start selection swaps ``iht`` for its warm-capable alias so
        carried state is actually consumed, and REJECTS decoders that
        would silently drop it (DESIGN.md §9)."""
        alg = self.decoder or self.recon_alg
        if self.warm_start:
            if alg == "iht":
                alg = "iht_warm"
            from repro.decode import get_decoder
            if not get_decoder(alg).warm:
                raise ValueError(
                    f"warm_start=True but decoder {alg!r} is not "
                    "warm-capable (state would be silently dropped); use "
                    "iht, iht_warm or iht_fused")
        return DecodeConfig(algorithm=alg, iters=self.biht_iters,
                            tau=self.recon_tau, use_kernels=self.use_kernels,
                            ht="bisect" if self.spmd_topk else "sort",
                            ht_iters=self.bisect_iters,
                            validate=self.decode_validate)


# --- compression core (per worker) ---------------------------------------------

def compress_chunks(cfg: OBCSAAConfig, flat: jnp.ndarray, phi=None,
                    presparsified: bool = False):
    """Per-worker compression C(g) = sign(Φ sparse_κ(g)) (eq. 6-7), chunked.

    flat: (D_pad,) with D_pad % chunk == 0, or pre-chunked (n, chunk).
    Returns (signs (n_chunks, S_c), mags (n_chunks,)) — with
    ``cfg.packed``, signs is instead uint32 (n_chunks, S_c//32): the sign
    epilogue packs 32 symbols per word via the shared ``x >= 0`` predicate,
    so unpacking reproduces the f32 symbols bit for bit (DESIGN.md §13).

    ``presparsified=True`` asserts the input is already the top-κ sparse
    vector and skips the selection — the engine's error-feedback path
    computes sparse_κ once for the residual split and feeds it straight
    here (DESIGN.md §11), instead of thresholding the same array twice."""
    phi = cfg.phi(flat.dtype) if phi is None else phi
    gc = flat if flat.ndim == 2 else flat.reshape(-1, cfg.chunk)
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        sparse = gc if presparsified else kops.topk_select(gc, cfg.topk)[0]
        signs = (kops.cs_project_pack(phi, sparse) if cfg.packed
                 else kops.cs_project_sign(phi, sparse))
    else:
        if presparsified:
            sparse = gc
        elif cfg.spmd_topk:
            sparse, _ = topk_sparsify_bisect(gc, cfg.topk,
                                             iters=cfg.bisect_iters)
        else:
            sparse, _ = topk_sparsify(gc, cfg.topk)
        proj = jnp.einsum("sd,nd->ns", phi, sparse)
        signs = pack_signs(proj) if cfg.packed else sign_pm1(proj)
    mags = jnp.linalg.norm(sparse, axis=-1)
    return signs, mags


def reconstruct_chunks(cfg: OBCSAAConfig, y: jnp.ndarray,
                       mags: Optional[jnp.ndarray] = None, phi=None,
                       x0: Optional[jnp.ndarray] = None,
                       return_raw: bool = False):
    """y: (n_chunks, S_c) post-processed aggregate (eq. 13). Decodes via the
    registry (eq. 43; repro.decode) and returns flat (D_pad,).

    ``x0``: warm-start chunks (n_chunks, D_c) from the previous round's RAW
    estimate. ``return_raw=True`` additionally returns that raw (pre-
    magnitude-scaling) estimate so the caller can carry it as next round's
    ``x0`` — warm state must live in decoder space, not gradient space."""
    phi = cfg.phi(y.dtype) if phi is None else phi
    xhat = cs_decode(y, phi, cfg.decode_k, cfg.decode_cfg(), x0=x0)
    raw = xhat
    if cfg.magnitude_tracking and mags is not None:
        norm = jnp.linalg.norm(xhat, axis=-1, keepdims=True)
        xhat = xhat * (mags[:, None] / jnp.maximum(norm, 1e-12))
    flat = xhat.reshape(-1)
    return (flat, raw) if return_raw else flat


# --- simulation mode (paper §V) --------------------------------------------------

def simulate_round(cfg: OBCSAAConfig, grads_flat: jnp.ndarray,
                   k_weights: jnp.ndarray, beta: jnp.ndarray, b_t,
                   h: jnp.ndarray, key, decode_x0=None, noise_var=None,
                   presparsified: bool = False) -> Tuple[jnp.ndarray, dict]:
    """grads_flat: (U, D). Returns (g_hat (D,), diag).

    Implements eq. (6)-(14) with perfect channel inversion: the received
    aggregate is Σ_i K_i b_t β_i C(g_i) + z (eq. 12). ``decode_x0`` warm-
    starts the decoder (eq. 43) with the previous round's raw estimate;
    ``diag["decode_xhat"]``, diag's one key, carries this round's raw
    estimate back out so the FL loop can thread the state (DESIGN.md §9).
    ``noise_var`` optionally overrides ``cfg.noise_var`` with a traced
    value — the FL engine's SNR arms axis (DESIGN.md §11) sweeps it
    without retracing. ``presparsified=True`` marks ``grads_flat`` as
    already top-κ sparse per chunk (the engine's fused EF path; see
    ``compress_chunks``). The compression runs under the ``repro.codec``
    phase scope, the MAC, noise and decode under ``repro.mac_decode``
    (``repro.obs``)."""
    U, D = grads_flat.shape
    pad = (-D) % cfg.chunk
    with obs.phase("codec"):
        gpad = jnp.pad(grads_flat, ((0, 0), (0, pad)))
        phi = cfg.phi()
        signs, mags = jax.vmap(
            lambda g: compress_chunks(cfg, g, phi,
                                      presparsified=presparsified))(gpad)
    with obs.phase("mac_decode"):
        # MAC superposition (eq. 12). The packed codec unpacks to the
        # exact ±1 floats the f32 path produced (shared sign predicate,
        # DESIGN.md §13), so the identical einsum keeps the two paths
        # bit-for-bit equal; the wire-level int32 bit-count MAC lives in
        # the shard-mapped path (collectives.psum_bits_mac).
        symbols = unpack_signs(signs) if cfg.packed else signs
        w = k_weights * beta * b_t                      # (U,)
        y = jnp.einsum("u,ucs->cs", w.astype(symbols.dtype), symbols)
        nv = cfg.noise_var if noise_var is None else noise_var
        noise = chan.draw_noise(key, y.shape, nv)
        y = y + noise                                   # eq. (12)
        denom = jnp.maximum(jnp.sum(k_weights * beta) * b_t, 1e-12)
        y = y / denom                                   # eq. (13)
        mbar = jnp.einsum("u,uc->c",
                          (k_weights * beta).astype(mags.dtype), mags
                          ) / jnp.maximum(jnp.sum(k_weights * beta), 1e-12)
        ghat, xraw = reconstruct_chunks(
            cfg, y, mbar if cfg.magnitude_tracking else None, phi,
            x0=decode_x0, return_raw=True)
        ghat = ghat[:D]
    return ghat, {"decode_xhat": xraw}


# --- distributed mode (inside shard_map over worker axes) -------------------------

def shardmap_compress(cfg: OBCSAAConfig, local_flat: jnp.ndarray,
                      worker_axes, *, k_weight, beta_i, b_t, phi=None,
                      wire_dtype=None):
    """Worker-side half, INSIDE shard_map(manual over worker_axes).

    Compress this worker's local gradient (eq. 7), scale by the power
    factor (eq. 10-11), and superpose over the MAC: the psum over
    ``worker_axes`` IS the over-the-air sum (eq. 12). ``wire_dtype``
    optionally narrows the transmitted symbols (±w each), halving wire
    bytes with bf16.

    Returns ``(y, ksum, mag_sum)``: the raw received aggregate, the
    weight normaliser Σ_i K_i β_i, and the weighted magnitude sum (None
    unless ``cfg.magnitude_tracking``) — everything the PS-side
    ``shardmap_reconstruct`` needs.

    With ``cfg.packed`` the wire carries uint32 words (32 signs each) and
    the superposition is the exact int32 bit-count MAC
    (``collectives.psum_bits_mac``): y = K·b_t · Σ_i β_i·(2·bit_i − 1),
    assuming the worker-uniform K_i·b_t of the shard-mapped trainer
    (equal-sized shards; DESIGN.md §13). ``wire_dtype`` is ignored on the
    packed path — the symbols are already 1-bit."""
    signs, mags = compress_chunks(cfg, local_flat, phi)
    return shardmap_mac(cfg, signs, mags, worker_axes, k_weight=k_weight,
                        beta_i=beta_i, b_t=b_t, wire_dtype=wire_dtype)


def shardmap_mac(cfg: OBCSAAConfig, signs, mags, worker_axes, *, k_weight,
                 beta_i, b_t, wire_dtype=None):
    """MAC superposition of one worker's ALREADY-compressed symbols
    (eq. 12), INSIDE shard_map(manual over worker_axes).

    Split out of ``shardmap_compress`` so callers that produce their signs
    in blocks — the sharded zoo round's ``lax.map``-chunked compression at
    ≥1B parameters (engine/zoo.py, DESIGN.md §14) — superpose through the
    identical wire path: exact int32 ``psum_bits_mac`` when ``cfg.packed``,
    f32 symbol psum otherwise. Returns ``(y, ksum, mag_sum)`` exactly like
    ``shardmap_compress``."""
    if cfg.packed:
        s_int = coll.psum_bits_mac(signs, worker_axes, beta_i=beta_i)
        y = s_int.astype(jnp.float32) * (k_weight * b_t)  # eq. (12)
    else:
        wd = wire_dtype or signs.dtype
        w = (k_weight * beta_i * b_t).astype(wd)
        y = coll.psum(signs.astype(wd) * w, worker_axes)    # eq. (12)
    ksum = coll.psum(k_weight * beta_i, worker_axes)
    mag_sum = (coll.psum(mags * (k_weight * beta_i).astype(mags.dtype),
                         worker_axes)
               if cfg.magnitude_tracking else None)
    return y, ksum, mag_sum


def shardmap_reconstruct(cfg: OBCSAAConfig, y: jnp.ndarray, ksum,
                         mag_sum=None, *, b_t, noise_key, phi=None,
                         decode_x0=None) -> jnp.ndarray:
    """PS-side half: AWGN + post-processing (eq. 13) + 1-bit CS decode
    (eq. 43, registry-selected via ``cfg.decoder``).

    Noise is added once at the PS — every shard folds the same key, so the
    (replicated) draw is identical and the result stays replicated.
    ``decode_x0`` warm-starts the decoder when the caller carries state."""
    denom = jnp.maximum(ksum * b_t, 1e-12)
    noise = chan.draw_noise(noise_key, y.shape, cfg.noise_var)
    y = (y.astype(jnp.float32) + noise) / denom         # eq. (13)
    mbar = (mag_sum / jnp.maximum(ksum, 1e-12)
            if (cfg.magnitude_tracking and mag_sum is not None) else None)
    return reconstruct_chunks(cfg, y, mbar, phi, x0=decode_x0)


def shardmap_aggregate(cfg: OBCSAAConfig, local_flat: jnp.ndarray,
                       worker_axes, *, k_weight, beta_i, b_t, n_workers: int,
                       noise_key, phi=None) -> jnp.ndarray:
    """Called INSIDE shard_map(manual over worker_axes). local_flat: (D_pad,)
    is this worker's local gradient; returns the reconstructed global
    gradient (identical on all workers, like the PS broadcast)."""
    del n_workers  # implied by worker_axes; kept for call-site stability
    y, ksum, mag_sum = shardmap_compress(cfg, local_flat, worker_axes,
                                         k_weight=k_weight, beta_i=beta_i,
                                         b_t=b_t, phi=phi)
    return shardmap_reconstruct(cfg, y, ksum, mag_sum, b_t=b_t,
                                noise_key=noise_key, phi=phi)


def comm_stats(cfg: OBCSAAConfig, D: int) -> dict:
    """Wire statistics per worker per round (vs uncompressed analog float)."""
    n_chunks = -(-D // cfg.chunk)
    symbols = n_chunks * cfg.measure + (n_chunks if cfg.magnitude_tracking
                                        else 0)
    # packed codec wire accounting (DESIGN.md §13): 1 bit per sign symbol
    # vs 32 for the f32 representation; the per-chunk magnitude scalar
    # stays a 32-bit float in both codecs
    mag_bits = 32 * n_chunks if cfg.magnitude_tracking else 0
    bits_f32 = 32 * n_chunks * cfg.measure + mag_bits
    bits_packed = n_chunks * cfg.measure + mag_bits
    return {
        "D": D,
        "n_chunks": n_chunks,
        "symbols_per_round": symbols,
        "compression_ratio": D / symbols,
        "latency_fraction": symbols / D,   # same-bandwidth transmission time
        "uplink_bits_f32": bits_f32,
        "uplink_bits_packed": bits_packed,
        "packed_wire_ratio": bits_f32 / bits_packed,
    }
