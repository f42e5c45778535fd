"""Model FLOPs of one training step, from a configuration's shapes.

Forward plus backward is three times the forward; activations recomputed
under remat are not counted. A multiply-add is two FLOPs.
"""
from __future__ import annotations


def mamba2_per_token(model: dict, seq: int) -> float:
    """Training FLOPs per token of a Mamba-2 LM with a tied head.

    Per layer: the in-projection (d x (2 d_inner + 2 G N + H)) and the
    out-projection (d_inner x d) matmuls, and the chunked SSD (chunk
    Q = min(chunk_size, seq)) per head: C.B scores over the chunk (2 Q N),
    their product with the inputs (2 Q P), the chunk states (2 N P) and
    their read-out (2 N P). Plus the tied head (d x V)."""
    d, L, V = model["d_model"], model["n_layers"], model["vocab"]
    N, P = model["d_state"], model["head_dim"]
    di = model["expand"] * d
    H = di // P
    G = model["n_groups"]
    Q = min(model["chunk_size"], seq)
    proj = 2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
    ssd = H * (2 * Q * N + 2 * Q * P + 4 * N * P)
    forward = L * (proj + ssd) + 2 * d * V
    return 3.0 * forward


def mlp_per_sample(sizes) -> float:
    """Training FLOPs per sample of a dense MLP with layer widths
    ``sizes`` (weights only; biases and activations are not counted)."""
    weights = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return 6.0 * weights
