"""Model FLOPs of one training step of a Zamba2 hybrid LM, from its
configuration file's keys (those of the model's ``config.json``).

Forward plus backward is three times the forward; activations recomputed
under remat are not counted, and attention counts the causal half of its
scores (each query against the keys at or before it). A multiply-add is
two FLOPs.
"""
from __future__ import annotations


def mamba_layer(c: dict, seq: int) -> float:
    """Forward FLOPs per token of one Mamba-2 layer: the in-projection
    (d x (2 d_inner + 2 G N + H)) and out-projection (d_inner x d)
    matmuls, and the chunked SSD (chunk Q = min(chunk_size, seq)) per
    head: C.B scores over the chunk (2 Q N), their product with the inputs
    (2 Q P), the chunk states (2 N P) and their read-out (2 N P)."""
    d, N, P = c["hidden_size"], c["mamba_d_state"], c["mamba_headdim"]
    di = c["mamba_expand"] * d
    H, G = di // P, c["mamba_ngroups"]
    Q = min(c["chunk_size"], seq)
    proj = 2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
    return proj + H * (2 * Q * N + 2 * Q * P + 4 * N * P)


def hybrid_call(c: dict, seq: int) -> float:
    """Forward FLOPs per token of one call of a shared block: q, k, v from
    [h, embedding] (attention_hidden_size wide), o_proj to d, attention
    over the causal half ((seq + 1) / 2 keys a query on average, scores
    and values), the gated MLP (gate_up d x 2 F, down F x d), the call's
    LoRA adapter (d x r, r x 2 F) and its linear (d x d)."""
    d, dA = c["hidden_size"], c["attention_hidden_size"]
    Ha, hd = c["num_attention_heads"], c["attention_head_dim"]
    Hk = c["num_key_value_heads"]
    F, r = c["ffn_hidden_size"], c["adapter_rank"]
    proj = 2 * dA * (Ha + 2 * Hk) * hd + 2 * Ha * hd * d
    attn = 2 * 2 * Ha * hd * (seq + 1) / 2
    mlp = 2 * d * 2 * F + 2 * F * d
    return proj + attn + mlp + 2 * r * (d + 2 * F) + 2 * d * d


def per_token(c: dict, seq: int) -> float:
    """Training FLOPs per token: every layer's Mamba-2 layer, every hybrid
    call, and the tied head (d x V)."""
    forward = (c["num_hidden_layers"] * mamba_layer(c, seq)
               + len(c["hybrid_layer_ids"]) * hybrid_call(c, seq)
               + 2 * c["hidden_size"] * c["vocab_size"])
    return 3.0 * forward
