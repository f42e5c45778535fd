"""zamba2-7b [hybrid] — Mamba2 backbone + two shared transformer blocks
[arXiv:2411.15242; huggingface.co/Zyphra/Zamba2-7B-Instruct config.json].

81 Mamba-2 layers. Before each of the 13 layers in ``hybrid_layer_ids``,
call c runs shared block c % 2 on ``[h, embedding]`` (7,168 wide): RMS
norm, 32-head MHA at head_dim 224 with RoPE and scale (224 / 2)^-0.5,
``o_proj`` to 3,584, RMS norm, and a gated-GELU MLP of width 14,336 whose
``gate_up`` carries call c's own rank-128 LoRA adapter; call c's own
3,584 x 3,584 ``linear`` follows, and its output joins h at that layer's
Mamba norm. No residual inside the block, no embedding scale, a tied
head and the gated norm grouped over the two SSM groups.
"""
import dataclasses

from repro.configs.base import AttentionConfig, ModelConfig, SSMConfig

HEAD_DIM = 224

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    d_ff=14336,                     # ffn_hidden_size of the shared MLP
    vocab_size=32000,
    ssm=SSMConfig(d_state=64, head_dim=64, expand=2, n_groups=2,
                  chunk_size=256, conv_width=4),
    attention=AttentionConfig(num_heads=32, num_kv_heads=32,
                              head_dim=HEAD_DIM, rope_theta=10_000.0,
                              softmax_scale=(HEAD_DIM / 2) ** -0.5),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    gated_mlp=True,
    embed_scale=False,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="[arXiv:2411.15242] Zamba2",
)


def smoke_config() -> ModelConfig:
    """Two layers, both hybrid, so both shared blocks and two adapters
    are used."""
    hd = 32
    return dataclasses.replace(
        CONFIG, name="zamba2-smoke", num_layers=2, d_model=128, d_ff=256,
        vocab_size=512, hybrid_layer_ids=(0, 1), adapter_rank=8,
        ssm=SSMConfig(d_state=16, head_dim=32, expand=2, n_groups=2,
                      chunk_size=32, conv_width=4),
        attention=AttentionConfig(num_heads=8, num_kv_heads=8, head_dim=hd,
                                  rope_theta=10_000.0,
                                  softmax_scale=(hd / 2) ** -0.5))
