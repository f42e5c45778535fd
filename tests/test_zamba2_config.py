"""The zamba2-7b configuration against the published config.json's keys,
as the benchmark's configuration file holds them, and the published
hybrid layer at the smoke size."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import transformer

PUBLISHED = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                        / "configs" / "zamba2-7b-zoo.json").read_text())


def test_config_has_the_published_keys():
    c, p = get_config("zamba2-7b"), PUBLISHED
    a, s = c.attention, c.ssm
    assert c.family == "hybrid"
    assert c.num_layers == p["published"]["num_hidden_layers"] == 81
    assert c.d_model == p["hidden_size"] == 3584
    assert c.vocab_size == p["vocab_size"] == 32000
    assert c.d_ff == p["ffn_hidden_size"] == p["intermediate_size"] == 14336
    assert 2 * c.d_model == p["attention_hidden_size"] == 7168
    assert a.head_dim == c.head_dim == p["attention_head_dim"] == 224
    assert a.num_heads == p["num_attention_heads"] == 32
    assert a.num_kv_heads == p["num_key_value_heads"] == 32
    assert c.d_model // a.num_heads == p["kv_channels"] == 112
    assert a.softmax_scale == (224 / 2) ** -0.5
    assert a.rope_theta == p["rope_theta"] and p["use_mem_rope"]
    assert c.num_mem_blocks == p["published"]["num_mem_blocks"] == 2
    assert list(c.hybrid_layer_ids) == p["published"]["hybrid_layer_ids"]
    assert c.adapter_rank == p["adapter_rank"] == 128
    assert p["hidden_act"] == "gelu" and c.gated_mlp
    assert c.norm_eps == p["rms_norm_eps"] == 1e-5
    assert c.tie_embeddings and not c.embed_scale
    assert (s.d_state, s.head_dim, s.expand, s.n_groups, s.chunk_size,
            s.conv_width) == (p["mamba_d_state"], p["mamba_headdim"],
                              p["mamba_expand"], p["mamba_ngroups"],
                              p["chunk_size"], p["mamba_d_conv"])
    assert transformer.gate_norm_groups(c) == s.n_groups == 2
    assert 2 * c.d_model // s.head_dim == p["n_mamba_heads"] == 112
    assert not p["use_shared_attention_adapter"]
    assert p["use_shared_mlp_adapter"]


def test_parameter_count_matches_init():
    cfg = get_smoke_config("zamba2-7b")
    shapes = jax.eval_shape(lambda k: transformer.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.param_count()
    assert set(shapes) == {"embedding", "layers", "final_norm",
                           "shared_blocks", "hybrid"}
    assert shapes["shared_blocks"]["attn"]["wq"].shape == (2, 256, 8, 32)
    assert shapes["shared_blocks"]["attn"]["wo"].shape == (2, 8, 32, 128)
    assert shapes["hybrid"]["adapter_out"].shape == (2, 8, 512)


def test_segments_split_the_stack_at_the_hybrid_layers():
    cfg = get_config("zamba2-7b")
    seg = transformer.hybrid_segments(cfg)
    assert seg[:3] == [(0, 6, None), (6, 7, 0), (7, 11, None)]
    assert seg[-2:] == [(77, 78, 12), (78, 81, None)]
    assert sum(b - a for a, b, _ in seg) == 81
    assert transformer.hybrid_segments(get_config("mamba2-2.7b")) == \
        [(0, 64, None)]


def test_hybrid_input_joins_the_mamba_norm_only():
    """The call's output t enters the layer's norm, not its residual: with
    the mixer's out-projection at zero the layer returns h unchanged."""
    cfg = get_smoke_config("zamba2-7b")
    params = transformer.init_lm(jax.random.PRNGKey(0), cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    lp["ssm"]["out_proj"] = jnp.zeros_like(lp["ssm"]["out_proj"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    t = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))
    out, _, _ = transformer._apply_layer_full(lp, x, cfg, None,
                                              jnp.arange(8), t)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
