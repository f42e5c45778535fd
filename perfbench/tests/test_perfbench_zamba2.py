"""The Zamba2 reference against the program and against ``transformers``'
Zamba2 at a CPU size, the round over two model shards against the
reference round, and the Zamba2 FLOP count against a hand count."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops_zamba2
from perfbench.reference import zamba2
from perfbench.tests import tiny

# six layers, two shared blocks and four hybrid calls, so that each block
# is tied over two calls; every width splits over two model shards
TINY = dict(hidden_size=32, num_hidden_layers=6, vocab_size=64,
            mamba_d_state=8, mamba_headdim=8, n_mamba_heads=8, mamba_expand=2,
            mamba_ngroups=2, mamba_d_conv=4, rms_norm_eps=1e-5,
            num_attention_heads=4, num_key_value_heads=4,
            attention_head_dim=16, attention_hidden_size=64,
            ffn_hidden_size=48, intermediate_size=48, adapter_rank=4,
            num_mem_blocks=2, hybrid_layer_ids=[1, 2, 4, 5],
            rope_theta=10000, chunk_size=8, hidden_act="gelu")


def tiny_config(**kw):
    c = json.loads((tiny.BENCH / "configs/zamba2-7b-zoo.json").read_text())
    c.update(TINY, dtype="float32", **kw)
    return c


def program_model(c):
    from perfbench.drivers.zoo_train_hybrid import program_config
    from repro.models.registry import build_model
    return build_model(program_config(c))


def test_reference_matches_the_program_in_float32():
    c = tiny_config()
    dims = zamba2.Dims.from_config(c)
    model = program_model(c)
    params = zamba2.init_params(jax.random.PRNGKey(3), dims)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(shapes)] == \
        [b.shape for b in jax.tree_util.tree_leaves(params)]
    tok = jax.random.randint(jax.random.PRNGKey(4), (1, 33), 0, 64)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    with jax.default_matmul_precision("highest"):
        logits_p = model.forward(params, batch)
        logits_r = zamba2.logits(params, tok[:, :-1], dims)
        lp, gp = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch)[0])(params)
        lr, gr = jax.value_and_grad(zamba2.lm_loss)(
            params, tok[:, :-1], tok[:, 1:], dims)
    # both float32 at full matmul precision, computed in different orders
    # (chunked scan against the quadratic form, query blocks against one
    # softmax): round-off, about 1e-6 of the logits' scale; 1e-4 leaves
    # room, and leaving out any term of the block moves them by far more
    scale = float(jnp.max(jnp.abs(logits_r)))
    assert float(jnp.max(jnp.abs(logits_p - logits_r))) <= 1e-4 * scale
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    # every leaf, the tied blocks (two calls each) included, to 1e-4 of its
    # norm: the gaps read 1e-6 to 2e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree_util.tree_leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b)), jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)


def test_reference_matches_transformers_zamba2():
    """The reference's logits against ``Zamba2ForCausalLM`` with the same
    weights copied in. The sequence is one SSD chunk long: transformers'
    torch path (the one on a CPU) sums the states carried between chunks
    over the wrong axis, which only one chunk leaves out of play."""
    pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from transformers import Zamba2Config, Zamba2ForCausalLM
    c = tiny_config()
    dims = zamba2.Dims.from_config(c)
    seq = 32
    hc = Zamba2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_hidden_layers"],
        layers_block_type=["hybrid" if i in c["hybrid_layer_ids"]
                           else "mamba"
                           for i in range(c["num_hidden_layers"])],
        mamba_d_state=c["mamba_d_state"], mamba_d_conv=c["mamba_d_conv"],
        mamba_expand=c["mamba_expand"], mamba_ngroups=c["mamba_ngroups"],
        n_mamba_heads=c["n_mamba_heads"], chunk_size=seq,
        intermediate_size=c["intermediate_size"], hidden_act="gelu",
        num_attention_heads=c["num_attention_heads"],
        num_mem_blocks=c["num_mem_blocks"],
        use_shared_attention_adapter=False,
        adapter_rank=c["adapter_rank"], use_mem_rope=True,
        rope_theta=c["rope_theta"], rms_norm_eps=c["rms_norm_eps"],
        # the program has no dt floor (the kernel path's (0, inf) limit);
        # the torch path clamps at time_step_min, so set it far below any dt
        time_step_min=1e-9, tie_word_embeddings=True,
        max_position_embeddings=64)
    torch.manual_seed(0)
    hf = Zamba2ForCausalLM(hc).eval()
    p = jax.tree_util.tree_map(
        np.asarray, zamba2.init_params(jax.random.PRNGKey(7), dims))

    def put(param, value):
        param.data = torch.tensor(np.ascontiguousarray(value),
                                  dtype=torch.float32)

    m = hf.model
    put(m.embed_tokens.weight, p["embedding"])
    put(m.final_layernorm.weight, 1 + p["final_norm"])
    calls = {lid: i for i, lid in enumerate(c["hybrid_layer_ids"])}
    for i, layer in enumerate(m.layers):
        mamba = getattr(layer, "mamba_decoder", layer)
        s = {k: v[i] for k, v in p["layers"]["ssm"].items()}
        put(mamba.input_layernorm.weight, 1 + p["layers"]["ssm_norm"][i])
        mx = mamba.mamba
        put(mx.in_proj.weight, s["in_proj"].T)
        put(mx.conv1d.weight, s["conv_w"].T[:, None, :])
        put(mx.conv1d.bias, s["conv_b"])
        put(mx.dt_bias, s["dt_bias"])
        put(mx.A_log, s["A_log"])
        put(mx.D, s["D"])
        put(mx.norm.weight, 1 + s["gate_norm"])
        put(mx.out_proj.weight, s["out_proj"].T)
        if i not in calls:
            continue
        call = calls[i]
        blk = jax.tree_util.tree_map(
            lambda a: a[call % c["num_mem_blocks"]], p["shared_blocks"])
        st = layer.shared_transformer
        put(st.input_layernorm.weight, 1 + blk["attn_norm"])
        put(st.pre_ff_layernorm.weight, 1 + blk["ffn_norm"])
        for name, w in (("q_proj", "wq"), ("k_proj", "wk"),
                        ("v_proj", "wv")):
            a = blk["attn"][w]
            put(getattr(st.self_attn, name).weight, a.reshape(a.shape[0],
                                                              -1).T)
        wo = blk["attn"]["wo"]
        put(st.self_attn.o_proj.weight, wo.reshape(-1, wo.shape[-1]).T)
        put(st.feed_forward.gate_up_proj.weight, blk["gate_up"].T)
        put(st.feed_forward.down_proj.weight, blk["down"].T)
        adapter = st.feed_forward.gate_up_proj_adapter_list[call]
        put(adapter[0].weight, p["hybrid"]["adapter_in"][call].T)
        put(adapter[1].weight, p["hybrid"]["adapter_out"][call].T)
        put(layer.linear.weight, p["hybrid"]["linear"][call].T)
    hf.lm_head.weight = m.embed_tokens.weight
    tok = np.random.default_rng(0).integers(0, c["vocab_size"], (1, seq))
    with torch.no_grad():
        want = hf(torch.tensor(tok), use_cache=False).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(zamba2.logits(
            jax.tree_util.tree_map(jnp.asarray, p), jnp.asarray(tok), dims))
    # float32 on both sides in different orders: about 5e-6 of the
    # logits' scale; 1e-4 leaves room, and dropping the adapter, the
    # linear, the embedding half of the block's input, RoPE or the
    # (hd / 2)^-0.5 scale each moves the logits by more than 1e-2
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


ROUND = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
    import jax
    jax.config.update("jax_threefry_partitionable", True)
    import jax.numpy as jnp
    from perfbench.drivers import zoo_train_hybrid
    from perfbench.reference import compare
    from repro.launch import steps

    # the round as the driver builds it, with the model in float32
    make = steps.make_zoo_train_round
    steps.make_zoo_train_round = lambda *a, **k: make(
        *a, compute_dtype=jnp.float32, **k)

    class Ctx:
        config = json.loads(sys.argv[2])
        traffic = {"batch": 1, "seq": 32}
        seed = 2 ** 33 + 5

    d = zoo_train_hybrid.Driver(Ctx)
    d.setup()
    assert d.zr.n_model == 2 and d.zr.U == 1
    d.free()
    nums, want = d.check()
    print(json.dumps({"nums": nums, "loss": d.readings["loss"],
                      "ref_loss": want["loss"]}))
""")


def test_round_on_two_model_shards_matches_the_reference_round():
    """``round_train`` on 1 worker x 2 model shards (the shared blocks
    and per-call weights gathered over the model axis one block at a
    time), three rounds of EF top-kappa, packed MAC, BIHT and Adam, against
    the reference round in the same flat order. The model is computed in
    float32 here: in bfloat16 this tiny random model's gradients differ
    from float32's by 5-30% per leaf, which would hide a layout fault."""
    c = tiny_config(round=dict(tiny_config()["round"], chunk=1024,
                               model_shards=2))
    r = subprocess.run(
        [sys.executable, "-c", ROUND, str(tiny.ROOT), json.dumps(c)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    nums = out["nums"]
    # float32 against float32: the residual and first decoded gradient
    # agree to round-off (read 1e-6 to 1e-4) unless a selection flips on a
    # near tie; 1e-2 is far under what a misplaced leaf or a block gathered
    # from the wrong shard gives (order one)
    assert nums["residual_gap"] <= 1e-2
    assert nums["grad_gap_median"] <= 1e-2
    assert nums["change_gap_median"] <= 1e-2
    np.testing.assert_allclose(out["loss"], out["ref_loss"], rtol=1e-4)


PROBES = textwrap.dedent("""
    import contextlib, json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
    from perfbench.drivers import zoo_train_hybrid

    class Ctx:
        config = json.loads(sys.argv[2])
        traffic = {"batch": 1, "seq": 32}
        seed = 7

    d = zoo_train_hybrid.Driver(Ctx)
    d.setup()
    spans = []

    def span(name):
        spans.append(name)
        return contextlib.nullcontext()

    d.probes(span, min_span_s=0.0, samples=2)
    print(json.dumps({"spans": spans, "measures": d.measures}))
""")


def test_probes_time_the_round_on_two_model_shards():
    """The traced run's probes on 1 worker x 2 model shards: the model's
    gradient and the shared block's call, the latter on the round's own
    master rows and resolver, each timed ``samples`` times."""
    c = tiny_config(round=dict(tiny_config()["round"], chunk=1024,
                               model_shards=2))
    r = subprocess.run(
        [sys.executable, "-c", PROBES, str(tiny.ROOT), json.dumps(c)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["spans"] == ["probe.model_grad"] * 2 \
        + ["probe.shared_block"] * 2
    for name in ("model_grad_ms", "shared_block_ms"):
        ms = out["measures"][name]
        assert len(ms) == 2 and all(x > 0 for x in ms), name


def test_layout_at_one_shard_is_pytree_order():
    c = tiny_config()
    dims = zamba2.Dims.from_config(c)
    params = zamba2.init_params(jax.random.PRNGKey(5), dims)
    lay = zamba2.Layout(params, 1024, 64)
    flat = np.concatenate([np.asarray(x).reshape(-1)
                           for x in jax.tree_util.tree_leaves(params)])
    master = np.asarray(lay.to_master(params)).reshape(-1)
    np.testing.assert_array_equal(master[:flat.size], flat)
    assert lay.n_chunks % 64 == 0 and not master[flat.size:].any()
    two = zamba2.Layout(params, 1024, 64, mp=2)
    back = two.to_tree(two.to_master(params))
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(
        np.asarray(two.leaf_norms(two.to_master(params))),
        [np.linalg.norm(np.asarray(x))
         for x in jax.tree_util.tree_leaves(params)], rtol=1e-6)


def test_zamba2_flops_hand_count():
    c = json.loads((tiny.BENCH / "configs/zamba2-7b-zoo.json").read_text())
    seq = 4096
    # one Mamba layer: in_proj 3584 x 14704, out_proj 7168 x 3584, SSD
    # 112 heads x (2*256*64 + 2*256*64 + 4*64*64)
    mamba = 2 * 3584 * 14704 + 2 * 7168 * 3584 + 112 * (
        2 * 256 * 64 + 2 * 256 * 64 + 4 * 64 * 64)
    # one call: q, k, v 7168 -> 32 x 224, o 7168 -> 3584, causal scores
    # and values, gate_up 3584 x 28672, down 14336 x 3584, adapter
    # 3584 x 128 and 128 x 28672, linear 3584 x 3584
    call = (3 * 2 * 7168 * 7168 + 2 * 7168 * 3584
            + 2 * 2 * 32 * 224 * (seq + 1) / 2
            + 2 * 3584 * 28672 + 2 * 14336 * 3584
            + 2 * 3584 * 128 + 2 * 128 * 28672 + 2 * 3584 * 3584)
    head = 2 * 3584 * 32000
    assert flops_zamba2.mamba_layer(c, seq) == mamba
    assert flops_zamba2.hybrid_call(c, seq) == pytest.approx(call)
    assert flops_zamba2.per_token(c, seq) == pytest.approx(
        3 * (6 * mamba + call + head))
    # about 2.0 GFLOP forward a token: 1.0 in the six Mamba layers, 0.76
    # in the call, 0.23 in the head
    assert 1.9e9 < (6 * mamba + call + head) < 2.1e9
