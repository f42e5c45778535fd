"""Real sharded backward passes (repro.engine.zoo_train, DESIGN.md §16).

The tentpole contract: genuine eq. 3 gradients of the scanned
stacked-layer model, computed parameter-sharded on the workers×model
mesh, must land bitwise-equal to the jitted single-device oracle — as
raw (U, n_chunks, D_c) gradients already in the compressor's layout, as
chained full rounds, and as the one-program multi-arm sweep (vs
``reference_sweep``, the oracle with the identical scan/map wrapping —
parity is per program structure). The in-process tier checks the scan
compilation itself (scanned ≡ unrolled layer stack, bitwise) and the
single-device host-mesh round; the 8-device subprocess test is the mesh
parity gate CI runs in the mesh-8 job."""
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_smoke_config
from repro.core.obcsaa import OBCSAAConfig
from repro.engine.zoo import ZooRound
from repro.engine.zoo_train import build_zoo_train_round
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PARITY_OB = dict(chunk=256, measure=64, topk=16, biht_iters=3,
                 recon_alg="iht", spmd_topk=True, packed=True,
                 bisect_iters=16)


def test_zoo_train_round_host_mesh():
    """Single-device host mesh: the real-gradient round moves the master,
    reports a finite loss/budget, and ``grads_in_layout`` matches the
    jitted oracle bitwise (same shard_map code path, unit federation)."""
    cfg = get_smoke_config("mnist-mlp")
    model = build_model(cfg)
    mesh = make_host_mesh()
    zr = build_zoo_train_round(model, mesh, OBCSAAConfig(**PARITY_OB))
    params = model.init(jax.random.PRNGKey(0))
    chunked = zr.chunk_params(params)
    master = zr.shard_params(chunked)
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    raw = {"x": 0.1 * jax.random.normal(kx, (zr.U, 2, 784), jnp.float32),
           "y": jax.random.randint(ky, (zr.U, 2), 0, 10, jnp.int32)}
    batch = zr.shard_batch(raw)

    g, losses = zr.grads_in_layout(master, batch)
    gr, lref = zr.reference_grads(chunked, raw)
    assert np.array_equal(np.asarray(g), np.asarray(gr))
    assert np.array_equal(np.asarray(losses), np.asarray(lref))

    s2, st = zr.round_train(master, batch, 0, jax.random.PRNGKey(1),
                            1e-4, 10.0, 0.1)
    m2 = np.asarray(s2.master)
    assert np.isfinite(float(st.loss))
    assert np.isfinite(m2).all()
    assert not np.array_equal(m2, np.asarray(master))
    for name, term in zip(st.budget._fields, st.budget):
        assert np.isfinite(np.asarray(term)).all(), name
    # the round consumed REAL gradients: params round-trip finitely
    p2 = zr.params_from_master(s2)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(p2))


@pytest.mark.parametrize("opt,kw", [("momentum", {"beta": 0.9}),
                                    ("adam", {})])
def test_zoo_train_stateful_round_host_mesh(opt, kw):
    """Momentum/adam moments live as sharded (n_chunks, D_c) carries and
    the per-worker EF residual as a (U, n_chunks, D_c) grads-layout carry
    (DESIGN.md §17): a 2-round chain on the host mesh matches the jitted
    oracle bitwise on EVERY carry leaf, and the residual is live (the
    1-bit uplink drops mass, so it must be non-zero after a round)."""
    cfg = get_smoke_config("mnist-mlp")
    model = build_model(cfg)
    mesh = make_host_mesh()
    zr = build_zoo_train_round(model, mesh, OBCSAAConfig(**PARITY_OB),
                               optimizer=opt, opt_kwargs=kw,
                               error_feedback=True)
    params = model.init(jax.random.PRNGKey(0))
    chunked = zr.chunk_params(params)
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    raw = {"x": 0.1 * jax.random.normal(kx, (zr.U, 2, 784), jnp.float32),
           "y": jax.random.randint(ky, (zr.U, 2), 0, 10, jnp.int32)}
    batch = zr.shard_batch(raw)
    key = jax.random.PRNGKey(1)

    s = zr.shard_state(zr.init_state(chunked))
    r = zr.init_state(chunked)
    for t in range(2):
        s, st = zr.round_train(s, batch, t, key, 1e-4, 10.0, 0.1)
        r, rst = zr.reference_round_train(r, raw, t, key, 1e-4, 10.0, 0.1)
        for i, (a, b) in enumerate(zip(jax.tree_util.tree_leaves(s),
                                       jax.tree_util.tree_leaves(r))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (t, i)
        assert np.isfinite(float(st.loss))
    assert float(np.abs(np.asarray(s.residual)).sum()) > 0.0


def test_zoo_train_state_validation_messages():
    """The carry is validated eagerly at the host entry points: a
    stateful round rejects bare masters, and the EF residual geometry
    errors name the expected (U, n_chunks, D_c) shape instead of dying
    as an opaque spec error inside shard_map (DESIGN.md §17)."""
    from repro.engine.zoo_train import ZooTrainState
    cfg = get_smoke_config("mnist-mlp")
    model = build_model(cfg)
    mesh = make_host_mesh()
    ob = OBCSAAConfig(**PARITY_OB)
    zr = build_zoo_train_round(model, mesh, ob, optimizer="adam",
                               error_feedback=True)
    chunked = zr.chunk_params(model.init(jax.random.PRNGKey(0)))
    want = (zr.U, zr.n_chunks, ob.chunk)

    # stateful round rejects a bare master array
    with pytest.raises(TypeError, match=r"optimizer='adam'.*stateful "
                                        r"moments/residuals"):
        zr.as_state(chunked)
    # EF on, residual missing
    bad = ZooTrainState(master=chunked, opt=zr.optimizer.init(chunked),
                        residual=None)
    with pytest.raises(ValueError, match=r"has no EF residual.*"
                                         r"\(U, n_chunks, D_c\)"):
        zr._check_state(bad)
    # EF on, residual with the wrong geometry
    bad = bad._replace(residual=jnp.zeros((1, 2, 3), jnp.float32))
    with pytest.raises(ValueError,
                       match=r"shape \(1, 2, 3\), expected"):
        zr._check_state(bad)
    # EF off, residual present
    zr2 = build_zoo_train_round(model, mesh, ob)
    full = ZooTrainState(master=chunked, opt=(),
                         residual=jnp.zeros(want, jnp.float32))
    with pytest.raises(ValueError, match=r"error_feedback=False.*WITH "
                                         r"an EF residual"):
        zr2._check_state(full)


def test_train_config_optimizer_and_ef_messages():
    """TrainConfig validates the optimizer name and the EF/aggregation
    coupling eagerly, naming the offending values (DESIGN.md §17)."""
    with pytest.raises(ValueError, match=r"optimizer='adamw' is not a "
                                         r"registered optimizer"):
        TrainConfig(optimizer="adamw")
    with pytest.raises(ValueError, match=r"error_feedback=True needs "
                                         r"aggregation='obcsaa'"):
        TrainConfig(aggregation="mean", error_feedback=True)
    TrainConfig(aggregation="obcsaa", error_feedback=True)   # fine


def test_scanned_vs_unrolled_layer_stack_bitwise():
    """The ``lax.scan`` over stacked per-layer params computes the SAME
    hidden states, bit for bit, as an unrolled per-layer chain of the
    identical CLOSED loop body (length-1 scans): the scan mixes nothing
    across layers. The closed body is load-bearing — an OPEN unrolled
    loop lets XLA fuse across layer boundaries and drifts final bf16
    ulps, the same per-structure parity contract as the round's decode
    blocks (DESIGN.md §16)."""
    from repro.configs.base import dtype_of
    from repro.dist.sharding import constrain
    from repro.models.layers import embed, rmsnorm
    from repro.models.transformer import (_apply_layer_full, layer_flags,
                                          lm_forward)

    cfg = get_smoke_config("gemma2-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                                cfg.vocab_size, jnp.int32)
    flags = layer_flags(cfg)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def body(carry, xs):
        # lm_forward's scan body (collect_cache off, no resolver)
        x, aux_acc = carry
        lp, fl = xs
        x = constrain(x, ("data", None, None))
        x, _, aux = _apply_layer_full(lp, x, cfg, fl, positions)
        return (x, aux_acc + aux), None

    @jax.jit
    def scanned(params):
        x, _, _ = lm_forward(params, cfg, tokens, remat=False,
                             return_hidden=True)
        return x

    @jax.jit
    def unrolled(params):
        x = embed(params["embedding"], tokens, dtype_of(cfg)) \
            * math.sqrt(cfg.d_model)
        carry = (x, jnp.zeros((), jnp.float32))
        for i in range(cfg.num_layers):
            xs = (jax.tree_util.tree_map(lambda a: a[i:i + 1],
                                         params["layers"]),
                  jax.tree_util.tree_map(lambda a: a[i:i + 1], flags))
            carry, _ = jax.lax.scan(body, carry, xs)
        return rmsnorm(carry[0], params["final_norm"], cfg.norm_eps)

    assert np.array_equal(np.asarray(scanned(params)),
                          np.asarray(unrolled(params)))


def test_train_config_packed_geometry_message():
    """cs_packed needs S_c % 32 == 0, validated EAGERLY at config
    construction with the offending field named (not as an opaque
    reshape error deep in the kernels)."""
    with pytest.raises(ValueError, match=r"cs_measure=100"):
        TrainConfig(cs_packed=True, cs_measure=100)
    TrainConfig(cs_packed=True, cs_measure=96)     # multiple of 32: fine
    TrainConfig(cs_packed=False, cs_measure=100)   # unpacked: no 32-rule


def test_zoo_round_n_chunks_geometry_message():
    """An explicit n_chunks that cannot cover D (or break mesh
    granularity) fails at construction, naming the offending value."""
    mesh = make_host_mesh()
    with pytest.raises(ValueError, match=r"n_chunks=3"):
        ZooRound(OBCSAAConfig(**PARITY_OB), 16000, mesh, n_chunks=3)
    ZooRound(OBCSAAConfig(**PARITY_OB), 16000, mesh, n_chunks=64)


SCRIPT_TRAIN_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.core.obcsaa import OBCSAAConfig
    from repro.engine import parity
    from repro.engine.zoo_train import build_zoo_train_round
    from repro.models.registry import build_model
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((4, 2), ("data", "model"))
    ob = OBCSAAConfig(chunk=256, measure=64, topk=16, biht_iters=3,
                      recon_alg="iht", spmd_topk=True, packed=True,
                      bisect_iters=16)
    cfg = get_smoke_config("gemma2-2b")
    model = build_model(cfg)
    zr = build_zoo_train_round(model, mesh, ob)
    assert (zr.U, zr.n_model) == (4, 2)
    params = model.init(jax.random.PRNGKey(0))
    chunked = zr.chunk_params(params)
    master = zr.shard_params(chunked)
    key = jax.random.PRNGKey(7)
    tok = jax.random.randint(jax.random.PRNGKey(1), (zr.U, 2, 32), 0,
                             cfg.vocab_size, jnp.int32)
    raw = {"tokens": tok, "targets": jnp.roll(tok, -1, axis=-1)}
    batch = zr.shard_batch(raw)

    # real gradients, already in the (U, n_chunks, D_c) compressor layout
    g, losses = zr.grads_in_layout(master, batch)
    gr, lref = zr.reference_grads(chunked, raw)
    assert np.array_equal(np.asarray(g), np.asarray(gr)), "grads"
    assert np.array_equal(np.asarray(losses), np.asarray(lref)), "losses"

    # 3 chained real-gradient rounds stay within the parity tolerances
    # of the jitted oracle (engine/parity.py gives each its reason)
    m, rc = master, chunked
    for t in range(3):
        m, st = zr.round_train(m, batch, t, key, 1e-4, 10.0, 0.05)
        rc, rst = zr.reference_round_train(rc, raw, t, key, 1e-4, 10.0,
                                           0.05)
        parity.assert_reports(parity.compare_states(m, rc, master0=chunked,
                                                    tag=f"round {t}"))
        # loss is telemetry, not round state: the mesh computes it as
        # psum/U, the oracle as mean-over-lax.map — different reduction
        # structures, so close-not-bitwise by contract
        np.testing.assert_allclose(float(st.loss), float(rst.loss),
                                   rtol=parity.LOSS_RTOL)
        assert np.isfinite(float(st.loss))
    assert all(np.isfinite(np.asarray(x)).all() for x in st.budget)

    # one-program multi-arm sweep == the oracle with the SAME scan/map
    # wrapping (parity is per program structure, DESIGN.md §16)
    A = 2
    arms = {"noise_var": jnp.array([1e-4, 1e-3], jnp.float32),
            "p_max": jnp.full((A,), 10.0, jnp.float32),
            "lr": jnp.array([0.05, 0.02], jnp.float32)}
    stacked = jnp.broadcast_to(chunked, (A,) + chunked.shape)
    ms = zr.shard_masters(stacked)
    m2, _ = zr.run_sweep(ms, batch, arms, 2, key=key)
    r2, _ = zr.reference_sweep(stacked, raw, arms, 2, key=key)
    parity.assert_reports(parity.compare_states(m2, r2, master0=stacked,
                                                tag="sweep"))
    print("OK")
""")


SCRIPT_OPT_STATE_PARITY = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.core.obcsaa import OBCSAAConfig
    from repro.engine import parity
    from repro.engine.zoo_train import build_zoo_train_round
    from repro.models.registry import build_model
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((4, 2), ("data", "model"))
    ob = OBCSAAConfig(chunk=256, measure=64, topk=16, biht_iters=3,
                      recon_alg="iht", spmd_topk=True, packed=True,
                      bisect_iters=16)
    cfg = get_smoke_config("gemma2-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 32), 0,
                             cfg.vocab_size, jnp.int32)
    raw = {"tokens": tok, "targets": jnp.roll(tok, -1, axis=-1)}

    def leaves_equal(a, b, tag):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb), tag
        for i, (x, y) in enumerate(zip(la, lb)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (tag, i)

    # sharded optimizer moments + per-worker EF residuals: a >=3-round
    # chain on the 4x2 mesh stays within the parity tolerances of the
    # jitted oracle on EVERY carry leaf (master, moments, residual; adam's
    # integer step counter exactly)
    for name, kw in (("momentum", dict(beta=0.9)), ("adam", {})):
        zr = build_zoo_train_round(model, mesh, ob, optimizer=name,
                                   opt_kwargs=kw, error_feedback=True)
        chunked = zr.chunk_params(params)
        batch = zr.shard_batch(raw)
        s = zr.shard_state(zr.init_state(chunked))
        r = zr.init_state(chunked)
        for t in range(3):
            s, st = zr.round_train(s, batch, t, key, 1e-4, 10.0, 0.05)
            r, rst = zr.reference_round_train(r, raw, t, key, 1e-4,
                                              10.0, 0.05)
            parity.assert_reports(parity.compare_states(
                s, r, master0=chunked, tag=f"{name} round {t}"))
            assert np.isfinite(float(st.loss)), (name, t)
        assert float(np.abs(np.asarray(s.residual)).sum()) > 0, name
        print(name + " chain parity OK", flush=True)

    # mid-chain checkpoint resume with non-trivial adam moments + EF
    # residuals: 4 rounds == 2 rounds -> save_state -> restore_state ->
    # 2 rounds, bit for bit on all carry leaves (zr is the adam round)
    s0 = zr.shard_state(zr.init_state(chunked))
    full, half = s0, s0
    for t in range(4):
        full, _ = zr.round_train(full, batch, t, key, 1e-4, 10.0, 0.05)
    for t in range(2):
        half, _ = zr.round_train(half, batch, t, key, 1e-4, 10.0, 0.05)
    with tempfile.TemporaryDirectory() as td:
        zr.save_state(td, 2, half, t_next=2)
        res, t0 = zr.restore_state(td)
        assert t0 == 2, t0
        for t in range(t0, 4):
            res, _ = zr.round_train(res, batch, t, key, 1e-4, 10.0, 0.05)
    leaves_equal(full, res, "chain resume")
    print("chain resume OK", flush=True)

    # mid-SWEEP resume: the one-program arms x rounds scan restarted
    # from a restored arm-stacked carry at t0=2 lands bitwise on the
    # uninterrupted 4-round sweep
    A = 2
    arms = {"noise_var": jnp.array([1e-4, 1e-3], jnp.float32),
            "p_max": jnp.full((A,), 10.0, jnp.float32),
            "lr": jnp.array([0.05, 0.02], jnp.float32)}
    states0 = zr.shard_state(zr.init_sweep_state(
        jnp.broadcast_to(chunked, (A,) + chunked.shape)), arms=A)
    full, _ = zr.run_sweep(states0, batch, arms, 4, key=key)
    half, _ = zr.run_sweep(states0, batch, arms, 2, key=key)
    with tempfile.TemporaryDirectory() as td:
        zr.save_state(td, 2, half, t_next=2)
        states2, t0 = zr.restore_state(td, arms=A)
        assert t0 == 2, t0
        resumed, _ = zr.run_sweep(states2, batch, arms, 2, key=key,
                                  t0=t0)
    leaves_equal(full, resumed, "sweep resume")
    print("OK")
""")


@pytest.mark.slow
def test_zoo_train_opt_state_ef_parity_8dev():
    """Tentpole gate (DESIGN.md §17): momentum/adam moments as sharded
    (n_chunks, D_c) carries and per-worker EF residuals as the
    (U, n_chunks, D_c) grads-layout carry stay within the parity
    tolerances (engine/parity.py) of the jitted single-device oracle over
    3-round chains on the 4x2 mesh, and a checkpoint saved mid-chain and
    mid-sweep (moments + residuals + t_next) resumes bit for bit on the
    same mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT_OPT_STATE_PARITY],
                       env=env, capture_output=True, text=True,
                       timeout=560)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


@pytest.mark.slow
def test_zoo_train_sharded_bitwise_parity_8dev():
    """Real backward passes on the 4 workers x 2 model shards mesh vs the
    single-device oracle: raw in-layout gradients bit for bit (replicated
    compute, no cross-device float reduction), chained rounds and the
    multi-arm sweep within the parity tolerances (DESIGN.md §16)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT_TRAIN_PARITY],
                       env=env, capture_output=True, text=True,
                       timeout=560)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout
