"""Distributed OBCSAA path: the shard_map (partial-manual) aggregation must
equal the centralized simulation, and the mean/obcsaa train steps must lower
and run on a multi-device host mesh. Runs in a subprocess so the 8-device
XLA flag never leaks into other tests."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.obcsaa import OBCSAAConfig, simulate_round, shardmap_aggregate
    from repro.core import channel as chan
    from repro.launch.mesh import auto_mesh

    U, D = 4, 2048
    mesh = auto_mesh((4, 2), ("data", "model"))
    cfg = OBCSAAConfig(chunk=1024, measure=256, topk=32, biht_iters=10)
    key = jax.random.PRNGKey(0)
    grads = jax.random.normal(key, (U, D))
    kw = jnp.ones(()); beta = jnp.ones((U,)); bt = jnp.float32(1.0)
    nkey = jax.random.PRNGKey(7)

    # centralized reference (workers equally weighted, unit channels)
    ghat_sim, _ = simulate_round(cfg, grads, jnp.ones((U,)), beta, bt,
                                 jnp.ones((U,)), nkey)

    # distributed: each data shard holds one worker's gradient
    def per_worker(g, beta_all, bt, nkey):
        widx = jax.lax.axis_index(("data",))
        ghat = shardmap_aggregate(cfg, g[0], ("data",), k_weight=jnp.float32(1.0),
                                  beta_i=beta_all[widx], b_t=bt,
                                  n_workers=U, noise_key=nkey)
        return ghat

    f = jax.shard_map(per_worker, mesh=mesh, axis_names={"data"},
                      in_specs=(P("data"), P(), P(), P()), out_specs=P(),
                      check_vma=False)
    with jax.set_mesh(mesh):
        ghat_dist = jax.jit(f)(grads, beta, bt, nkey)
    err = float(jnp.max(jnp.abs(ghat_dist[:D] - ghat_sim)))
    rel = err / (float(jnp.max(jnp.abs(ghat_sim))) + 1e-12)
    print("MAXERR", err, "REL", rel)
    assert rel < 5e-2, (err, rel)

    # train steps lower + run on the host mesh (both aggregations)
    from repro.configs import TrainConfig, get_smoke_config
    from repro.launch import steps as steps_lib
    from repro.models.registry import build_model
    from repro.data import token_stream

    cfg2 = get_smoke_config("gemma2-2b")
    model = build_model(cfg2)
    for agg in ("mean", "obcsaa"):
        tcfg = TrainConfig(aggregation=agg, cs_chunk=512, cs_measure=128,
                           cs_topk=32, biht_iters=3, learning_rate=0.01)
        with jax.set_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            opt = steps_lib.make_optimizer(tcfg)
            ostate = opt.init(params)
            step = jax.jit(steps_lib.make_train_step(model, tcfg, mesh))
            toks, tgts = token_stream(8, 32, cfg2.vocab_size)
            batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgts)}
            losses = []
            for t in range(3):
                ctx = steps_lib.default_round_ctx(mesh, seed=t)
                params, ostate, m = step(params, ostate, batch, ctx)
                losses.append(float(m["loss"]))
            print("AGG", agg, losses)
            assert losses[-1] < losses[0], (agg, losses)
    print("OK")
""")


@pytest.mark.slow
def test_distributed_equivalence_and_train_steps():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


SCRIPT_PACKED_MAC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import psum_bits_mac
    from repro.kernels.sign import pack_signs, unpack_signs
    from repro.launch.mesh import auto_mesh

    # 8 workers, one per device: the int32 packed-word MAC psum must equal
    # the f32 einsum superposition of the unpacked +-1 symbols bit for bit
    # (uniform power-of-two scale K*b_t => every partial sum is exact).
    U, n, S = 8, 3, 256
    mesh = auto_mesh((8,), ("data",))
    key = jax.random.PRNGKey(0)
    proj = jax.random.normal(key, (U, n, S))
    packed = pack_signs(proj)                       # (U, n, S//32) uint32
    symbols = unpack_signs(packed)                  # (U, n, S) +-1 f32
    beta = (jax.random.uniform(jax.random.PRNGKey(1), (U,)) > 0.3)
    beta = beta.astype(jnp.float32)
    scale = jnp.float32(0.5)                        # K*b_t, power of two

    y_ref = jnp.einsum("u,uns->ns", beta * scale, symbols)

    def per_worker(pk, beta_all):
        widx = jax.lax.axis_index("data")
        s_int = psum_bits_mac(pk[0], ("data",), beta_i=beta_all[widx])
        return s_int.astype(jnp.float32) * scale

    f = jax.shard_map(per_worker, mesh=mesh, axis_names={"data"},
                      in_specs=(P("data"), P()), out_specs=P(),
                      check_vma=False)
    with jax.set_mesh(mesh):
        y_mac = jax.jit(f)(packed, beta)
    assert y_mac.shape == y_ref.shape, (y_mac.shape, y_ref.shape)
    assert bool(jnp.all(y_mac == y_ref)), "packed MAC psum != f32 einsum"
    print("OK")
""")


SCRIPT_LARGE_D_UPLINK = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.obcsaa import OBCSAAConfig, compress_chunks, shardmap_compress
    from repro.launch.mesh import auto_mesh

    # zoo-scale packed uplink (DESIGN.md §14): full shardmap_compress ->
    # psum_bits_mac pipeline at D = 4.19M on the 8-worker mesh must equal
    # the single-device f32 symbol reference bit for bit. K*b_t = 0.5 is a
    # power of two, so every scaled int32 MAC value is exactly
    # representable in f32.
    U, CH, S = 8, 8192, 256
    D = 512 * CH
    cfg = OBCSAAConfig(chunk=CH, measure=S, topk=64, packed=True,
                       spmd_topk=True, bisect_iters=20)
    mesh = auto_mesh((8,), ("data",))
    grads = jnp.stack([
        0.1 * jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(0), u),
                                (D,), jnp.float32) for u in range(U)])
    beta = (jax.random.uniform(jax.random.PRNGKey(1), (U,)) > 0.25)
    beta = beta.astype(jnp.float32)
    bt = jnp.float32(0.5)

    def per_worker(g, beta_all):
        widx = jax.lax.axis_index("data")
        return shardmap_compress(cfg, g[0], ("data",),
                                 k_weight=jnp.float32(1.0),
                                 beta_i=beta_all[widx], b_t=bt)

    f = jax.shard_map(per_worker, mesh=mesh, axis_names={"data"},
                      in_specs=(P("data"), P()), out_specs=(P(), P(), P()),
                      check_vma=False)
    with jax.set_mesh(mesh):
        y, ksum, mag_sum = jax.jit(f)(grads, beta)

    # single-device f32 reference: same compression, f32 +-1 symbols,
    # plain weighted sums over the worker axis
    ref_cfg = dataclasses.replace(cfg, packed=False)

    @jax.jit
    def reference(grads, beta):
        signs, mags = jax.vmap(
            lambda g: compress_chunks(ref_cfg, g, None))(grads)
        y = jnp.einsum("u,ucs->cs", beta * bt, signs)
        return y, jnp.sum(beta), jnp.einsum("u,uc->c", beta, mags)

    y_ref, ksum_ref, mag_ref = reference(grads, beta)
    assert y.shape == (D // CH, S)
    assert np.array_equal(np.asarray(y), np.asarray(y_ref)), "y"
    assert np.array_equal(np.asarray(ksum), np.asarray(ksum_ref)), "ksum"
    assert np.array_equal(np.asarray(mag_sum), np.asarray(mag_ref)), "mags"
    print("NNZROWS", int(jnp.sum(jnp.any(y != 0, axis=1))))
    print("OK")
""")


@pytest.mark.slow
def test_packed_uplink_large_d_bitwise_vs_single_device():
    """Satellite of the zoo PR: the packed compress+MAC uplink at D=4.19M
    (the ≥1B bench wire path, scaled to CI) on the 8-device mesh is
    bitwise equal to the single-device f32 symbol reference."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT_LARGE_D_UPLINK],
                       env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


@pytest.mark.slow
def test_packed_mac_psum_matches_einsum_on_mesh():
    """Worker-axis popcount-style MAC (DESIGN.md §13): int32 psum of
    packed sign words == the f32 symbol superposition, bitwise."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT_PACKED_MAC], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout
