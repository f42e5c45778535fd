"""repro.dist sharding subsystem: best_spec / infer_param_sharding
properties on 1-device and 8-device CPU meshes, constrain's no-op
guarantees, and the worker-axis MAC equivalence — ``shardmap_compress``'s
psum over the worker axes must reproduce ``simulate_round``'s stacked
einsum superposition bit-for-bit (the over-the-air sum of ±w symbols is
exact integer arithmetic in float32).

Multi-device parts run in a subprocess so the 8-device XLA flag never
leaks into this (1-device) test process — same pattern as
test_distributed.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import collectives
from repro.dist.sharding import best_spec, constrain, infer_param_sharding
from repro.launch.mesh import auto_mesh
from repro.models.mlp_mnist import init_mlp_mnist

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --- 1-device mesh ---------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh1():
    return auto_mesh((1, 1), ("data", "model"))


def test_best_spec_signature_and_hint_priority(mesh1):
    # exact call shape used by launch/steps.py:batch_pspecs and dryrun.py
    spec = best_spec((8, 16), ["data", None], mesh1)
    assert isinstance(spec, P)
    assert spec == P("data", None)
    # first divisible candidate in the hint list wins
    assert best_spec((8,), [["model", "data"]], mesh1) == P("model")


def test_best_spec_replication_fallback(mesh1):
    # no hint, or hint None -> replicated dims
    assert best_spec((4, 4), [None, None], mesh1) == P(None, None)
    # unknown axis names are skipped, not errors
    assert best_spec((4,), ["expert"], mesh1) == P(None)


def test_infer_param_sharding_1device(mesh1):
    params = init_mlp_mnist(jax.random.PRNGKey(0))
    sh = infer_param_sharding(params, mesh1)
    leaves = jax.tree_util.tree_leaves(sh)
    assert all(isinstance(s, NamedSharding) for s in leaves)
    # size-1 model axis shards trivially; placing params must round-trip
    placed = jax.device_put(params, sh)
    np.testing.assert_array_equal(np.asarray(placed["w1"]),
                                  np.asarray(params["w1"]))


def test_constrain_noop_without_mesh():
    x = jnp.arange(12.0).reshape(3, 4)
    y = constrain(x, ("data", "model"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_constrain_inside_jit_under_mesh(mesh1):
    x = jnp.arange(16.0).reshape(4, 4)

    @jax.jit
    def f(x):
        return constrain(x, ("data", None)) * 2

    with jax.set_mesh(mesh1):
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x) * 2)


def test_collectives_no_axes_identity():
    x = jnp.arange(4.0)
    np.testing.assert_array_equal(np.asarray(collectives.psum(x, ())),
                                  np.asarray(x))
    assert int(collectives.axis_index(())) == 0
    assert collectives.axis_size(()) == 1
    assert collectives.norm_axes("data") == ("data",)
    assert collectives.norm_axes(None) == ()


# --- 8-device mesh (subprocess) ---------------------------------------------------

PROP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import best_spec, infer_param_sharding
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((4, 2), ("data", "model"))

    # divisibility: dim 6 is not divisible by data=4 -> next candidate/repl
    assert best_spec((6, 8), [["data", "model"], None], mesh) == P("model", None)
    assert best_spec((5, 7), [["data", "model"], None], mesh) == P(None, None)
    # hint priority: both divide, first named wins
    assert best_spec((8, 8), [["model", "data"], None], mesh) == P("model", None)
    # an axis is used at most once across dims
    assert best_spec((8, 8), ["data", "data"], mesh) == P("data", None)
    # "data" hint widens to ("pod", "data") on the 3-axis production mesh
    from repro.launch.mesh import worker_axes
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 2, "model": 2}
    assert best_spec((8, 4), ["data", None], FakeMesh()) == P(("pod", "data"),
                                                             None)
    # 3-axis worker-axes definition agrees
    assert worker_axes(FakeMesh()) == ("pod", "data")

    # infer_param_sharding: MNIST-MLP pytree (model=2)
    from repro.models.mlp_mnist import init_mlp_mnist
    params = init_mlp_mnist(jax.random.PRNGKey(0))
    sh = infer_param_sharding(params, mesh)
    assert sh["w1"].spec == P("model", None)     # largest dim 784 % 2 == 0
    assert sh["b2"].spec == P("model")           # 10 % 2 == 0
    placed = jax.device_put(params, sh)
    for k in params:
        np.testing.assert_array_equal(np.asarray(placed[k]),
                                      np.asarray(params[k]))

    # transformer smoke-config param AND optimizer-state pytrees place
    # without error and keep worker axes replicated
    from repro.configs import get_smoke_config
    from repro.models.registry import build_model
    from repro.optim.optimizers import adam
    model = build_model(get_smoke_config("gemma2-2b"))
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    psh = infer_param_sharding(pshapes, mesh)
    oshapes = jax.eval_shape(adam().init, pshapes)
    osh = infer_param_sharding(oshapes, mesh)
    for tree, shtree in ((pshapes, psh), (oshapes, osh)):
        for leaf, s in zip(jax.tree_util.tree_leaves(tree),
                           jax.tree_util.tree_leaves(shtree)):
            assert isinstance(s, NamedSharding)
            assert "data" not in jax.tree_util.tree_leaves(
                [list(p) if isinstance(p, tuple) else [p] for p in s.spec])
            for dim, p in zip(leaf.shape, s.spec):
                if p is not None:
                    assert dim % mesh.shape["model"] == 0
    print("PROPS_OK")
""")


MAC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.obcsaa import (OBCSAAConfig, compress_chunks,
                                   shardmap_aggregate, shardmap_compress,
                                   simulate_round)
    from repro.launch.mesh import make_host_mesh, num_workers, worker_axes

    mesh = make_host_mesh()
    waxes = worker_axes(mesh)
    U = num_workers(mesh)
    assert U == 8
    D = 2048
    cfg = OBCSAAConfig(chunk=512, measure=128, topk=24, biht_iters=8)
    grads = jax.random.normal(jax.random.PRNGKey(3), (U, D))
    beta = jnp.ones((U,)); bt = jnp.float32(1.0)
    nkey = jax.random.PRNGKey(11)

    # reference MAC: the stacked einsum superposition from simulate_round
    phi = cfg.phi()
    signs, mags = jax.vmap(lambda g: compress_chunks(cfg, g, phi))(grads)
    w = (jnp.ones((U,)) * beta * bt).astype(signs.dtype)
    y_ref = jnp.einsum("u,ucs->cs", w, signs)             # eq. (12), pre-noise
    ksum_ref = jnp.sum(jnp.ones((U,)) * beta)

    def per_worker(g, beta_all, bt):
        widx = jax.lax.axis_index(waxes)
        return shardmap_compress(cfg, g[0], waxes, k_weight=jnp.float32(1.0),
                                 beta_i=beta_all[widx], b_t=bt)

    f = jax.shard_map(per_worker, mesh=mesh, axis_names=set(waxes),
                      in_specs=(P("data"), P(), P()), out_specs=(P(), P(), P()),
                      check_vma=False)
    with jax.set_mesh(mesh):
        y, ksum, mag_sum = jax.jit(f)(grads, beta, bt)

    # the over-the-air sum of +-1 symbols is exact integer float arithmetic:
    # psum must match the einsum bit for bit
    assert np.array_equal(np.asarray(y), np.asarray(y_ref)), (
        np.abs(np.asarray(y) - np.asarray(y_ref)).max())
    assert float(ksum) == float(ksum_ref)
    mag_ref = jnp.einsum("u,uc->c", (jnp.ones((U,)) * beta).astype(mags.dtype),
                         mags)
    np.testing.assert_allclose(np.asarray(mag_sum), np.asarray(mag_ref),
                               rtol=1e-6)

    # end-to-end: distributed aggregate tracks the centralized simulation
    # for the same PRNG channel draw
    ghat_sim, _ = simulate_round(cfg, grads, jnp.ones((U,)), beta, bt,
                                 jnp.ones((U,)), nkey)
    def agg(g, beta_all, bt, nkey):
        widx = jax.lax.axis_index(waxes)
        return shardmap_aggregate(cfg, g[0], waxes, k_weight=jnp.float32(1.0),
                                  beta_i=beta_all[widx], b_t=bt, n_workers=U,
                                  noise_key=nkey)
    fa = jax.shard_map(agg, mesh=mesh, axis_names=set(waxes),
                       in_specs=(P("data"), P(), P(), P()), out_specs=P(),
                       check_vma=False)
    with jax.set_mesh(mesh):
        ghat = jax.jit(fa)(grads, beta, bt, nkey)
    np.testing.assert_allclose(np.asarray(ghat[:D]), np.asarray(ghat_sim),
                               rtol=1e-4, atol=1e-6)
    print("MAC_OK")
""")


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560)


@pytest.mark.slow
def test_sharding_properties_8device():
    r = _run(PROP_SCRIPT)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "PROPS_OK" in r.stdout


@pytest.mark.slow
def test_worker_axis_mac_matches_simulation_bitwise():
    r = _run(MAC_SCRIPT)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "MAC_OK" in r.stdout
