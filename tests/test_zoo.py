"""Model-zoo regression tier (DESIGN.md §14).

Every architecture in ``repro.configs`` must survive one sharded FL round:
real per-worker gradients of the real smoke model, chunked and fed through
the shard_map'd compress → packed MAC → decode → update pipeline of
``repro.engine.zoo``, with a finite Theorem-1 ErrorBudget. The in-process
tier runs on the single-device host mesh (same shard_map code path, unit
worker federation); the 8-device subprocess test checks the sharded round
is BITWISE equal to the single-device reference oracle — surrogate-
gradient, real-gradient, and 3-round-chain variants."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_MODULES, InputShape, get_smoke_config
from repro.core.obcsaa import OBCSAAConfig
from repro.core.sparsify import flatten_pytree
from repro.engine.zoo import build_zoo_round
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ZOO_OB = dict(chunk=256, measure=64, topk=16, biht_iters=3,
              recon_alg="iht", spmd_topk=True, packed=True,
              bisect_iters=16)


def _make_batch(model, B=2, S=24, seed=0):
    """Materialise small concrete inputs from the model's input_specs."""
    cfg = model.cfg
    if cfg.family == "vlm":
        S = cfg.num_image_tokens + 8
    specs = model.input_specs(InputShape("zoo_smoke", S, B, "train"))
    key = jax.random.PRNGKey(seed)
    batch = {}
    for name in sorted(specs):
        sd = specs[name]
        key, k = jax.random.split(key)
        if jnp.issubdtype(sd.dtype, jnp.integer):
            batch[name] = jax.random.randint(k, sd.shape, 0,
                                             cfg.vocab_size, sd.dtype)
        else:
            batch[name] = (0.05 * jax.random.normal(k, sd.shape)
                           ).astype(sd.dtype)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCH_MODULES))
def test_zoo_smoke_round(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _make_batch(model)
    grads = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    gflat, _ = flatten_pytree(grads)
    D = int(gflat.shape[0])

    mesh = make_host_mesh()
    zr = build_zoo_round(OBCSAAConfig(**ZOO_OB), D, mesh)
    assert zr.U == 1 and zr.n_chunks * zr.ob.chunk >= D
    psh = zr.shard_params(zr.chunk_params(params))
    gsh = zr.chunk_worker_grads(gflat[None])
    p2, st = zr.round_from_grads(psh, gsh, 0, jax.random.PRNGKey(1),
                                 1e-4, 10.0, 0.1)

    p2 = np.asarray(p2)
    assert p2.shape == (zr.n_chunks, zr.ob.chunk)
    assert np.isfinite(p2).all(), arch
    assert not np.array_equal(p2, np.asarray(psh)), \
        f"{arch}: round left parameters untouched"
    assert int(st.n_scheduled) == 1
    assert np.isfinite(float(st.ghat_norm)) and float(st.ghat_norm) > 0
    assert st.budget is not None
    for name, term in zip(st.budget._fields, st.budget):
        assert np.isfinite(np.asarray(term)).all(), (arch, name)
    # the updated flat vector round-trips out of the chunk layout
    flat2 = zr.unchunk(p2)
    assert flat2.shape == (D,) and np.isfinite(flat2).all()


SCRIPT_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.obcsaa import OBCSAAConfig
    from repro.engine import parity
    from repro.engine.zoo import build_zoo_round
    from repro.launch.mesh import auto_mesh

    def close(got, want, tag):
        parity.assert_reports([parity.compare_leaf(
            tag, got, want, parity.TOLERANCES["master"], base=chunked)])

    mesh = auto_mesh((4, 2), ("data", "model"))
    ob = OBCSAAConfig(chunk=256, measure=64, topk=16, biht_iters=3,
                      recon_alg="iht", spmd_topk=True, packed=True,
                      bisect_iters=16)
    D = 16000                      # pads to 64 chunks, 8 per device
    zr = build_zoo_round(ob, D, mesh)
    assert (zr.U, zr.n_model, zr.n_local) == (4, 2, 8)
    key = jax.random.PRNGKey(7)
    flat = jax.random.normal(jax.random.PRNGKey(1), (D,), jnp.float32)
    chunked = zr.chunk_params(flat)
    psh = zr.shard_params(chunked)

    # surrogate-gradient round (the >=1B bench path)
    p2, st = zr.round_gen(psh, 0, key, 1e-4, 10.0, 0.1)
    r2, rst = zr.reference_round(chunked, 0, key, 1e-4, 10.0, 0.1)
    close(p2, r2, "gen round")
    np.testing.assert_allclose(np.asarray(st.ghat_norm),
                               np.asarray(rst.ghat_norm), rtol=1e-5)
    assert all(np.isfinite(np.asarray(x)).all() for x in st.budget)

    # real-gradient round (the zoo smoke-tier path), U = 4 workers
    grads = jax.random.normal(jax.random.PRNGKey(2), (zr.U, D), jnp.float32)
    gsh = zr.chunk_worker_grads(grads)
    p3, _ = zr.round_from_grads(psh, gsh, 1, key, 1e-4, 10.0, 0.1)
    gref = jnp.pad(grads, ((0, 0), (0, zr.D_pad - D))).reshape(
        zr.U, zr.n_chunks, ob.chunk)
    r3, _ = zr.reference_round(chunked, 1, key, 1e-4, 10.0, 0.1, grads=gref)
    close(p3, r3, "grads round")

    # 3 chained rounds stay on-sharding and within tolerance
    p4, stats = zr.run_rounds(psh, 3, key=key, noise_var=1e-4, p_max=10.0,
                              lr=0.1)
    rc = chunked
    for t in range(3):
        rc, _ = zr.reference_round(rc, t, key, 1e-4, 10.0, 0.1)
    close(p4, rc, "3-round chain")
    assert len(stats) == 3
    print("OK")
""")


@pytest.mark.slow
def test_zoo_sharded_round_bitwise_parity_8dev():
    """shard_map'd zoo round on a 4 workers x 2 model shards mesh vs the
    single-device reference (packed int32 uplink + shared full-noise
    draw; DESIGN.md §14), within the parity tolerances of
    engine/parity.py."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT_PARITY], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout
