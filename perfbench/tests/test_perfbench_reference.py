"""The plain references against the program at a CPU size, and their
pieces on cases worked out by hand."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import codec, compare, mamba2, mnist

DIMS = dict(arch="mamba2-2.7b", d_model=64, n_layers=2, vocab=128,
            d_state=16, head_dim=16, expand=2, n_groups=2, chunk_size=16,
            conv_width=4, norm_eps=1e-6)


def program_model(dtype="float32"):
    from repro.configs import SSMConfig, get_config
    from repro.models.registry import build_model
    m = DIMS
    cfg = dataclasses.replace(
        get_config(m["arch"]), num_layers=m["n_layers"],
        d_model=m["d_model"], vocab_size=m["vocab"], norm_eps=m["norm_eps"],
        dtype=dtype,
        ssm=SSMConfig(d_state=m["d_state"], head_dim=m["head_dim"],
                      expand=m["expand"], n_groups=m["n_groups"],
                      chunk_size=m["chunk_size"],
                      conv_width=m["conv_width"]))
    return build_model(cfg)


def test_mamba2_reference_matches_the_program_in_float32():
    dims = mamba2.Dims.from_config(DIMS)
    model = program_model()
    params = mamba2.init_params(jax.random.PRNGKey(3), dims)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure(params)
    assert [a.shape for a in jax.tree_util.tree_leaves(shapes)] == \
        [b.shape for b in jax.tree_util.tree_leaves(params)]
    tok = jax.random.randint(jax.random.PRNGKey(4), (1, 65), 0, 128)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch)[0])(params)
        lr, gr = jax.value_and_grad(mamba2.lm_loss)(
            params, tok[:, :-1], tok[:, 1:], dims)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b)) + 1e-12


def test_layout_is_the_rounds_chunk_layout():
    from repro.configs import TrainConfig
    from repro.launch import steps
    from repro.launch.mesh import make_zoo_mesh
    dims = mamba2.Dims.from_config(DIMS)
    tcfg = TrainConfig(aggregation="obcsaa", optimizer="adam",
                       error_feedback=True, cs_chunk=1024, cs_measure=32,
                       cs_topk=8, cs_packed=True)
    zr = steps.make_zoo_train_round(program_model(), tcfg,
                                    make_zoo_mesh(1, 1))
    params = mamba2.init_params(jax.random.PRNGKey(5), dims)
    lay = mamba2.Layout(params, 1024, 64)
    assert lay.n_chunks == zr.n_chunks and lay.D == zr.D
    np.testing.assert_array_equal(np.asarray(lay.to_master(params)),
                                  np.asarray(zr.chunk_params(params)))
    norms = np.asarray(lay.leaf_norms(lay.to_master(params)))
    want = [np.linalg.norm(np.asarray(x))
            for x in jax.tree_util.tree_leaves(params)]
    np.testing.assert_allclose(norms, want, rtol=1e-6)


def test_codec_recovers_a_sparse_direction_and_its_norm():
    phi = codec.make_phi(42, 256, 512)
    x = jnp.zeros((2, 512)).at[0, jnp.array([3, 70, 400])].set(
        jnp.array([1.0, -2.0, 0.5])).at[1, 9].set(3.0)
    signs, mags = codec.uplink(codec.topk(x, 4), phi)
    np.testing.assert_allclose(np.asarray(mags),
                               np.linalg.norm(np.asarray(x), axis=1),
                               rtol=1e-6)
    y, mbar = codec.aggregate(signs[None], mags[None], jnp.ones(1), 2.0,
                              jnp.zeros(signs.shape))
    with jax.default_matmul_precision("highest"):
        xhat = codec.decode(y, mbar, phi, 8, 20, 1.0)
    cos = jnp.sum(xhat * x, axis=1) / (jnp.linalg.norm(xhat, axis=1)
                                       * jnp.linalg.norm(x, axis=1))
    assert float(jnp.min(cos)) > 0.95
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(xhat, axis=1)),
                               np.asarray(mags), rtol=1e-5)


def test_topk_keeps_the_largest_magnitudes():
    x = jnp.array([[0.1, -3.0, 2.0, -0.5, 1.0]])
    np.testing.assert_array_equal(np.asarray(codec.topk(x, 2)),
                                  [[0.0, -3.0, 2.0, 0.0, 0.0]])
    np.testing.assert_array_equal(np.asarray(codec.sign(
        jnp.array([-1.0, 0.0, 2.0]))), [-1.0, 1.0, 1.0])


def test_schedule_is_exact_over_every_worker_set():
    an = mnist.Analysis(rho1=1.0, G=10.0, delta=0.2)
    h = np.array([0.3, 1.7, 0.9, 1.2])
    beta, b_t = mnist.schedule(h, np.full(4, 3000.0), 10.0, 1e-4, 50890,
                               1024, 80, an)
    # each scheduled worker costs (1 + delta)(D - kappa)/D G^2 ~ 120, far
    # more than leaving one out (rho1 / 4): one worker, the best channel
    np.testing.assert_array_equal(beta, [0, 1, 0, 0])
    assert b_t == pytest.approx(1.7 * np.sqrt(10.0) / 3000.0)
    # with G small the sparsification penalty is light and all transmit
    beta, _ = mnist.schedule(h, np.full(4, 3000.0), 10.0, 1e-4, 50890,
                             1024, 80, mnist.Analysis(1.0, 0.01, 0.2))
    np.testing.assert_array_equal(beta, [1, 1, 1, 1])


def test_digits_are_seeded_and_in_range():
    a = mnist.make_data(jax.random.PRNGKey(1), 2, 8, 4)
    b = mnist.make_data(jax.random.PRNGKey(1), 2, 8, 4)
    np.testing.assert_array_equal(np.asarray(a[0]["x"]),
                                  np.asarray(b[0]["x"]))
    assert a[0]["x"].shape == (2, 8, 784) and a[1].shape == (4, 784)
    assert float(a[0]["x"].min()) >= 0.0 and float(a[0]["x"].max()) <= 1.0


def test_numbers_take_the_worst_leaf_against_the_median():
    ref = {"loss": [2.0, 1.9, 1.8], "grad": [1.0, 0.01, 4.0],
           "change": [1.0, 0.0, 2.0], "ref_grad_max": [1.0, 0.0, 4.0]}
    prog = {"loss": [2.0, 1.9, 1.8 * 1.01], "grad": [1.1, 0.02, 4.0],
            "change": [1.0, 5.0, 2.2]}
    nums = compare.numbers(prog, ref)
    assert nums["loss_gap"] == pytest.approx(0.01)
    # the small leaf is measured against the median (1.0): 0.01
    assert nums["grad_gap"] == pytest.approx(0.1)
    # the leaf whose reference gradient is zero is left out
    assert nums["change_gap"] == pytest.approx(0.1)
    # by the median leaf: grad gaps 0.1, 0.01, 0; change gaps 0, 0.1
    assert nums["grad_gap_median"] == pytest.approx(0.01)
    assert nums["change_gap_median"] == pytest.approx(0.05)
    ok, lines = compare.judge(nums, {"loss_gap": {"limit": 0.02},
                                     "grad_gap": {"limit": 0.2},
                                     "change_gap": {"limit": 0.05}})
    assert not ok
    assert [n for n, _, _, passed in lines if not passed] == ["change_gap"]


def test_judge_compares_the_named_numbers_and_needs_each():
    nums = {"loss_gap": 0.01, "grad_gap": 5.0}
    ok, lines = compare.judge(nums, {"loss_gap": {"limit": 0.02}})
    assert ok and [n for n, *_ in lines] == ["loss_gap"]
    ok, lines = compare.judge(nums, {"loss_gap": {"limit": 0.02},
                                     "bt_gap": {"limit": 1e-5}})
    assert not ok and ("bt_gap", None, 1e-5, False) in lines
    assert compare.judge(nums, {}) == (False, [])
