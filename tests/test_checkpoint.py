"""Checkpoint/restore (DESIGN.md §14): atomic step dirs, template-strict
validation, corruption errors that say what to do, engine-wired resume that
is bit-for-bit identical to the uninterrupted sweep — including restoring
onto a differently-sized mesh (1 -> 8 and 8 -> 1 devices) and resuming the
train CLI."""
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro.core.obcsaa import OBCSAAConfig
from repro.engine import EngineRun, FLConfig, make_arms

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --- io primitives ---------------------------------------------------------------

def _tree():
    return {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.bfloat16) * 1.5,
            "n": (jnp.int32(7), {"deep": jnp.zeros((2, 2), jnp.float64)})}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tree()
    path = checkpoint.save(d, 3, tree)
    assert path.endswith("step_00000003") and os.path.isdir(path)
    assert checkpoint.latest_step(d) == 3
    out = checkpoint.restore(d, 3, jax.eval_shape(lambda: tree))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float64),
                              np.asarray(b, np.float64)), (a, b)
    # overwriting a step is atomic-in-place; later steps win latest_step
    checkpoint.save(d, 3, tree)
    checkpoint.save(d, 10, tree)
    assert checkpoint.latest_step(d) == 10


def test_restore_validation_errors(tmp_path):
    d = str(tmp_path / "ck")
    checkpoint.save(d, 2, _tree())
    with pytest.raises(FileNotFoundError, match="available steps.*2"):
        checkpoint.restore(d, 5, _tree())
    with pytest.raises(FileNotFoundError, match="none"):
        checkpoint.restore(str(tmp_path / "nowhere"), 0, _tree())
    with pytest.raises(ValueError, match="leaves, template has"):
        checkpoint.restore(d, 2, {"only": jnp.zeros(3)})
    bad = _tree()
    bad["w"] = jnp.zeros((9, 9))
    with pytest.raises(ValueError, match="geometry"):
        checkpoint.restore(d, 2, bad)


def test_restore_dtype_strict_message(tmp_path):
    """Restore validates per-leaf dtypes against the template: optimizer
    moments and round carries restore dtype-strict, a silent cast would
    break bitwise resume (DESIGN.md §17). The one legitimate aliasing is
    ml_dtypes storage — a bfloat16 template accepts the float32 bytes
    ``save`` wrote for it."""
    d = str(tmp_path / "ck")
    checkpoint.save(d, 1, {"m": jnp.zeros((3,), jnp.float32)})
    bad = {"m": jax.ShapeDtypeStruct((3,), jnp.float16)}
    with pytest.raises(ValueError, match=r"has dtype float32, template "
                                         r"expects float16.*dtype-strict"):
        checkpoint.restore(d, 1, bad)
    with pytest.raises(ValueError, match=r"expects int32"):
        checkpoint.restore(d, 1, {"m": jax.ShapeDtypeStruct((3,),
                                                            jnp.int32)})
    out = checkpoint.restore(d, 1, {"m": jax.ShapeDtypeStruct(
        (3,), jnp.bfloat16)})        # bf16 is STORED as f32: accepted
    assert out["m"].dtype == jnp.bfloat16


@pytest.mark.parametrize("victim", ["tree.msgpack", "arrays.npz"])
def test_corrupt_checkpoint_errors(tmp_path, victim):
    """A truncated/garbled file must surface as ValueError telling the
    user which file broke and to resume from an earlier step — not as a
    raw zipfile/msgpack traceback."""
    d = str(tmp_path / "ck")
    checkpoint.save(d, 1, _tree())
    p = os.path.join(checkpoint.step_dir(d, 1), victim)
    blob = open(p, "rb").read()
    with open(p, "wb") as f:       # truncate to a prefix
        f.write(blob[:max(1, len(blob) // 3)])
    with pytest.raises(ValueError) as ei:
        checkpoint.restore(d, 1, _tree())
    msg = str(ei.value)
    assert "corrupt or truncated" in msg and victim in msg
    assert "resume from an earlier step" in msg


# --- engine-wired resume ---------------------------------------------------------

def _sweep_fixture():
    U, D = 4, 1200
    cfg = FLConfig(aggregator="obcsaa", scheduler="all", rounds=8,
                   eval_every=3, error_feedback=True,
                   obcsaa=OBCSAAConfig(chunk=256, measure=64, topk=16,
                                       biht_iters=3, warm_start=True,
                                       recon_alg="iht"))
    params0 = {"w": jnp.linspace(-1.0, 1.0, D, dtype=jnp.float32)}
    data = {"c": jax.random.normal(jax.random.PRNGKey(3), (U, D))}

    def loss(p, d):
        return 0.5 * jnp.sum((p["w"] - d["c"]) ** 2)

    def ev(p):
        return jnp.sum(p["w"] ** 2), jnp.float32(0.0)

    def run():
        return EngineRun(cfg, loss, params0, data, np.ones(U), eval_fn=ev)
    return cfg, run


def _trim(ckpt_dir, keep_to):
    for sub in os.listdir(ckpt_dir):
        if int(sub.split("_")[1]) > keep_to:
            shutil.rmtree(os.path.join(ckpt_dir, sub))


def test_engine_resume_bitwise(tmp_path):
    """Kill a sweep at an eval boundary, resume: the full carry (params /
    fade / prev-beta / warm-start / EF residual), the stat tail and the
    eval stream must equal the uninterrupted run bit for bit."""
    cfg, mk = _sweep_fixture()
    arms = make_arms(cfg, noise_var=[1e-4, 1e-2])
    d = str(tmp_path / "sweep")
    full = mk().run_sweep(arms, ckpt_dir=d)
    assert full["t_start"] == 0
    # chunk boundaries for rounds=8, eval_every=3 are 1, 4, 7, 8
    assert checkpoint.latest_step(d) == 8
    _trim(d, 4)
    res = mk().run_sweep(arms, ckpt_dir=d, resume=True)
    assert res["t_start"] == 4
    eq = jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        full["state"], res["state"])
    assert all(jax.tree_util.tree_leaves(eq)), eq
    n = res["n_scheduled"].shape[1]
    assert np.array_equal(full["n_scheduled"][:, -n:], res["n_scheduled"])
    assert np.array_equal(full["b_t"][:, -n:], res["b_t"])
    assert np.array_equal(full["rt_bound"][:, -n:], res["rt_bound"])
    assert np.array_equal(full["loss"][:, -1], res["loss"][:, -1])
    # resuming past the end is a no-op that still returns the final state
    done = mk().run_sweep(arms, ckpt_dir=d, resume=True)
    assert done["t_start"] in (7, 8)


def test_engine_resume_rejects_different_arms(tmp_path):
    cfg, mk = _sweep_fixture()
    arms = make_arms(cfg, noise_var=[1e-4, 1e-2])
    d = str(tmp_path / "sweep")
    mk().run_sweep(arms, ckpt_dir=d)
    other = make_arms(cfg, noise_var=[1e-4, 5e-2])
    with pytest.raises(ValueError, match="different arms"):
        mk().run_sweep(other, ckpt_dir=d, resume=True)


def test_engine_resume_requires_ckpt_dir():
    cfg, mk = _sweep_fixture()
    with pytest.raises(ValueError, match="ckpt_dir"):
        mk().run_sweep(make_arms(cfg, noise_var=[1e-4]), resume=True)


SCRIPT_ELASTIC = textwrap.dedent("""
    import os, shutil, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.obcsaa import OBCSAAConfig
    from repro.engine import EngineRun, FLConfig, make_arms
    from repro.launch.mesh import auto_mesh
    from repro.optim import make as make_opt

    U, D = 4, 1200
    cfg = FLConfig(aggregator="obcsaa", scheduler="all", rounds=8,
                   eval_every=3, error_feedback=True,
                   obcsaa=OBCSAAConfig(chunk=256, measure=64, topk=16,
                                       biht_iters=3, warm_start=True,
                                       recon_alg="iht"))
    params0 = {"w": jnp.linspace(-1.0, 1.0, D, dtype=jnp.float32)}
    data = {"c": jax.random.normal(jax.random.PRNGKey(3), (U, D))}
    loss = lambda p, d: 0.5 * jnp.sum((p["w"] - d["c"]) ** 2)
    arms = make_arms(cfg, noise_var=[1e-4, 1e-3, 1e-2, 1e-1])
    mesh = auto_mesh((4, 2), ("data", "model"))
    # adam: the checkpoint carries NON-TRIVIAL optimizer moments through
    # the device-layout transitions (DESIGN.md §17)
    mk = lambda: EngineRun(cfg, loss, params0, data, np.ones(U),
                           optimizer=make_opt("adam"))

    def trim(d, keep):
        for s in os.listdir(d):
            if int(s.split("_")[1]) > keep:
                shutil.rmtree(os.path.join(d, s))

    def assert_bitwise(a, b, what):
        eq = jax.tree_util.tree_map(
            lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
            a, b)
        assert all(jax.tree_util.tree_leaves(eq)), (what, eq)

    base = tempfile.mkdtemp()
    # uninterrupted single-placement run = the reference trajectory
    ref = mk().run_sweep(arms, eval_every=3)["state"]
    assert float(np.abs(np.asarray(ref.opt_state["m"]["w"])).sum()) > 0
    assert float(np.abs(np.asarray(ref.opt_state["v"]["w"])).sum()) > 0

    # 1 -> 8: save on default placement, finish on the 8-device mesh with
    # the arm axis sharded over the workers
    d1 = os.path.join(base, "from1")
    mk().run_sweep(arms, ckpt_dir=d1, eval_every=3)
    trim(d1, 4)
    r8 = mk().run_sweep(arms, ckpt_dir=d1, resume=True,
                        mesh=mesh, eval_every=3)
    assert r8["t_start"] == 4
    assert_bitwise(ref, r8["state"], "1->8")

    # 8 -> 1: save while arms-sharded on the mesh, finish single-placement
    d8 = os.path.join(base, "from8")
    mk().run_sweep(arms, ckpt_dir=d8, mesh=mesh, eval_every=3)
    trim(d8, 4)
    r1 = mk().run_sweep(arms, ckpt_dir=d8, resume=True,
                        eval_every=3)
    assert r1["t_start"] == 4
    assert_bitwise(ref, r1["state"], "8->1")
    print("OK")
""")


@pytest.mark.slow
def test_mesh_elastic_resume_8dev():
    """A sweep checkpoint saved under one device layout restores onto a
    differently-sized mesh (1 -> 8 and 8 -> 1) and finishes bit-for-bit
    identical to the uninterrupted run — checkpoints hold plain host
    arrays, placement is reapplied at restore (DESIGN.md §14)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT_ELASTIC], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


# --- train CLI -------------------------------------------------------------------

@pytest.mark.slow
def test_train_cli_resume(tmp_path):
    """``--resume`` continues from the latest step and reaches the same
    final parameters+optimizer state, bit for bit, as the uninterrupted
    run (step RNG/schedules index absolute steps)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    # collection imports launch.dryrun, which pins a 512-device XLA flag
    # in this process — don't leak it into the CLI child
    env.pop("XLA_FLAGS", None)
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "gemma2-2b", "--smoke", "--batch", "2", "--seq", "32",
            "--cs-chunk", "512", "--cs-measure", "64", "--cs-topk", "16"]

    def run(extra):
        r = subprocess.run(base + extra, env=env, capture_output=True,
                           text=True, timeout=560)
        assert r.returncode == 0, \
            f"ARGS {extra}\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
        return r.stdout

    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    run(["--steps", "4", "--ckpt-dir", da])
    run(["--steps", "2", "--ckpt-dir", db])
    out = run(["--steps", "4", "--ckpt-dir", db, "--resume"])
    assert "resumed from step 2" in out
    a = np.load(os.path.join(checkpoint.step_dir(da, 4), "arrays.npz"))
    b = np.load(os.path.join(checkpoint.step_dir(db, 4), "arrays.npz"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        assert np.array_equal(a[k], b[k]), f"leaf {k} differs after resume"
