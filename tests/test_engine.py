"""Device-resident FL engine (repro.engine, DESIGN.md §11).

The load-bearing claims:
- scan engine ≡ host reference loop BITWISE at float32 (params + EF
  residual + decode warm-start carry) over ≥20 rounds;
- a vmapped arms lane is bitwise the corresponding single-arm run;
- the scan-safe batched ADMM matches the host-compacted fleet solver;
- the shared fade helper draws the paper's Rayleigh marginal (the old
  host loop drew half-normal |N(0,1)| — the fixed inconsistency);
- per-round scheduling stats are dense (no eval-gated holes);
- error feedback improves the final solution on a synthetic task.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import channel as chan
from repro.theory import AnalysisConstants
from repro.core.obcsaa import OBCSAAConfig, simulate_round
from repro.core.sparsify import topk_sparsify
from repro.engine import EngineRun, FLConfig, make_arms, run_sweep
from repro.fl import FederatedTrainer

U = 4
CONST = AnalysisConstants(rho1=200.0, G=1.0)


# --- tiny task --------------------------------------------------------------------

@pytest.fixture(scope="module")
def task():
    """4-worker linear-regression task with a known optimum w*."""
    d_in, d_out, n = 24, 8, 16
    key = jax.random.PRNGKey(7)
    kw, kx, kn = jax.random.split(key, 3)
    w_star = jax.random.normal(kw, (d_in, d_out))
    x = jax.random.normal(kx, (U, n, d_in))
    y = jnp.einsum("ukd,dc->ukc", x, w_star) \
        + 0.01 * jax.random.normal(kn, (U, n, d_out))
    wd = {"x": x, "y": y}
    params0 = {"w": jnp.zeros((d_in, d_out))}

    def loss_fn(p, data):
        pred = data["x"] @ p["w"]
        return jnp.mean((pred - data["y"]) ** 2)

    def eval_fn(p):
        loss = jnp.mean((x.reshape(-1, d_in) @ p["w"]
                         - y.reshape(-1, d_out)) ** 2)
        return loss, -loss

    return wd, params0, loss_fn, eval_fn, w_star


@pytest.fixture(scope="module")
def mnist_task():
    """The paper's MLP at bitwise-stable shapes (D=50,890, 4096-chunks):
    tiny-dot fusions are context-dependent on XLA CPU, so the bitwise
    scan≡host claims are made where the bench makes them — on the
    MNIST-MLP task."""
    from repro.data import load_mnist, partition_workers
    from repro.models.mlp_mnist import init_mlp_mnist, mlp_mnist_loss
    xtr, ytr, _, _ = load_mnist()
    wx, wy = partition_workers(xtr, ytr, U, 4, seed=0)
    wd = {"x": jnp.asarray(wx), "y": jnp.asarray(wy)}
    params0 = init_mlp_mnist(jax.random.PRNGKey(0))

    def loss_fn(p, d):
        return mlp_mnist_loss(p, d["x"], d["y"])

    return wd, params0, loss_fn, None, None


def _ob(**kw):
    base = dict(chunk=64, measure=32, topk=8, biht_iters=4,
                recon_alg="iht", recon_tau=0.25)
    base.update(kw)
    return OBCSAAConfig(**base)


def _mnist_ob(**kw):
    base = dict(chunk=4096, measure=16, topk=8, biht_iters=2,
                recon_alg="iht", recon_tau=0.25)
    base.update(kw)
    return OBCSAAConfig(**base)


def _cfg(**kw):
    base = dict(aggregator="obcsaa", scheduler="greedy_batched",
                rounds=22, eval_every=8, obcsaa=_ob(), const=CONST,
                learning_rate=0.3)
    base.update(kw)
    return FLConfig(**base)


def _trainer(cfg, task_, **kw):
    wd, params0, loss_fn, eval_fn, _ = task_
    return FederatedTrainer(cfg, loss_fn, params0, wd,
                            np.full(U, 16.0), eval_fn=eval_fn, **kw)


def _tree_eq(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# --- engine ≡ host parity ---------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["greedy_batched", "admm_batched"])
def test_scan_equals_host_bitwise_warm_ef(mnist_task, scheduler):
    """The acceptance-criterion parity: scan engine ≡ host loop bitwise
    at float32 over ≥20 rounds, obcsaa with warm start + error feedback.
    Covers params, the EF residual carry and the decode warm-start carry."""
    wd, params0, loss_fn, _, _ = mnist_task
    cfg = FLConfig(aggregator="obcsaa", scheduler=scheduler, rounds=22,
                   obcsaa=_mnist_ob(warm_start=True), const=CONST,
                   error_feedback=True)
    scan_tr = FederatedTrainer(cfg, loss_fn, params0, wd, np.full(U, 4.0))
    scan_tr.run()
    host_tr = FederatedTrainer(dataclasses.replace(cfg, mode="host"),
                               loss_fn, params0, wd, np.full(U, 4.0))
    host_tr.run()
    assert scan_tr._mode == "scan" and host_tr._mode == "host"
    assert _tree_eq(scan_tr.params, host_tr.params)
    assert _tree_eq(scan_tr._state.residual, host_tr._state.residual)
    assert _tree_eq(scan_tr._state.decode_x0, host_tr._state.decode_x0)
    # dense stats streams agree (b_t to f32 tolerance: the caps product
    # h·√P/K may fuse differently across jit contexts — 1-ulp wiggle that
    # provably cancels out of the params trajectory above)
    assert [(s.round, s.n_scheduled) for s in scan_tr.sched_logs] \
        == [(s.round, s.n_scheduled) for s in host_tr.sched_logs]
    np.testing.assert_allclose([s.b_t for s in scan_tr.sched_logs],
                               [s.b_t for s in host_tr.sched_logs],
                               rtol=1e-6)


@pytest.mark.parametrize("opt_name,kw", [("momentum", {"beta": 0.9}),
                                         ("adam", {})])
def test_scan_equals_host_bitwise_optimizer_moments(mnist_task, opt_name,
                                                    kw):
    """§17 satellite of the optimizer-state tentpole: with a STATEFUL
    optimizer (momentum/adam moments riding the scan carry) and error
    feedback on, the scan engine still matches the host loop bitwise —
    params, every opt_state moment leaf, and the EF residual."""
    from repro.optim import make
    wd, params0, loss_fn, _, _ = mnist_task
    cfg = FLConfig(aggregator="obcsaa", scheduler="greedy_batched",
                   rounds=12, obcsaa=_mnist_ob(warm_start=True),
                   const=CONST, error_feedback=True)
    scan_tr = FederatedTrainer(cfg, loss_fn, params0, wd, np.full(U, 4.0),
                               optimizer=make(opt_name, **kw))
    scan_tr.run()
    host_tr = FederatedTrainer(dataclasses.replace(cfg, mode="host"),
                               loss_fn, params0, wd, np.full(U, 4.0),
                               optimizer=make(opt_name, **kw))
    host_tr.run()
    assert scan_tr._mode == "scan" and host_tr._mode == "host"
    assert _tree_eq(scan_tr.params, host_tr.params)
    assert _tree_eq(scan_tr.opt_state, host_tr.opt_state)
    assert _tree_eq(scan_tr._state.residual, host_tr._state.residual)
    # the moments did accumulate (non-trivial state went through parity)
    assert any(float(np.abs(np.asarray(x)).sum()) > 0
               for x in jax.tree_util.tree_leaves(scan_tr.opt_state))


def test_sweep_lane_equals_single_run(mnist_task):
    """vmap over arms must not change any lane's trajectory: lane i of a
    3-arm noise sweep matches the single-arm engine run at that σ² to
    f32 resolution (batched dots may re-associate — observed deviation is
    ~1e-8 after 8 rounds)."""
    wd, params0, loss_fn, _, _ = mnist_task
    cfg = FLConfig(aggregator="obcsaa", scheduler="greedy_batched",
                   obcsaa=_mnist_ob(warm_start=True), const=CONST)
    noise = [1e-6, 1e-4, 1e-2]
    out = run_sweep(cfg, loss_fn, params0, wd, np.full(U, 4.0),
                    rounds=8, noise_var=noise)
    single_cfg = dataclasses.replace(
        cfg, obcsaa=dataclasses.replace(cfg.obcsaa, noise_var=noise[2]))
    tr = FederatedTrainer(single_cfg, loss_fn, params0, wd,
                          np.full(U, 4.0))
    tr.run(8)
    lane = jax.tree_util.tree_map(lambda l: l[2], out["params"])
    for a, b in zip(jax.tree_util.tree_leaves(tr.params),
                    jax.tree_util.tree_leaves(lane)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
    assert out["n_scheduled"].shape == (3, 8)


def test_fused_ef_compression_matches_double_selection(task):
    """The engine's fused EF path (sparse_κ computed once, fed to the
    compressor presparsified) is bitwise the naive double-selection
    pipeline."""
    ob = _ob()
    grads = jax.random.normal(jax.random.PRNGKey(3), (U, 192))
    kw = jnp.full((U,), 16.0)
    beta = jnp.ones((U,))
    h = jnp.ones((U,))
    key = jax.random.PRNGKey(0)
    gc = grads.reshape(U, -1, ob.chunk)
    sp = topk_sparsify(gc, ob.topk)[0].reshape(U, -1)
    a, _ = simulate_round(ob, grads, kw, beta, 1.0, h, key)
    b, _ = simulate_round(ob, sp, kw, beta, 1.0, h, key,
                          presparsified=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# --- scan-safe ADMM ---------------------------------------------------------------

def test_admm_jit_matches_compacted_solver():
    """admm_solve_batched_jit (scan-safe, DESIGN.md §11) returns the same
    schedules as the host-compacted fleet solver."""
    from repro.sched import (BatchedProblem, admm_solve_batched,
                             admm_solve_batched_jit)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (16, 8))) + 1e-3
    bp = BatchedProblem.from_arrays(h, 3000.0, 10.0, 1e-4, D=50890,
                                    S=1000, kappa=1000, const=CONST)
    beta_c, bt_c, r_c = admm_solve_batched(bp)
    beta_j, bt_j, r_j = admm_solve_batched_jit(bp)
    assert np.array_equal(np.asarray(beta_c), np.asarray(beta_j))
    np.testing.assert_allclose(np.asarray(bt_c), np.asarray(bt_j),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r_c), np.asarray(r_j), rtol=1e-6)


# --- channel model (the fixed half-normal inconsistency) --------------------------

def _ks_rayleigh(samples) -> float:
    """Kolmogorov-Smirnov statistic of |h| against F(x) = 1 − exp(−x²),
    the |CN(0, 1)| (Rayleigh) magnitude CDF."""
    s = np.sort(np.asarray(samples).ravel())
    n = s.size
    cdf = 1.0 - np.exp(-s ** 2)
    i = np.arange(1, n + 1)
    return float(np.max(np.maximum(i / n - cdf, cdf - (i - 1) / n)))


def test_draw_fades_rayleigh_marginal_ks():
    """Regression for the channel-model fix: the shared helper draws
    Rayleigh magnitudes (KS vs the closed-form CDF at fixed seed), and
    the old half-normal |N(0,1)| draw is firmly rejected by the same
    statistic."""
    n = 20000
    h, _ = chan.draw_fades(jax.random.PRNGKey(11), (n,), clamp=False)
    assert _ks_rayleigh(h) < 0.015          # ≈1.95/√n at α=0.001
    half_normal = np.abs(np.random.default_rng(0).normal(size=n))
    assert _ks_rayleigh(half_normal) > 0.05


def test_gauss_markov_carry_keeps_rayleigh_marginal():
    """Stepping the Gauss-Markov recursion preserves the stationary
    CN(0, 1) marginal (magnitudes stay Rayleigh after many steps)."""
    key = jax.random.PRNGKey(13)
    _, g = chan.draw_fades(key, (4000,))
    for t in range(30):
        h, g = chan.draw_fades(jax.random.fold_in(key, t), rho=0.9,
                               prev=g, clamp=False)
    assert _ks_rayleigh(h) < 0.03


def test_trainer_and_scenario_share_fade_model(task):
    """Both consumers route through core.channel: the trainer's per-round
    magnitudes and the scenario generator's trajectories have the same
    Rayleigh marginal (KS on pooled draws)."""
    from repro.sched.scenario import ScenarioConfig, generate
    traj = generate(ScenarioConfig(rounds=64, cells=4, workers=16,
                                   model="iid"), jax.random.PRNGKey(3))
    assert _ks_rayleigh(np.asarray(traj)) < 0.03
    tr = _trainer(_cfg(rounds=4, eval_every=2), task)
    hs = [tr.run_round(t)["h"] for t in range(4)]
    assert np.all(np.concatenate(hs) >= chan.H_MIN)


# --- dense scheduling stats (RoundLog sparsity fix) -------------------------------

def test_sched_trajectory_dense_every_round(task):
    """n_scheduled/b_t are recorded EVERY round (the old loop only logged
    on eval rounds, leaving holes in scheduling trajectories)."""
    cfg = _cfg(rounds=15, eval_every=4)
    tr = _trainer(cfg, task)
    tr.run()
    traj = tr.sched_trajectory
    assert list(traj["round"]) == list(range(15))
    assert traj["n_scheduled"].shape == (15,)
    assert np.all(traj["n_scheduled"] >= 1)
    assert np.all(traj["b_t"] > 0)
    # eval stream stays on the eval cadence
    assert [l.round for l in tr.logs] == [0, 4, 8, 12, 14]


def test_sweep_counts_ten_host_copies_a_chunk(task):
    """``run_sweep`` copies 10 arrays to the host at each chunk boundary
    with the budget on and an eval: n_scheduled, b_t, the 6 ``ErrorBudget``
    fields, the eval's loss and accuracy; ``obs.host_copies`` counts each."""
    from repro import obs
    from repro.engine.runner import chunk_spans
    wd, params0, loss_fn, eval_fn, _ = task
    cfg = _cfg(rounds=5, eval_every=2)
    run = EngineRun(cfg, loss_fn, params0, wd, np.full(U, 16.0),
                    eval_fn=eval_fn)
    before = obs.host_copies
    out = run.run_sweep(make_arms(cfg, seeds=[0]))
    chunks = len(chunk_spans(5, 2))
    assert chunks == 3 and "budget" in out and "loss" in out
    assert obs.host_copies - before == chunks * 10


# --- error feedback ---------------------------------------------------------------

def test_error_feedback_improves_final_nmse(task):
    """EF compensates the top-κ compression bias: final NMSE
    ||w_T − w*||²/||w*||² improves with error_feedback=True on the
    synthetic regression task (aggressive sparsification, no AWGN)."""
    wd, params0, loss_fn, eval_fn, w_star = task
    nmse = {}
    for ef in (False, True):
        cfg = _cfg(aggregator="topk_aa", topk_dense=24, rounds=120,
                   eval_every=119, error_feedback=ef,
                   obcsaa=_ob(noise_var=1e-12), learning_rate=0.5)
        tr = _trainer(cfg, task)
        tr.run()
        w = np.asarray(tr.params["w"])
        nmse[ef] = float(np.sum((w - np.asarray(w_star)) ** 2)
                         / np.sum(np.asarray(w_star) ** 2))
    assert nmse[True] < 0.5 * nmse[False], nmse


# --- host reference path ----------------------------------------------------------

def test_enum_scheduler_runs_on_host_path(task):
    """The non-jittable enumeration oracle still works through the host
    reference path (auto mode resolution)."""
    cfg = _cfg(scheduler="enum", rounds=3, eval_every=2)
    tr = _trainer(cfg, task)
    assert tr._mode == "host"
    logs = tr.run()
    assert np.isfinite(logs[-1].loss)
    assert len(tr.sched_logs) == 3


def test_scan_mode_rejects_nonjittable_scheduler():
    with pytest.raises(ValueError, match="not jittable"):
        FLConfig(scheduler="enum", mode="scan").resolved_mode()


# --- launch wiring ----------------------------------------------------------------

def test_scan_train_step_and_scheduled_span_smoke():
    """launch/steps.py engine wiring: a whole span's P2 schedules solved
    in one batched call, then N rounds advanced by one jitted scan step
    (mesh train path, DESIGN.md §11)."""
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.launch import steps as steps_lib
    from repro.models.registry import build_model

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    cfg = get_config("mnist-mlp")
    tcfg = TrainConfig(aggregation="obcsaa", cs_chunk=512, cs_measure=64,
                       cs_topk=16, biht_iters=2)
    model = build_model(cfg)
    n = 3
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        opt = steps_lib.make_optimizer(tcfg)
        opt_state = opt.init(params)
        D = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(params))
        span = steps_lib.make_scheduled_round_span(mesh, tcfg, D, n)
        assert span["h"].shape == (n, 1) and span["beta"].shape == (n, 1)
        assert np.all(np.asarray(span["b_t"]) > 0)
        batch = {"x": jnp.ones((8, 784)),
                 "y": jnp.zeros((8,), jnp.int32)}
        step = jax.jit(steps_lib.make_scan_train_step(model, tcfg, mesh,
                                                      n))
        params2, opt_state, metrics = step(params, opt_state, batch, span)
        assert metrics["loss"].shape == (n,)
        assert np.all(np.isfinite(np.asarray(metrics["loss"])))
        moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree_util.tree_leaves(params),
                                    jax.tree_util.tree_leaves(params2)))
        assert moved
