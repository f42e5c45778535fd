"""Tracing of the round programs (repro.obs): the phase scopes reach the
compiled ops of the engine's scan chunk and of the zoo-train round as
``op_name`` metadata, one phase per op, and the engine's host loop writes
its spans into a recorded profiler trace."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.core.obcsaa import OBCSAAConfig
from repro.engine import EngineRun, FLConfig, make_arms
from repro.engine.runner import chunk_spans
from repro.engine.zoo_train import build_zoo_train_round
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.theory import AnalysisConstants

U = 4
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _task():
    kx, ky = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (U, 8, 12))
    y = jax.random.normal(ky, (U, 8, 3))

    def loss_fn(p, d):
        return jnp.mean((d["x"] @ p["w"] - d["y"]) ** 2)

    def eval_fn(p):
        loss = loss_fn(p, {"x": x, "y": y})
        return loss, -loss

    return {"x": x, "y": y}, {"w": jnp.zeros((12, 3))}, loss_fn, eval_fn


def _engine(**kw):
    wd, params0, loss_fn, eval_fn = _task()
    ob = OBCSAAConfig(chunk=32, measure=32, topk=4, biht_iters=2,
                      recon_alg="iht", recon_tau=0.25, packed=True)
    cfg = FLConfig(aggregator="obcsaa", scheduler="admm_batched",
                   rounds=5, eval_every=2, obcsaa=ob, learning_rate=0.1,
                   const=AnalysisConstants(rho1=200.0, G=1.0), **kw)
    return cfg, EngineRun(cfg, loss_fn, params0, wd, np.full(U, 8.0),
                          eval_fn=jax.jit(eval_fn))


@pytest.fixture(scope="module")
def engine_hlo():
    """Compiled text of the engine's vmapped scan chunk (ADMM schedule,
    error feedback, packed codec)."""
    cfg, run = _engine(error_feedback=True)
    arms = make_arms(cfg, seeds=[0])
    state = jax.vmap(lambda a: run.fns.init_state(run._params0, a))(arms)
    fn = run._chunk_fn(2, True)
    return fn.lower(state, arms, run.worker_data, run.k_weights,
                    jnp.int32(0)).compile().as_text()


@pytest.fixture(scope="module")
def zoo_hlo():
    """Compiled text of the zoo-train round (adam, error feedback) on the
    single-device host mesh."""
    model = build_model(get_smoke_config("mnist-mlp"))
    ob = OBCSAAConfig(chunk=256, measure=64, topk=16, biht_iters=3,
                      recon_alg="iht", spmd_topk=True, packed=True,
                      bisect_iters=16)
    zr = build_zoo_train_round(model, make_host_mesh(), ob,
                               optimizer="adam", error_feedback=True)
    state = zr.shard_state(zr.init_state(zr.chunk_params(
        model.init(jax.random.PRNGKey(0)))))
    batch = zr.shard_batch({"x": jnp.zeros((zr.U, 2, 784)),
                            "y": jnp.zeros((zr.U, 2), jnp.int32)})
    fn = zr._fns(batch)["round_train"]
    return fn.lower(state, batch, 0, jax.random.PRNGKey(1), 1e-4, 10.0,
                    0.1).compile().as_text()


def _phases(hlo):
    """The distinct ``repro.`` components of each op_name in a compiled
    text (a merged op joins its names with ";", and a transposed op may
    repeat its scope as ``transpose(repro.<phase>)``)."""
    return [sorted(set(re.findall(r"repro\.\w+", name)))
            for name in OP_NAME.findall(hlo)]


@pytest.mark.parametrize("phase", ["schedule", "grad", "codec",
                                   "mac_decode"])
def test_engine_round_ops_carry_their_phase(engine_hlo, phase):
    assert any(p == ["repro." + phase] for p in _phases(engine_hlo))


@pytest.mark.parametrize("phase", ["grad", "codec", "mac_decode", "optim"])
def test_zoo_round_ops_carry_their_phase(zoo_hlo, phase):
    assert any(p == ["repro." + phase] for p in _phases(zoo_hlo))


@pytest.mark.parametrize("program", ["engine_hlo", "zoo_hlo"])
def test_phase_scopes_do_not_nest(program, request):
    phases = _phases(request.getfixturevalue(program))
    assert phases and max(len(p) for p in phases) == 1


@pytest.fixture(scope="module")
def zamba2_hlo():
    """Compiled text of the zoo-train round of the two-layer Zamba2 smoke
    model (both layers hybrid: Mamba blocks and shared-block calls)."""
    model = build_model(get_smoke_config("zamba2-7b"))
    ob = OBCSAAConfig(chunk=4096, measure=64, topk=16, biht_iters=2,
                      recon_alg="iht", spmd_topk=True, packed=True,
                      bisect_iters=16)
    zr = build_zoo_train_round(model, make_host_mesh(), ob,
                               optimizer="adam", error_feedback=True)
    state = zr.shard_state(zr.init_state(zr.chunk_params(
        model.init(jax.random.PRNGKey(0)))))
    tok = jnp.zeros((zr.U, 1, 32), jnp.int32)
    batch = zr.shard_batch({"tokens": tok, "targets": tok})
    fn = zr._fns(batch)["round_train"]
    return fn.lower(state, batch, 0, jax.random.PRNGKey(1), 1e-4, 10.0,
                    0.1).compile().as_text()


def _scopes(hlo):
    """The distinct ``repro.`` scope names (dotted) of each op_name."""
    return [sorted(set(re.findall(r"repro\.[\w.]*\w", name)))
            for name in OP_NAME.findall(hlo)]


@pytest.mark.parametrize("block", ["mamba", "shared"])
def test_block_scopes_lie_only_under_the_grad_phase(zamba2_hlo, block):
    scopes = _scopes(zamba2_hlo)
    name = "repro.block." + block
    with_block = [s for s in scopes if name in s]
    assert with_block
    assert all("repro.grad" in s for s in with_block)


def test_zamba2_round_ops_carry_one_phase_each(zamba2_hlo):
    phases = [[n for n in s if not n.startswith("repro.block.")]
              for s in _scopes(zamba2_hlo)]
    assert phases and max(len(p) for p in phases) == 1
    for phase in ("grad", "codec", "mac_decode", "optim"):
        assert ["repro." + phase] in phases


@pytest.mark.parametrize("make", [obs.phase, obs.span, obs.block])
def test_unknown_names_are_refused(make):
    with pytest.raises(ValueError, match="unknown"):
        make("decode")


def test_sweep_writes_its_host_spans_into_a_trace(tmp_path):
    """A recorded CPU trace of one sweep holds ``repro.init`` once and
    ``repro.dispatch``, ``repro.fetch`` and ``repro.eval`` once a
    chunk."""
    from jax.profiler import ProfileData
    cfg, run = _engine()
    arms = make_arms(cfg, seeds=[0])
    run.run_sweep(arms)                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        run.run_sweep(arms)
    finally:
        jax.profiler.stop_trace()
    counts = {}
    for f in tmp_path.glob("**/*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(obs.PREFIX):
                        counts[e.name] = counts.get(e.name, 0) + 1
    chunks = len(chunk_spans(cfg.rounds, cfg.eval_every))
    assert counts == {"repro.init": 1, "repro.dispatch": chunks,
                      "repro.fetch": chunks, "repro.eval": chunks}
