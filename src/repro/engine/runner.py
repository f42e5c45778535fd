"""Chunked scan-over-rounds execution + vmap-over-arms sweeps
(DESIGN.md §11).

The engine runs rounds as ``lax.scan`` chunks cut at the eval cadence:
one jitted device call advances ``eval_every`` rounds (carry donated, so
params/opt/EF/warm-start buffers are reused in place), then the host
streams metrics (eval_fn, per-round scheduling stats) and launches the
next chunk. Chunk lengths take at most three distinct values (1,
``eval_every``, tail), so the jit cache stays bounded. The host loop's
steps are ``repro.obs`` spans (``init``, ``dispatch``, ``fetch``,
``eval``), and each stats or eval copy to the host counts in
``obs.host_copies``.

``run_sweep`` vmaps the same chunk over an ``Arms`` pytree: A experiment
arms (seeds × SNR × P^Max × lr) advance in ONE compiled program per
chunk — the fig1–fig5 sweep grids as a single device-resident computation
instead of sequential fig-script loops.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint, obs
from repro.core.sparsify import flatten_pytree
from repro.engine.core import EngineFns, build_engine
from repro.engine.state import Arms, SweepCheckpoint, make_arms, single_arm
from repro.optim.optimizers import sgd
from repro.theory.bounds import ErrorBudget


def _donate():
    # buffer donation is a no-op (with a warning) on CPU; only ask for it
    # where the runtime honors it
    return (0,) if jax.default_backend() != "cpu" else ()


def eval_points(rounds: int, eval_every: int) -> List[int]:
    """Rounds after which the host evaluates — t % eval_every == 0 plus
    the final round, matching the historical trainer cadence."""
    pts = sorted({t for t in range(rounds) if t % eval_every == 0}
                 | {rounds - 1})
    return pts


def chunk_spans(rounds: int, eval_every: Optional[int]) -> List[tuple]:
    """(t0, n) scan chunks whose boundaries land on the eval points; one
    full-range chunk when metrics are not streamed."""
    if not eval_every:
        return [(0, rounds)]
    spans, t0 = [], 0
    for t in eval_points(rounds, eval_every):
        spans.append((t0, t - t0 + 1))
        t0 = t + 1
    return spans


class EngineRun:
    """One built engine + its jitted chunk programs (single arm or
    vmapped arms — same scan body either way)."""

    def __init__(self, cfg, loss_fn, params, worker_data, k_weights,
                 eval_fn: Optional[Callable] = None, optimizer=None):
        self.cfg = cfg
        self.worker_data = worker_data
        self.k_weights = jnp.asarray(k_weights, jnp.float32)
        self.eval_fn = eval_fn
        self.opt = optimizer or sgd()
        flat, unflatten = flatten_pytree(params)
        self.fns: EngineFns = build_engine(cfg, loss_fn, self.opt,
                                           int(flat.shape[0]),
                                           int(self.k_weights.shape[0]),
                                           unflatten)
        self._params0 = params
        self._chunk_cache: Dict[tuple, Callable] = {}

    # -- chunk programs ----------------------------------------------------

    def _chunk_fn(self, n: int, vmapped: bool) -> Callable:
        key = (n, vmapped)
        if key in self._chunk_cache:
            return self._chunk_cache[key]
        full_round = self.fns.full_round

        def chunk(state, arm, worker_data, k_weights, t0):
            def body(st, t):
                return full_round(st, arm, worker_data, k_weights, t)

            return jax.lax.scan(body, state, t0 + jnp.arange(n))

        fn = chunk
        if vmapped:
            fn = jax.vmap(chunk, in_axes=(0, 0, None, None, None))
        fn = jax.jit(fn, donate_argnums=_donate())
        self._chunk_cache[key] = fn
        return fn

    # -- single-arm run (the trainer's scan path) --------------------------

    def init(self, arm: Optional[Arms] = None):
        arm = arm if arm is not None else single_arm(self.cfg)
        # run_chunk donates the state: give it its own copy of the params,
        # so the caller's arrays outlive the first chunk
        params = jax.tree_util.tree_map(jnp.copy, self._params0)
        return self.fns.init_state(params, arm), arm

    def run_chunk(self, state, arm, t0: int, n: int, vmapped=False):
        """Advance ``n`` rounds from ``t0`` in one device call. Returns
        (state', RoundStats with (n,)-leading stat arrays)."""
        fn = self._chunk_fn(n, vmapped)
        return fn(state, arm, self.worker_data, self.k_weights,
                  jnp.int32(t0))

    # -- checkpointing (DESIGN.md §14) -------------------------------------

    def sweep_template(self, arms: Arms) -> SweepCheckpoint:
        """Shape/dtype template of the sweep checkpoint — built with
        ``eval_shape`` (no state allocation), structurally identical to
        what ``run_sweep`` saves, so ``checkpoint.restore`` can validate
        leaf-by-leaf before touching the carry."""
        state = jax.eval_shape(
            jax.vmap(lambda a: self.fns.init_state(self._params0, a)), arms)
        return SweepCheckpoint(state=state, arms=arms,
                               t_next=jnp.zeros((), jnp.int32))

    def _restore_sweep(self, ckpt_dir: str, arms: Arms):
        """(state, t_start) from the latest checkpoint step, or None.
        The saved arms must match the requested ones bitwise — a resumed
        sweep under different seeds/SNR/P^Max/lr would silently produce a
        chimera trajectory."""
        step = checkpoint.latest_step(ckpt_dir)
        if step is None:
            return None
        ck = checkpoint.restore(ckpt_dir, step, self.sweep_template(arms))
        for name, saved, want in zip(Arms._fields, ck.arms, arms):
            if not np.array_equal(np.asarray(saved), np.asarray(want)):
                raise ValueError(
                    f"checkpoint {ckpt_dir!r} step {step} was written "
                    f"under different arms (field {name!r} differs); "
                    f"resuming would mix trajectories — pass the arms the "
                    f"sweep was started with")
        return ck.state, int(ck.t_next)

    # -- vmapped arms sweep ------------------------------------------------

    def run_sweep(self, arms: Arms, rounds: Optional[int] = None,
                  eval_every: Optional[int] = None, *,
                  ckpt_dir: Optional[str] = None,
                  resume: Optional[bool] = None, mesh=None) -> Dict:
        """Run A arms for ``rounds`` rounds as vmapped scan chunks.

        Returns a dict of host arrays: per-round scheduling trajectories
        ``n_scheduled``/``b_t`` with shape (A, rounds) (dense — every
        round, DESIGN.md §11), the predicted Theorem-1 ``budget``
        (``ErrorBudget`` of (A, rounds) arrays) with its ``rt_bound``
        total (repro.theory, DESIGN.md §12) — the whole seeds×SNR grid's
        bounds from the same compiled program; eq. 19 models the 1-bit CS
        pipeline, so these keys are present for ``aggregator="obcsaa"``
        only — plus ``agg_err`` when the
        measured-error probe is on, eval streams ``eval_rounds``/``loss``/
        ``accuracy`` when an eval_fn is present, and the final per-arm
        ``params`` (stacked pytree) + ``state``.

        Checkpointing (DESIGN.md §14): with ``ckpt_dir`` (or
        ``cfg.ckpt_dir``) the full ``SweepCheckpoint`` is saved at every
        scan-chunk boundary (the eval cadence); ``resume`` (or
        ``cfg.ckpt_resume``) restores the latest step and continues —
        bit-for-bit identical to the uninterrupted sweep, because the
        post-boundary chunk programs and their absolute-round PRNG folds
        are the same in both runs. Stat/eval streams then cover only
        [t_start, rounds) — ``out["t_start"]`` says where they begin.
        ``mesh``: optional device mesh; state/arms are placed with the
        leading arm axis sharded over the worker axes
        (``dist.infer_batch_sharding``) so A-arm sweeps spread over
        devices."""
        cfg = self.cfg
        rounds = rounds or cfg.rounds
        eval_every = eval_every if eval_every is not None \
            else (cfg.eval_every if self.eval_fn else None)
        ckpt_dir = ckpt_dir if ckpt_dir is not None else cfg.ckpt_dir
        resume = cfg.ckpt_resume if resume is None else resume
        A = int(arms.noise_var.shape[0])
        with obs.span("init"):
            state = jax.vmap(
                lambda a: self.fns.init_state(self._params0, a))(arms)
        t_start = 0
        if resume:
            if not ckpt_dir:
                raise ValueError("run_sweep(resume=True) needs ckpt_dir "
                                 "(or FLConfig.ckpt_dir)")
            restored = self._restore_sweep(ckpt_dir, arms)
            if restored is not None:
                state, t_start = restored
        if mesh is not None:
            from repro.dist.sharding import infer_batch_sharding
            state = jax.device_put(state, infer_batch_sharding(state, mesh))
            arms = jax.device_put(arms, infer_batch_sharding(arms, mesh))
        eval_v = jax.vmap(self.eval_fn) if self.eval_fn else None
        n_sched, b_ts, losses, accs, eval_ts = [], [], [], [], []
        budgets, errs = [], []
        for t0, n in chunk_spans(rounds, eval_every):
            if t0 + n <= t_start:
                continue                    # chunk fully covered by resume
            if t0 < t_start:
                raise ValueError(
                    f"checkpoint t_next={t_start} does not land on a chunk "
                    f"boundary for rounds={rounds}, eval_every={eval_every} "
                    f"— resume must use the cadence the sweep was saved "
                    f"with (boundary before it: t0={t0})")
            with obs.span("dispatch"):
                state, stats = self.run_chunk(state, arms, t0, n,
                                              vmapped=True)
            # stats leaves: (A, n) -> per-round trajectory slabs
            with obs.span("fetch"):
                n_sched.append(obs.fetch(stats.n_scheduled))
                b_ts.append(obs.fetch(stats.b_t))
                if stats.budget is not None:
                    budgets.append(tuple(obs.fetch(x)
                                         for x in stats.budget))
                if stats.agg_err is not None:
                    errs.append(obs.fetch(stats.agg_err))
            if eval_v is not None:
                with obs.span("eval"):
                    loss, acc = eval_v(state.params)
                    losses.append(obs.fetch(loss))
                    accs.append(obs.fetch(acc))
                eval_ts.append(t0 + n - 1)
            if ckpt_dir:
                checkpoint.save(ckpt_dir, t0 + n, SweepCheckpoint(
                    state=state, arms=arms,
                    t_next=jnp.asarray(t0 + n, jnp.int32)))

        def cat(parts, dtype=np.float32):
            return (np.concatenate(parts, axis=1) if parts
                    else np.zeros((A, 0), dtype))

        out = {"n_scheduled": cat(n_sched, np.int32), "b_t": cat(b_ts),
               "state": state, "params": state.params, "arms": arms,
               "t_start": t_start}
        assert out["n_scheduled"].shape == (A, rounds - t_start)
        if budgets:
            budget = ErrorBudget(*(np.concatenate(parts, axis=1)
                                   for parts in zip(*budgets)))
            out["budget"] = budget
            out["rt_bound"] = np.asarray(budget.rt())
            assert out["rt_bound"].shape == (A, rounds - t_start)
        if errs:
            out["agg_err"] = np.concatenate(errs, axis=1)
        if eval_v is not None and losses:
            out["eval_rounds"] = np.asarray(eval_ts)
            out["loss"] = np.stack(losses, axis=1)       # (A, n_evals)
            out["accuracy"] = np.stack(accs, axis=1)
        return out


def run_sweep(cfg, loss_fn, params, worker_data, k_weights, *,
              arms: Optional[Arms] = None, eval_fn=None, optimizer=None,
              rounds: Optional[int] = None,
              eval_every: Optional[int] = None,
              ckpt_dir: Optional[str] = None,
              resume: Optional[bool] = None, mesh=None, **arm_axes) -> Dict:
    """One-call sweep: build the engine, broadcast ``arm_axes`` (seeds /
    noise_var / p_max / lr sequences) into an ``Arms`` pytree and run the
    scan × vmap grid. See ``EngineRun.run_sweep`` for the result dict and
    the checkpoint/resume semantics (DESIGN.md §14)."""
    run = EngineRun(cfg, loss_fn, params, worker_data, k_weights,
                    eval_fn=eval_fn, optimizer=optimizer)
    arms = arms if arms is not None else make_arms(cfg, **arm_axes)
    return run.run_sweep(arms, rounds=rounds, eval_every=eval_every,
                         ckpt_dir=ckpt_dir, resume=resume, mesh=mesh)
