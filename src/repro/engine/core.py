"""The engine's round body: eq. (3)–(14) as one traceable function
(DESIGN.md §11).

``build_engine`` closes an ``FLConfig`` + task (loss_fn, optimizer, D, U)
over three pure functions:

- ``fade_step``      — Gauss-Markov block-fading draw (core/channel.py;
                       Rayleigh marginal — the paper's §V model, replacing
                       the old host loop's half-normal ``np.abs(normal)``)
- ``schedule``       — P2 inside the trace: closed-form ``all``, the
                       vectorized greedy prefix solver, or the scan-safe
                       batched ADMM (repro.sched, DESIGN.md §10)
- ``round_given_schedule`` / ``full_round`` — local gradients (eq. 3),
                       optional error-feedback correction, compress +
                       MAC + decode (eq. 6-13, repro.core.obcsaa /
                       repro.decode) and the model update (eq. 14)

``full_round`` is the ``lax.scan`` body; the host reference loop in
``fl/rounds.py`` calls the SAME ``fade_step``/``schedule``/
``round_given_schedule`` functions one round at a time, which is what
makes the engine ≡ host-loop parity bitwise (tests/test_engine.py).

PRNG discipline: round t of an arm uses ``fold_in(arm.key, t)``, folded
again with 0 for the channel draw and 1 for the receiver AWGN — identical
key trees in scan and host execution.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import channel as chan
from repro.core.obcsaa import simulate_round
from repro.core.sparsify import (flatten_pytree, topk_sparsify,
                                 topk_sparsify_bisect)
from repro.engine.config import ENGINE_SCHEDULERS, FLConfig
from repro.engine.state import Arms, EngineState, RoundStats
from repro.optim.optimizers import ef_step
from repro.sched.admm import AdmmDuals, admm_solve_batched_jit
from repro.sched.greedy import greedy_solve_batched
from repro.sched.problem import BatchedProblem
from repro.theory.bounds import error_budget

_FADE_INIT_FOLD = 0x7FADE   # fold_in tag for the stationary t=0 fade draw


def budget_geometry(ob, D: int):
    """(n_chunks, S_eff, κ_eff) of the block-diagonal Φ at dimension D —
    the Theorem-1 budget geometry (DESIGN.md §4/§12): the chunked operator
    measures n_chunks·S_c symbols of an (up to) n_chunks·κ_c-sparse
    vector. Shared by the engine round body and the sharded zoo round
    (engine/zoo.py, DESIGN.md §14) so both report the same eq. 19 bound."""
    n_chunks = -(-D // ob.chunk)
    return n_chunks, n_chunks * ob.measure, min(n_chunks * ob.topk, D)


class EngineFns(NamedTuple):
    """The built round functions + static geometry."""
    init_state: Callable    # (params, arm) -> EngineState
    fade_step: Callable     # (fade, key) -> (h, fade')
    # (h, k_weights, noise_var, p_max, duals=None) -> (β, b_t, duals')
    # duals' is the exit AdmmDuals when FLConfig.sched_warm_duals is
    # active, else None — the carry leaf stays fixed per build
    schedule: Callable
    round_given_schedule: Callable
    full_round: Callable    # (state, arm, worker_data, k_weights, t)
    D: int
    U: int


def stacked_grads(loss_fn, params, stacked_data):
    """vmap of the per-worker full-batch gradient (eq. 3), flattened to
    (U, D) — the same ops as ``fl.worker.stacked_local_gradients`` (kept
    separate from ``repro.fl`` to break the wrapper→engine import cycle)."""
    def one(data):
        g = jax.grad(lambda p: loss_fn(p, data))(params)
        return flatten_pytree(g)[0]

    return jax.vmap(one)(stacked_data)


def perfect_aggregate(grads_flat, k_weights, beta):
    """Error-free weighted mean (paper's "perfect aggregation" bench)."""
    w = (k_weights * beta)[:, None]
    return jnp.sum(grads_flat * w, axis=0) / jnp.maximum(
        jnp.sum(k_weights * beta), 1e-12)


def topk_aa_aggregate(grads_flat, k_weights, beta, b_t, kappa, noise_var,
                      key):
    """Sparsified analog aggregation (no CS, no 1-bit): workers transmit
    their top-κ gradients directly; AWGN at the PS."""
    sp, _ = topk_sparsify(grads_flat, kappa)
    w = (k_weights * beta * b_t)[:, None]
    y = jnp.sum(sp * w, axis=0)
    y = y + chan.draw_noise(key, y.shape, noise_var)
    return y / jnp.maximum(jnp.sum(k_weights * beta) * b_t, 1e-12)


def build_engine(cfg: FLConfig, loss_fn: Callable, opt, D: int, U: int,
                 unflatten: Callable) -> EngineFns:
    """Close the static experiment config over the round functions.

    ``ob.packed`` flows through unchanged: the scan round body's compress
    emits uint32 sign words and the MAC unpacks them to the identical ±1
    floats (DESIGN.md §13), so packed engine sweeps are bit-for-bit equal
    to f32 sweeps (tests/test_packed.py). Validated here so a bad geometry
    fails at build time, not inside a traced round."""
    ob = cfg.obcsaa
    if ob.packed and cfg.aggregator == "obcsaa" and ob.measure % 32:
        raise ValueError(
            f"build_engine: packed 1-bit codec needs S_c % 32 == 0, got "
            f"measure={ob.measure} (DESIGN.md §13)")
    n_chunks, s_eff, kappa_eff = budget_geometry(ob, D)
    pad = n_chunks * ob.chunk - D
    warm = cfg.aggregator == "obcsaa" and ob.warm_start
    ef = cfg.error_feedback
    rho = jnp.float32(cfg.channel_rho)
    scfg = cfg.sched_cfg
    probe = cfg.probe_agg_error
    # Eq. 19 models the 1-bit CS pipeline, so the budget is only emitted
    # for the obcsaa aggregator (None leaf otherwise — fixed per build)
    track_bound = cfg.aggregator == "obcsaa"
    # Dual warm-starting only applies where ADMM actually runs per round
    warm_duals = (cfg.sched_warm_duals and cfg.aggregator != "perfect"
                  and cfg.scheduler in ("admm_batched", "admm_batched_jit"))

    def init_state(params, arm: Arms) -> EngineState:
        _, fade0 = chan.draw_fades(
            jax.random.fold_in(arm.key, _FADE_INIT_FOLD), (U,))
        return EngineState(
            params=params, opt_state=opt.init(params), fade=fade0,
            prev_beta=-jnp.ones((U,), jnp.float32),
            decode_x0=jnp.zeros((n_chunks, ob.chunk)) if warm else None,
            residual=jnp.zeros((U, D)) if ef else None,
            sched_duals=AdmmDuals.zeros((U,)) if warm_duals else None)

    def fade_step(fade, key):
        return chan.draw_fades(key, rho=rho, prev=fade)

    def schedule(h, k_weights, noise_var, p_max, duals=None):
        """P2 for one round's channels, inside the trace (B = 1).
        ``duals`` (a (U,)-leaf ``AdmmDuals`` | None) warm-starts the ADMM
        multipliers from the previous round's schedule; the returned
        triple carries the exit duals back when warm-starting is active
        (None otherwise, so the scan carry leaf is fixed per build)."""
        bp = BatchedProblem.from_arrays(
            h[None], k_weights[None], p_max, noise_var, D=D, S=ob.measure,
            kappa=ob.topk, const=cfg.const)
        duals_out = None
        if cfg.scheduler == "all":
            beta = jnp.ones_like(bp.h)
            b_t = bp.optimal_bt(beta)
        elif cfg.scheduler == "greedy_batched":
            beta, b_t, _ = greedy_solve_batched(bp, scfg)
        elif cfg.scheduler in ("admm_batched", "admm_batched_jit"):
            if warm_duals and duals is not None:
                d1 = jax.tree_util.tree_map(lambda l: l[None], duals)
                beta, b_t, _, info = admm_solve_batched_jit(
                    bp, scfg, duals=d1, return_duals=True)
                duals_out = jax.tree_util.tree_map(lambda l: l[0],
                                                   info.duals)
            else:
                beta, b_t, _ = admm_solve_batched_jit(bp, scfg)
        else:
            raise ValueError(
                f"scheduler {cfg.scheduler!r} cannot run inside the "
                f"engine scan (jittable: {ENGINE_SCHEDULERS}); use the "
                "host reference path")
        return beta[0], b_t[0], duals_out

    def _ef_sparse_approx(corrected):
        """approx_fn for ``optim.ef_step``: per-chunk top-κ of the padded
        corrected gradient. The selection follows ``ob.spmd_topk`` like
        the compression core: bisection thresholds are the scan/SPMD-
        native path (sort lowers to an XLA CPU/GSPMD-hostile full sort;
        DESIGN.md §9). Returns (sparse (U, D_pad), its unpadded view) —
        the residual accumulates exactly what the top-κ dropped."""
        gp = jnp.pad(corrected, ((0, 0), (0, pad)))
        gc = gp.reshape(gp.shape[0], -1, ob.chunk)
        if ob.spmd_topk:
            sp, _ = topk_sparsify_bisect(gc, ob.topk,
                                         iters=ob.bisect_iters)
        else:
            sp, _ = topk_sparsify(gc, ob.topk)
        sp = sp.reshape(gp.shape)
        return sp, sp[:, :D]

    def ef_split(grads, residual):
        """EF correction + residual update via the shared ``optim.ef_step``
        (one Stich-et-al implementation repo-wide, DESIGN.md §17).
        Returns (corrected, residual', sparse (U, D_pad)) — the sparse
        vector IS sparse_κ of what obcsaa transmits, so the compressor
        consumes it directly instead of re-thresholding (DESIGN.md §11)."""
        sp, new_residual, corrected = ef_step(grads, residual,
                                              _ef_sparse_approx)
        return corrected, new_residual, sp

    def round_given_schedule(state: EngineState, arm: Arms, worker_data,
                             k_weights, t, h, fade, beta, b_t,
                             sched_duals=None):
        """Eq. 3 → 6-7 → 10 → 13 → 43 → 14 for one round, with the
        schedule already decided (the host path injects β from the
        registry here; the engine computes it in ``full_round``).
        ``sched_duals`` is the exit-multiplier state of the β solve,
        stored in the carry for the next round's warm start (must be an
        ``AdmmDuals`` whenever ``sched_warm_duals`` built the carry with
        one — the scan structure is fixed per build)."""
        with obs.phase("grad"):
            grads = stacked_grads(loss_fn, state.params, worker_data)
        residual = state.residual
        presparse = False
        if ef:
            with obs.phase("codec"):
                grads, residual, sparse = ef_split(grads, residual)
        dense = grads          # probe target: pre-compression gradients
        if ef and cfg.aggregator == "obcsaa":
            # fused EF: the residual split's sparse_κ IS what obcsaa
            # transmits — skip the second selection (DESIGN.md §11)
            grads, presparse = sparse, True
        x0 = state.decode_x0
        if warm:
            # schedule change -> reset warm-start state (DESIGN.md §9);
            # masked where instead of the old host np.array_equal sync
            changed = jnp.any(beta != state.prev_beta)
            x0 = jnp.where(changed, jnp.zeros_like(x0), x0)
        k_noise = jax.random.fold_in(jax.random.fold_in(arm.key, t), 1)
        if cfg.aggregator == "perfect":
            ghat = perfect_aggregate(grads, k_weights, beta)
        elif cfg.aggregator == "topk_aa":
            ghat = topk_aa_aggregate(grads, k_weights, beta, b_t,
                                     cfg.topk_dense, arm.noise_var,
                                     k_noise)
        elif cfg.aggregator == "obcsaa":
            ghat, diag = simulate_round(ob, grads, k_weights, beta, b_t,
                                        h, k_noise, decode_x0=x0,
                                        noise_var=arm.noise_var,
                                        presparsified=presparse)
            if warm:
                x0 = diag["decode_xhat"]
        else:
            raise ValueError(f"unknown aggregator {cfg.aggregator!r}")
        params, opt_state = opt.update(unflatten(ghat[:D]),
                                       state.opt_state, state.params,
                                       arm.lr)
        new_state = EngineState(params=params, opt_state=opt_state,
                                fade=fade, prev_beta=beta, decode_x0=x0,
                                residual=residual, sched_duals=sched_duals)
        # predicted Theorem-1 budget at this round's operating point
        # (repro.theory, DESIGN.md §12) — pure closed-form scalar math on
        # (β, b_t, σ²), no effect on the training dataflow above
        budget = None
        if track_bound:
            budget = error_budget(cfg.const, D=D, S=s_eff,
                                  kappa=kappa_eff, beta=beta,
                                  k_weights=k_weights, b_t=b_t,
                                  noise_var=arm.noise_var)
        agg_err = None
        if probe:
            # measured ‖ĝ−ḡ‖²: the decoded estimate against the
            # error-free weighted mean over the scheduled cohort — the
            # quantity eq. (19) bounds. Static flag: off, the trace is
            # the pre-probe engine (DESIGN.md §12 measure-zero contract)
            ideal = perfect_aggregate(dense, k_weights, beta)
            agg_err = jnp.sum((ghat[:D] - ideal) ** 2)
        stats = RoundStats(n_scheduled=jnp.sum(beta).astype(jnp.int32),
                           b_t=jnp.asarray(b_t, jnp.float32),
                           budget=budget, agg_err=agg_err)
        return new_state, stats

    def full_round(state: EngineState, arm: Arms, worker_data, k_weights,
                   t):
        """The scan body: fade draw + P2 + the full round update."""
        with obs.phase("schedule"):
            k_t = jax.random.fold_in(arm.key, t)
            h, fade = fade_step(state.fade, jax.random.fold_in(k_t, 0))
            if cfg.aggregator == "perfect":
                beta = jnp.ones((U,), jnp.float32)
                b_t = jnp.float32(1.0)
                duals = None
            else:
                beta, b_t, duals = schedule(h, k_weights, arm.noise_var,
                                            arm.p_max, state.sched_duals)
        return round_given_schedule(state, arm, worker_data, k_weights, t,
                                    h, fade, beta, b_t, duals)

    return EngineFns(init_state=init_state, fade_step=fade_step,
                     schedule=schedule,
                     round_given_schedule=round_given_schedule,
                     full_round=full_round, D=D, U=U)
