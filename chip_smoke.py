#!/usr/bin/env python3
"""Run the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: train, paper, serve
    python chip_smoke.py --four-chips  # four chips: the sharded round only

One process, phases in this order:

- train: ``repro.launch.train.main`` on mamba2-2.7b at its published
  widths, depth cut to ``TRAIN_LAYERS`` layers, through the zoo-train
  round (adam, error feedback, packed 1-bit uplink) on a 1x1 mesh. Checks
  a finite loss in every round and a master that moves every round.
- paper: the engine's sweep (``EngineRun.run_sweep``), the paper's round
  on mnist-mlp at the geometry of the figures (U=10, K=3000, D=50,890,
  D_c=4096, S_c=1024, kappa_c=80), ADMM scheduling, packed uplink, run
  twice. Checks a finite loss, and that the second run (its buffers
  donated and reused) equals the first.
- serve: ``repro.serve.cli.main``, a few ticks of the scheduling service
  at 10,000 cells; then the chip's batched greedy schedule against the
  plain NumPy solver on a few cells.

With ``--four-chips`` only the sharded zoo-train round runs: 2 workers x 2
model shards against ``reference_round_train`` on one chip, both under
float32 matmul precision, compared with ``repro.engine.parity``; and the
packed int32 MAC over the mesh against a one-chip integer sum, exactly.

Each phase prints what it ran, its XLA compile seconds (persistent-cache
reads included; tracing is not), its seconds per round or tick after
``block_until_ready`` and the device's peak bytes in use so far. These
are smoke timings from one run, not benchmark numbers.

The last line, printed only when every phase passed, is one JSON object
naming the device. Without a TPU the script fails before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# the largest depth whose compiled round (arguments + outputs +
# temporaries) stays under 13 GB of a v5e's 16 GB: 12.73 GB at 5 layers,
# 14.35 GB at 6
TRAIN_LAYERS = 5
TRAIN_ARGV = ["--zoo-train", "--arch", "mamba2-2.7b",
              "--layers", str(TRAIN_LAYERS), "--steps", "3", "--batch", "1",
              "--seq", "2048", "--cs-chunk", "16384", "--cs-measure", "32",
              "--cs-topk", "8", "--optimizer", "adam", "--error-feedback",
              "--remat-policy", "full"]
SERVE_ARGV = ["--cells", "10000", "--ticks", "5",
              "--scheduler", "greedy_batched"]
PAPER_ROUNDS = 5
FOUR_CHIP_LAYERS = 2

# one event per program, compile or persistent-cache read; the trace and
# lowering events nest (a jit traced inside another's trace) and would
# count twice
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _load_repo():
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {ROOT}/src; "
                         "run this script from a checkout of the repo")
    sys.path.insert(0, str(ROOT / "src"))


class CompileClock:
    """Seconds XLA spends compiling (persistent-cache reads included), one
    entry per program, and the persistent-cache hits, from
    ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.programs, self.hits = [], 0

        def on_duration(event, duration, **_):
            if event == _COMPILE_EVENT:
                self.programs.append(duration)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return len(self.programs), self.hits

    def since(self, mark):
        """(per-program compile seconds, cache hits) since ``mark``."""
        return self.programs[mark[0]:], self.hits - mark[1]


def require_tpu(count: int):
    """The devices, or exit non-zero when JAX finds fewer than ``count``
    TPU chips. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips; JAX found "
                         f"{len(devs)}")
    return devs


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def report(phase, clock_mark, clock, **fields):
    """One smoke-timing line per phase. JAX writes a program to the
    persistent cache only when its compile took at least
    ``jax_persistent_cache_min_compile_time_secs``; the line counts those."""
    import jax
    programs, hits = clock.since(clock_mark)
    cache_min = jax.config.jax_persistent_cache_min_compile_time_secs
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke timing] {phase}: xla_compile_s={sum(programs):.2f} "
          f"programs={len(programs)} longest_compile_s="
          f"{max(programs, default=0.0):.2f} programs_over_cache_min"
          f"({cache_min}s)={sum(t >= cache_min for t in programs)} "
          f"cache_hits={hits} {extra} peak_bytes_in_use(device 0, "
          f"process so far)={peak_bytes()}", flush=True)


def phase_train(clock, argv=TRAIN_ARGV):
    """repro.launch.train.main; checks finite losses and a moving master."""
    from repro.launch import train
    print(f"== train: repro.launch.train.main({' '.join(argv)})",
          flush=True)
    mark = clock.mark()
    history = train.main(argv)
    steps = int(argv[argv.index("--steps") + 1])
    if not history or len(history) != steps:
        raise RuntimeError(f"train: {steps} rounds asked, history "
                           f"{history!r}")
    for rec in history:
        if not math.isfinite(rec["loss"]):
            raise RuntimeError(f"train: non-finite loss {rec}")
    norms = [rec["w_norm"] for rec in history]
    if any(a == b for a, b in zip(norms, norms[1:])) \
            or not all(map(math.isfinite, norms)):
        raise RuntimeError(f"train: master did not move every round "
                           f"(|w| per round {norms})")
    secs = [rec["seconds"] for rec in history]
    report("train", mark, clock, first_round_s=f"{secs[0]:.3f}",
           round_s=",".join(f"{s:.3f}" for s in secs[1:]))


def phase_paper(clock, rounds=PAPER_ROUNDS, U=10, K=3000):
    """The paper's round on mnist-mlp through the engine's sweep
    (``EngineRun.run_sweep``, what ``repro.engine.run_sweep`` calls), run
    twice: the second run reuses the compiled scan chunk and times the
    rounds."""
    import jax
    import numpy as np
    from repro.core.obcsaa import OBCSAAConfig
    from repro.data import load_mnist, partition_workers
    from repro.engine import EngineRun, FLConfig, make_arms
    from repro.models.mlp_mnist import (init_mlp_mnist, mlp_mnist_accuracy,
                                        mlp_mnist_loss, param_dim)
    mnist_dir = os.environ.get("MNIST_DIR", "")
    print("== paper: repro.engine.EngineRun.run_sweep, mnist-mlp, U=10 "
          "K=3000 D_c=4096 S_c=1024 kappa_c=80, admm_batched, packed uplink; "
          "data: " + (f"MNIST from {mnist_dir}" if mnist_dir else
                      "MNIST_DIR unset, so the synthetic MNIST substitute"),
          flush=True)
    xtr, ytr, xte, yte = load_mnist()
    wx, wy = partition_workers(xtr, ytr, U, K, seed=0)
    data = {"x": jax.numpy.asarray(wx), "y": jax.numpy.asarray(wy)}
    params0 = init_mlp_mnist(jax.random.PRNGKey(0))
    xe, ye = jax.numpy.asarray(xte[:2000]), jax.numpy.asarray(yte[:2000])
    ob = OBCSAAConfig(chunk=4096, measure=1024, topk=80, biht_iters=25,
                      packed=True)
    cfg = FLConfig(aggregator="obcsaa", scheduler="admm_batched",
                   rounds=rounds, eval_every=rounds, obcsaa=ob)
    run = EngineRun(cfg, lambda p, d: mlp_mnist_loss(p, d["x"], d["y"]),
                    params0, data, np.full(U, float(K)),
                    eval_fn=jax.jit(lambda p: (mlp_mnist_loss(p, xe, ye),
                                               mlp_mnist_accuracy(p, xe, ye))))
    arms = make_arms(cfg, seeds=[0])
    mark = clock.mark()
    walls, outs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        out = run.run_sweep(arms)
        jax.block_until_ready(out["params"])
        walls.append(time.perf_counter() - t0)
        outs.append(out)
    loss = np.asarray(out["loss"])
    if param_dim(params0) != 50890 or not np.isfinite(loss).all() \
            or not (np.asarray(out["n_scheduled"]) > 0).all() \
            or not np.array_equal(loss, np.asarray(outs[0]["loss"])):
        raise RuntimeError(f"paper: D={param_dim(params0)} loss={loss} "
                           f"(first run {outs[0]['loss']}) "
                           f"n_scheduled={out['n_scheduled']}")
    print(f"paper: loss after {rounds} rounds {loss[0, -1]:.4f}, accuracy "
          f"{float(np.asarray(out['accuracy'])[0, -1]):.4f}, scheduled "
          f"{out['n_scheduled'][0].tolist()}, both runs equal", flush=True)
    report("paper", mark, clock, first_run_s=f"{walls[0]:.3f}",
           round_s=f"{walls[1] / rounds:.4f}")


def phase_serve(clock, argv=SERVE_ARGV, check_cells=8, workers=16):
    """repro.serve.cli.main, then the chip's batched greedy schedule vs the
    NumPy reference solver (repro.sched.reference.greedy_solve)."""
    import numpy as np
    from repro.sched import reference as ref
    from repro.sched.greedy import greedy_solve_batched
    from repro.sched.problem import BatchedProblem
    from repro.serve.cli import main as serve_main
    from repro.theory.bounds import AnalysisConstants
    print(f"== serve: repro.serve.cli.main({' '.join(argv)})", flush=True)
    mark = clock.mark()
    if serve_main(argv) != 0:
        raise RuntimeError("serve: CLI exited non-zero")
    rng = np.random.default_rng(5)
    probs = [ref.Problem(h=np.abs(rng.standard_normal(workers)) + 1e-3,
                         k_weights=np.full(workers, 3000.0), p_max=10.0,
                         noise_var=1e-4, D=50890, S=1024, kappa=80,
                         const=AnalysisConstants())
             for _ in range(check_cells)]
    beta, b_t, _ = greedy_solve_batched(BatchedProblem.from_problems(probs))
    beta, b_t = np.asarray(beta), np.asarray(b_t)
    for c, prob in enumerate(probs):
        # beta and b_t are picks from the same cap array (test_sched.py)
        want_beta, want_bt, _ = ref.greedy_solve(prob)
        if not np.array_equal(beta[c], want_beta) \
                or not np.isclose(b_t[c], want_bt, rtol=1e-6):
            raise RuntimeError(f"serve: cell {c} schedule {beta[c]} "
                               f"b_t={b_t[c]} != reference {want_beta} "
                               f"b_t={want_bt}")
    print(f"serve: greedy_batched on the device == NumPy greedy_solve on "
          f"{check_cells} cells x {workers} workers", flush=True)
    report("serve", mark, clock)


def four_chips(clock, cfg=None, rounds=2, seq=2048, chunk=16384):
    """Sharded zoo-train round on 2 workers x 2 model shards vs the
    one-chip reference; packed int32 MAC vs a one-chip integer sum.
    ``cfg`` defaults to mamba2-2.7b cut to FOUR_CHIP_LAYERS layers."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import TrainConfig, get_config
    from repro.dist.collectives import psum_bits_mac
    from repro.engine import parity
    from repro.kernels.sign import unpack_bits
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_zoo_mesh
    from repro.launch.train import make_zoo_batch
    from repro.models.registry import build_model

    mesh = make_zoo_mesh(2, 2)
    cfg = cfg or dataclasses.replace(get_config("mamba2-2.7b"),
                                     num_layers=FOUR_CHIP_LAYERS)
    tcfg = TrainConfig(aggregation="obcsaa", optimizer="adam",
                       learning_rate=3e-2, error_feedback=True,
                       cs_chunk=chunk, cs_measure=32, cs_topk=8,
                       biht_iters=10, cs_packed=True, remat_policy="full")
    model = build_model(cfg)
    zr = steps_lib.make_zoo_train_round(model, tcfg, mesh)
    print(f"== four-chips: {cfg.name}, {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, D={zr.D:,}, mesh {zr.U} workers x {zr.n_model} "
          f"model vs "
          f"reference_round_train on one chip, {rounds} rounds, matmul "
          f"precision highest on both", flush=True)
    chunked = zr.chunk_params(model.init(jax.random.PRNGKey(0)))
    raw = make_zoo_batch(cfg, zr.U, 1, seq)
    batch = zr.shard_batch(raw)
    key = jax.random.PRNGKey(1)
    reports = []
    mark = clock.mark()
    with jax.default_matmul_precision("highest"):
        s = zr.shard_state(zr.init_state(chunked))
        r = zr.init_state(chunked)
        for t in range(rounds):
            t0 = time.perf_counter()
            s, st = zr.round_train(s, batch, t, key, tcfg.noise_var,
                                   tcfg.p_max, tcfg.learning_rate)
            jax.block_until_ready(s)
            t1 = time.perf_counter()
            r, rst = zr.reference_round_train(r, raw, t, key,
                                              tcfg.noise_var, tcfg.p_max,
                                              tcfg.learning_rate)
            jax.block_until_ready(r)
            t2 = time.perf_counter()
            loss, rloss = float(st.loss), float(rst.loss)
            print(f"round {t}: mesh {t1 - t0:.3f}s loss={loss:.6f} | "
                  f"reference {t2 - t1:.3f}s loss={rloss:.6f}", flush=True)
            got = parity.compare_states(s, r, master0=chunked,
                                        tag=f"round {t}")
            for rep in got:
                print("  " + rep.line(), flush=True)
            reports += got
            if not math.isclose(loss, rloss, rel_tol=parity.LOSS_RTOL):
                reports.append(parity.LeafReport(
                    f"round {t} loss", abs(loss - rloss),
                    abs(loss - rloss) / abs(rloss), 0.0, False))
    for name, tol in parity.TOLERANCES.items():
        print(f"tolerance {name}: rtol={tol.rtol} atol_frac={tol.atol_frac}"
              f" min_rows={tol.min_rows} ({tol.reason})", flush=True)
    print(f"tolerance loss: rtol={parity.LOSS_RTOL}", flush=True)

    # the packed MAC: integer sums over the worker axis, exact by
    # construction, at the round's own (U, n_chunks, S_c/32) wire shape
    words = jax.random.bits(jax.random.PRNGKey(3),
                            (zr.U, zr.n_chunks, zr.ob.measure // 32),
                            jnp.uint32)
    mac = jax.jit(jax.shard_map(
        lambda w: psum_bits_mac(w[0], ("data",)), mesh=mesh,
        in_specs=P("data", "model", None), out_specs=P("model", None)))
    got_mac = np.asarray(mac(jax.device_put(
        words, NamedSharding(mesh, P("data", "model", None)))))
    want_mac = np.asarray(jnp.sum(2 * unpack_bits(words, jnp.int32) - 1,
                                  axis=0))
    mac_ok = got_mac.dtype == np.int32 and np.array_equal(got_mac, want_mac)
    print(f"packed int32 MAC over the mesh == one-chip sum: {mac_ok} "
          f"({got_mac.size:,} lanes)", flush=True)
    report("four-chips", mark, clock)
    parity.assert_reports(reports)
    if not mac_ok:
        raise AssertionError("packed MAC differs from the one-chip sum")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded zoo-train round on a 2x2 "
                         "mesh against its one-chip reference")
    args = ap.parse_args(argv)
    _load_repo()
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(clock)
    else:
        phase_train(clock)
        phase_paper(clock)
        phase_serve(clock)
    print(f"[smoke timing] total: {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
