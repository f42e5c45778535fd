"""Runnable trainer over the local devices (CPU host or TPU chips).

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 20 --agg obcsaa
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-2.7b \
      --layers 4 --zoo-train --batch 1 --seq 2048 --cs-chunk 16384 \
      --cs-measure 32 --cs-topk 8 --optimizer adam --error-feedback

Uses the same step builders as the dry-run, on a mesh of the devices this
process sees. --smoke trains the reduced config; without it the model
keeps its published widths and --layers cuts its depth.

``--serve`` hands the remaining arguments to the continuous scheduling
service instead (``repro.serve``, DESIGN.md §15):

  PYTHONPATH=src python -m repro.launch.train --serve --cells 10000
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config, get_smoke_config
from repro.data import token_stream
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model


def make_batch(cfg, B, S, rng_seed=0):
    tokens, targets = token_stream(B, S, cfg.vocab_size, seed=rng_seed)
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(targets)}
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.01 * jnp.ones(
            (B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = 0.01 * jnp.ones(
            (B, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    return batch


def make_zoo_batch(cfg, U, B, S, rng_seed=0):
    """(U, B, ...)-stacked per-worker batches for the zoo round: each of
    the mesh's U FL workers trains on its own token stream."""
    per = [make_batch(cfg, B, S, rng_seed=rng_seed * 1000 + u)
           for u in range(U)]
    return {k: jnp.stack([p[k] for p in per]) for k in per[0]}


def run_zoo_train(args, cfg, tcfg, model, mesh):
    """--zoo-train driver: real sharded backward passes through the
    chunked (n_chunks, D_c) round (engine.zoo_train, DESIGN.md §16/§17).

    The carry is the full ZooTrainState — master + optimizer moments +
    per-worker EF residuals — so --ckpt-dir/--resume restore mid-run with
    non-trivial optimizer state bit-for-bit. With --data, every round
    samples a fresh (U, B, S) batch from the memmapped token shards,
    keyed by the absolute round index (no iterator state to serialize).

    Returns one dict per round run (``round``, ``loss``, ``w_norm`` = the
    master's L2 norm after the round, ``seconds`` until the new carry is
    ready) for the single-arm run, None for a sweep."""
    zr = steps_lib.make_zoo_train_round(model, tcfg, mesh)
    print(f"zoo-train: D={zr.D:,} n_chunks={zr.n_chunks} "
          f"({zr.n_model} model x {zr.U} workers x {zr.n_local} local), "
          f"optimizer={zr.optimizer_name} ef={zr.error_feedback} "
          f"remat={tcfg.remat_mode}", flush=True)
    # the params pytree is only the source of the master: dropping it
    # keeps one copy of the weights on the device, not two
    master = zr.chunk_params(model.init(jax.random.PRNGKey(0)))
    key = jax.random.PRNGKey(1)
    data_key = jax.random.PRNGKey(2)
    shards = None
    if args.data:
        from repro.data import TokenShards
        shards = TokenShards.open(args.data)
        print(f"data: {len(shards.names)} token shards, "
              f"{shards.total_tokens:,} tokens from {args.data}",
              flush=True)

    def zoo_batch(t):
        if shards is not None:
            return zr.shard_batch(shards.sample_zoo_batch(
                data_key, t, zr.U, args.batch, args.seq))
        return zr.shard_batch(
            make_zoo_batch(cfg, zr.U, args.batch, args.seq))

    if args.arms > 1:
        A = args.arms
        arms = {"noise_var": jnp.float32(tcfg.noise_var)
                * jnp.logspace(0, 2, A, dtype=jnp.float32),
                "p_max": jnp.full((A,), tcfg.p_max, jnp.float32),
                "lr": jnp.float32(args.lr)
                * jnp.logspace(0, -1, A, dtype=jnp.float32)}
        states = zr.shard_state(zr.init_sweep_state(
            jnp.broadcast_to(master, (A,) + master.shape)), arms=A)
        t_start = 0
        if args.resume:
            got = zr.restore_state(args.ckpt_dir, arms=A)
            if got is not None:
                states, t_start = got
                print(f"resumed sweep at round {t_start}", flush=True)
        batch = zoo_batch(t_start)   # sweeps run one fixed batch
        t0 = time.time()
        states, stats = zr.run_sweep(states, batch, arms,
                                     args.steps - t_start, key=key,
                                     t0=t_start)
        losses = np.asarray(stats.loss)          # (rounds, A)
        dt = time.time() - t0
        for a in range(A):
            print(f"arm {a}: noise_var={float(arms['noise_var'][a]):.2e} "
                  f"lr={float(arms['lr'][a]):.3f} "
                  f"loss {losses[0, a]:.4f} -> {losses[-1, a]:.4f}",
                  flush=True)
        print(f"{A} arms x {args.steps - t_start} rounds in one program "
              f"({dt:.2f}s)", flush=True)
        if args.ckpt_dir:
            path = zr.save_state(args.ckpt_dir, args.steps, states,
                                 t_next=args.steps)
            print(f"saved checkpoint: {path}")
    else:
        state = zr.shard_state(zr.init_state(master))
        # the carry holds the master now; another reference would keep
        # the round-0 weights on the device for the whole run
        del master
        t_start = 0
        if args.resume:
            got = zr.restore_state(args.ckpt_dir)
            if got is not None:
                state, t_start = got
                print(f"resumed zoo-train at round {t_start}", flush=True)
        batch = None
        history = []
        for t in range(t_start, args.steps):
            if shards is not None or batch is None:
                batch = zoo_batch(t)
            t0 = time.perf_counter()
            state, st = zr.round_train(state, batch, t, key,
                                       tcfg.noise_var, tcfg.p_max,
                                       args.lr)
            jax.block_until_ready(state)
            dt = time.perf_counter() - t0
            rec = {"round": t, "loss": float(st.loss),
                   "w_norm": float(jnp.linalg.norm(state.master)),
                   "seconds": dt}
            history.append(rec)
            print(f"round {t:4d} loss={rec['loss']:.4f} "
                  f"b_t={float(st.b_t):.4f} |w|={rec['w_norm']:.6f} "
                  f"({dt:.2f}s)", flush=True)
            if args.ckpt_dir and args.ckpt_every \
                    and (t + 1) % args.ckpt_every == 0:
                zr.save_state(args.ckpt_dir, t + 1, state, t_next=t + 1)
        if args.ckpt_dir:
            path = zr.save_state(args.ckpt_dir, args.steps, state,
                                 t_next=args.steps)
            print(f"saved checkpoint: {path}")
        return history


def main(argv=None):
    """Parse ``argv`` (default: ``sys.argv[1:]``) and train. With
    --zoo-train, returns the per-round history of ``run_zoo_train``."""
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--serve" in argv:
        # dispatch to the scheduling-service CLI with the rest of the
        # arguments (repro.serve owns its own parser)
        from repro.serve.cli import main as serve_main
        raise SystemExit(serve_main([a for a in argv if a != "--serve"]))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced widths (the arch's smoke config)")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers (a depth cut; "
                         "every width stays as configured). 0: all")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--agg", default="obcsaa", choices=["mean", "obcsaa"])
    ap.add_argument("--zoo-train", action="store_true",
                    help="train through the chunked zoo round with REAL "
                         "sharded backward passes (engine.zoo_train, "
                         "DESIGN.md §16): master lives as the sharded-flat "
                         "(n_chunks, D_c) array, grads flow into the "
                         "packed 1-bit uplink with no full-D gather")
    ap.add_argument("--arms", type=int, default=1,
                    help="with --zoo-train: run an N-arm noise_var x lr "
                         "grid as ONE jitted scan-over-rounds program "
                         "(ZooTrainRound.run_sweep)")
    ap.add_argument("--remat-policy", default=None,
                    choices=["off", "full", "dots", "dots_no_batch"],
                    help="scan-body checkpoint policy "
                         "(TrainConfig.remat_policy)")
    ap.add_argument("--scan-rounds", type=int, default=0,
                    help="fuse N rounds per dispatch via the scan engine "
                         "(P2 pre-scheduled for the whole span in one "
                         "batched solver call; DESIGN.md §11)")
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--optimizer", default="sgd",
                    help="sgd | momentum | adam — moments live as sharded "
                         "(n_chunks, D_c) carries in the zoo round "
                         "(DESIGN.md §17)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="per-worker EF residual over the 1-bit uplink "
                         "(Stich et al.; DESIGN.md §11/§17). Needs "
                         "--agg obcsaa")
    ap.add_argument("--data", default=None,
                    help="token-shard directory (repro.data.TokenShards) "
                         "— with --zoo-train, each round samples a fresh "
                         "per-worker batch keyed by the absolute round "
                         "index; default: fixed synthetic streams")
    ap.add_argument("--cs-chunk", type=int, default=1024)
    ap.add_argument("--cs-measure", type=int, default=256)
    ap.add_argument("--cs-topk", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="also snapshot params+opt every N steps (0: only "
                         "the final step); scan mode snapshots at chunk "
                         "boundaries whenever --ckpt-dir is set")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest step from --ckpt-dir and "
                         "continue; round RNG/schedules index absolute "
                         "steps, so the result matches an uninterrupted "
                         "run (DESIGN.md §14)")
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir")
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    mesh = make_host_mesh()
    tcfg = TrainConfig(aggregation=args.agg, optimizer=args.optimizer,
                       learning_rate=args.lr,
                       error_feedback=args.error_feedback,
                       cs_chunk=args.cs_chunk,
                       cs_measure=args.cs_measure, cs_topk=args.cs_topk,
                       biht_iters=10, cs_packed=args.zoo_train,
                       remat_policy=args.remat_policy)
    model = build_model(cfg)
    if args.zoo_train:
        # NOTE: no ambient set_mesh — the zoo round owns its shard_map and
        # the model forward runs fully manual inside it (DESIGN.md §16)
        return run_zoo_train(args, cfg, tcfg, model, mesh)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        opt = steps_lib.make_optimizer(tcfg)
        opt_state = opt.init(params)
        t_start = 0
        if args.resume:
            restored = steps_lib.restore_train_state(args.ckpt_dir, model,
                                                     tcfg, mesh)
            if restored is not None:
                params, opt_state, t_start = restored
                print(f"resumed from step {t_start}", flush=True)
        batch = make_batch(cfg, args.batch, args.seq)
        if args.scan_rounds > 0:
            # scan engine: one dispatch per n-round chunk, channels +
            # schedules precomputed for the whole run in one batched P2
            # solve (DESIGN.md §11)
            n = args.scan_rounds
            D = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(params))
            span = steps_lib.make_scheduled_round_span(
                mesh, tcfg, D, args.steps)
            scan_steps = {}   # chunk length -> jitted program (full + tail)

            def run_chunk(t0_round, m):
                if m not in scan_steps:
                    scan_steps[m] = jax.jit(
                        steps_lib.make_scan_train_step(model, tcfg, mesh,
                                                       m),
                        donate_argnums=(0, 1))
                ctxs = jax.tree_util.tree_map(
                    lambda x: x[t0_round:t0_round + m], span)
                return scan_steps[m](params, opt_state, batch, ctxs)

            if t_start % n:
                raise SystemExit(
                    f"--resume step {t_start} does not land on a "
                    f"--scan-rounds {n} chunk boundary; rerun with the "
                    f"cadence the checkpoints were saved with")
            for t0_round in range(0, args.steps, n):
                m = min(n, args.steps - t0_round)
                if t0_round + m <= t_start:
                    continue
                t0 = time.time()
                params, opt_state, metrics = run_chunk(t0_round, m)
                loss = float(metrics["loss"][-1])
                print(f"rounds {t0_round:4d}..{t0_round + m - 1} "
                      f"loss={loss:.4f} ({time.time()-t0:.2f}s)",
                      flush=True)
                if args.ckpt_dir:
                    steps_lib.save_train_state(args.ckpt_dir, t0_round + m,
                                               params, opt_state)
        else:
            step = jax.jit(steps_lib.make_train_step(model, tcfg, mesh),
                           donate_argnums=(0, 1))
            for t in range(t_start, args.steps):
                ctx = steps_lib.default_round_ctx(mesh, seed=t)
                t0 = time.time()
                params, opt_state, metrics = step(params, opt_state,
                                                  batch, ctx)
                loss = float(metrics["loss"])
                print(f"step {t:4d} loss={loss:.4f} "
                      f"({time.time()-t0:.2f}s)", flush=True)
                if args.ckpt_dir and args.ckpt_every \
                        and (t + 1) % args.ckpt_every == 0:
                    steps_lib.save_train_state(args.ckpt_dir, t + 1,
                                               params, opt_state)
        if args.ckpt_dir:
            path = steps_lib.save_train_state(args.ckpt_dir, args.steps,
                                              params, opt_state)
            print(f"saved checkpoint: {path}")


if __name__ == "__main__":
    main()
