"""OBCSAA as a first-class distributed-training feature: train a reduced
gemma2 on an 8-device host mesh where each data shard is an FL worker and
gradient aggregation happens "over the air" (psum + AWGN + BIHT decode).

  PYTHONPATH=src python examples/distributed_obcsaa.py --steps 5
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import TrainConfig, get_smoke_config
from repro.data import token_stream
from repro.launch import steps as steps_lib
from repro.launch.mesh import auto_mesh
from repro.models.registry import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--agg", default="obcsaa", choices=["obcsaa", "mean"])
    args = ap.parse_args()

    mesh = auto_mesh((4, 2), ("data", "model"))
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    tcfg = TrainConfig(aggregation=args.agg, cs_chunk=1024, cs_measure=256,
                       cs_topk=64, biht_iters=10, learning_rate=0.02)
    print(f"mesh: {dict(mesh.shape)}  workers = data axis = 4  "
          f"tensor-parallel = model axis = 2")
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        opt = steps_lib.make_optimizer(tcfg)
        ostate = opt.init(params)
        step = jax.jit(steps_lib.make_train_step(model, tcfg, mesh),
                       donate_argnums=(0, 1))
        toks, tgts = token_stream(8, 64, cfg.vocab_size)
        batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgts)}
        for t in range(args.steps):
            ctx = steps_lib.default_round_ctx(mesh, seed=t)
            t0 = time.time()
            params, ostate, m = step(params, ostate, batch, ctx)
            print(f"step {t}: loss={float(m['loss']):.4f} "
                  f"({time.time()-t0:.2f}s)")


if __name__ == "__main__":
    main()
