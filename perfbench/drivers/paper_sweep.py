"""Path driver: the paper's round through the engine's sweep,
``EngineRun.run_sweep``, what ``repro.engine.run_sweep`` calls.

The sweep runs its rounds as scan chunks that end where it evaluates:
after round 0, every ``eval_every``-th round and the last. Set-up makes
the worker data, a test set and the weights from the seed on the device,
builds the engine and drives it with the window's own call and chunk
programs through its first round, then through the first chunk boundary
past the third round, keeping the readings the check compares, and warms
the remaining chunk length with one whole sweep. The window runs whole
sweeps back to back. The check runs the plain reference of
``perfbench.reference.mnist`` over the same rounds.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import codec, compare
from perfbench.reference import mnist as ref

STEPS = 3


def eval_points(rounds: int, every: int):
    """Rounds after which a sweep evaluates: round 0, every ``every``-th
    round and the last."""
    return sorted({t for t in range(rounds) if t % every == 0}
                  | {rounds - 1})


def seed_keys(seed: int):
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
    return {name: jax.random.fold_in(base, i)
            for i, name in enumerate(("weights", "data", "probe"))}


def first_update(p0, p1, lr):
    """The first round's update as one flat float64 vector, (p0 - p1) / lr,
    worked out from the parameters alike on both sides."""
    return (np.asarray(ref.flatten(p0), np.float64)
            - np.asarray(ref.flatten(p1), np.float64)) / lr


def leaf_norms(tree):
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree_util.tree_leaves(tree)]


class Driver:
    # planted faults, as keyword arguments of ``reference``: half of every
    # worker's samples left out of its gradient; the schedule of each round
    # worked out from the next round's channel (an answer altered where it
    # is produced)
    FAULTS = {"half_batch": {"keep_half": True},
              "stale_csi": {"stale_csi": True}}

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.rnd = self.cfg["round"]
        self.keys = seed_keys(ctx.seed)
        # the engine's per-arm key is PRNGKey(arm seed), a 31-bit seed
        self.arm_seed = ctx.seed % (2 ** 31 - 1)
        points = eval_points(self.traffic["sweep_rounds"],
                             self.traffic["eval_every"])
        # the rounds compared: through the first chunk boundary that
        # covers the first three, with the losses evaluated on the way
        self.steps = 1 + min(t for t in points if t >= STEPS - 1)
        self.loss_rounds = [t for t in points if t < self.steps]
        self.measures = {}
        self.nonfinite = 0

    def data(self):
        r, tr = self.rnd, self.traffic
        return jax.jit(ref.make_data, static_argnums=(1, 2, 3))(
            self.keys["data"], r["workers"], r["samples_per_worker"],
            tr["eval_samples"])

    def setup(self):
        jax.config.update("jax_default_matmul_precision",
                          self.cfg["matmul_precision"])
        from repro.core.obcsaa import OBCSAAConfig
        from repro.engine import EngineRun, FLConfig, make_arms
        from repro.models.mlp_mnist import mlp_mnist_accuracy, mlp_mnist_loss
        r, tr = self.rnd, self.traffic
        data, xte, yte = self.data()
        self.params0 = jax.jit(ref.init_params)(self.keys["weights"])
        ob = OBCSAAConfig(chunk=r["chunk"], measure=r["measure"],
                          topk=r["topk"], biht_iters=r["biht_iters"],
                          recon_tau=r["tau"], noise_var=r["noise_var"],
                          p_max=r["p_max"], phi_seed=r["phi_seed"],
                          packed=r["packed"])
        self.ob = ob
        self.flcfg = FLConfig(aggregator="obcsaa", scheduler=r["scheduler"],
                              learning_rate=r["lr"],
                              rounds=tr["sweep_rounds"],
                              eval_every=tr["eval_every"], obcsaa=ob)
        self.run = EngineRun(
            self.flcfg, lambda p, d: mlp_mnist_loss(p, d["x"], d["y"]),
            self.params0, data,
            np.full(r["workers"], float(r["samples_per_worker"])),
            eval_fn=jax.jit(lambda p: (mlp_mnist_loss(p, xte, yte),
                                       mlp_mnist_accuracy(p, xte, yte))))
        self.arms = make_arms(self.flcfg, seeds=[self.arm_seed])
        self.first_steps()
        # one whole sweep warms the chunk lengths the first steps did not
        self.run.run_sweep(self.arms)

    def first_steps(self):
        """The first round, then the rounds through the first chunk
        boundary past the third, through ``run_sweep`` at the window's
        cadence; keeps their readings."""
        r = self.rnd
        one = self.run.run_sweep(self.arms, rounds=1)
        first = self.run.run_sweep(self.arms, rounds=self.steps)
        p0 = self.params0
        p1 = jax.tree_util.tree_map(lambda x: x[0], one["params"])
        pn = jax.tree_util.tree_map(lambda x: x[0], first["params"])
        lr = r["lr"]
        self.readings = {
            "loss": np.asarray(first["loss"][0], np.float64).tolist(),
            "grad": [g / lr for g in leaf_norms(
                jax.tree_util.tree_map(jnp.subtract, p0, p1))],
            "change": leaf_norms(jax.tree_util.tree_map(jnp.subtract, pn,
                                                        p0)),
            "b_t": np.asarray(first["b_t"][0], np.float64).tolist(),
            "update": first_update(p0, p1, lr)}
        self.nonfinite = sum(not math.isfinite(x)
                             for x in self.readings["loss"])

    def window(self, seconds: float, span):
        """Whole sweeps back to back until the first sweep boundary after
        ``seconds``."""
        rounds, sweeps = 0, 0
        t0 = time.perf_counter()
        while True:
            with span("sweep"):
                out = self.run.run_sweep(self.arms)
            sweeps += 1
            rounds += int(out["n_scheduled"].shape[1])
            self.nonfinite += int(np.sum(~np.isfinite(out["loss"])))
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        return {"units": sweeps, "seconds": t1 - t0, "rounds": rounds,
                "metrics": {"paper_rounds_per_s": rounds / (t1 - t0)}}

    def probes(self, span, min_span_s: float = 0.25, samples: int = 10):
        """The server's decode alone: ``reconstruct_chunks`` at the
        round's geometry (every chunk of D, its BIHT settings), timings of
        enough back-to-back calls to span ``min_span_s`` each, in ms per
        call."""
        from repro.core.obcsaa import reconstruct_chunks
        D = sum(x.size for x in jax.tree_util.tree_leaves(self.params0))
        n = -(-D // self.ob.chunk)
        ky, km = jax.random.split(self.keys["probe"])
        y = jax.random.normal(ky, (n, self.ob.measure))
        mags = jax.random.uniform(km, (n,), minval=0.5, maxval=1.5)
        ob = self.ob
        dec = jax.jit(lambda y, m: reconstruct_chunks(ob, y, m))
        jax.block_until_ready(dec(y, mags))
        t0 = time.perf_counter()
        jax.block_until_ready(dec(y, mags))
        per = max(1, math.ceil(min_span_s / (time.perf_counter() - t0)))
        out = []
        for _ in range(samples):
            with span("probe.decode"):
                t0 = time.perf_counter()
                for _ in range(per):
                    x = dec(y, mags)
                jax.block_until_ready(x)
                out.append(1e3 * (time.perf_counter() - t0) / per)
        self.measures["decode_ms"] = out

    def free(self):
        self.run = None

    # -- the reference ---------------------------------------------------

    def reference(self, policy: str, keep_half: bool = False,
                  stale_csi: bool = False):
        """The reference's reading over the rounds the program's is taken
        from, its matmuls at ``policy`` precision (highest, or the three
        bfloat16 passes of the ``high`` control); ``keep_half`` and
        ``stale_csi`` plant the faults of ``FAULTS``."""
        r = self.rnd
        hp = ref.Hyper(r["chunk"], r["measure"], r["topk"],
                       r["decode_topk"], r["biht_iters"], r["tau"],
                       r["phi_seed"], r["noise_var"], r["p_max"], r["lr"])
        an = ref.Analysis(**self.cfg["analysis"])
        K = np.full(r["workers"], float(r["samples_per_worker"]))
        key = jax.random.PRNGKey(self.arm_seed)
        keep = ((jnp.arange(r["samples_per_worker"])
                 < r["samples_per_worker"] // 2) if keep_half else None)
        mm = codec.MATMULS[policy]
        with jax.default_matmul_precision("highest"):
            data, xte, yte = self.data()
            params = jax.jit(ref.init_params)(self.keys["weights"])
            p0 = params
            D = sum(x.size for x in jax.tree_util.tree_leaves(params))
            # data as arguments, not constants: the programs stay small
            grads = jax.jit(lambda p, d: ref.grads(p, d, keep, mm))
            step = jax.jit(lambda p, g, w, b, t: ref.codec_step(
                p, g, w, b, t, key, hp, mm))
            evaluate = jax.jit(lambda p, x, y: ref.loss(p, x, y, mm=mm))
            fades = jax.jit(lambda t: ref.round_fades(key, t,
                                                      r["workers"]))
            losses, bts, gmax = [], [], None
            for t in range(self.steps):
                h = np.asarray(fades(t + stale_csi))
                beta, b_t = ref.schedule(h, K, r["p_max"], r["noise_var"],
                                         D, r["measure"], r["topk"], an)
                _, g = grads(params, data)
                params, ghat = step(params, g, jnp.asarray(K * beta,
                                                           jnp.float32),
                                    jnp.float32(b_t), t)
                if t in self.loss_rounds:
                    losses.append(float(evaluate(params, xte, yte)))
                bts.append(float(b_t))
                gn = np.asarray(leaf_norms(ref.unflatten(ghat, params)))
                if t == 0:
                    grad = gn
                    update = first_update(p0, params, r["lr"])
                gmax = gn if gmax is None else np.maximum(gmax, gn)
            change = leaf_norms(jax.tree_util.tree_map(jnp.subtract,
                                                       params, p0))
        return {"loss": losses, "grad": grad.tolist(), "change": change,
                "ref_grad_max": gmax.tolist(), "b_t": bts,
                "update": update, "chunk": r["chunk"]}

    def check(self):
        want = self.reference(self.cfg["reference_policy"])
        return compare.numbers(self.readings, want), want
