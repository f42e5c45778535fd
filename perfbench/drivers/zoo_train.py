"""Path driver: the zoo-train round, ``ZooTrainRound.round_train``, built
by ``repro.launch.steps.make_zoo_train_round`` as ``repro.launch.train``
builds it.

Set-up makes the weights and every round's tokens from the seed on the
device, builds the round, and drives it through its first three rounds
with the window's own call, keeping the readings the check compares. The
window then continues from that state: one fresh batch per round, one
round in flight. The check runs the plain reference of
``perfbench.reference.mamba2`` over the same three rounds, after the
program's state is freed.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import compare
from perfbench.reference import mamba2 as ref

STEPS = 3


def seed_keys(seed: int):
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
    return {name: jax.random.fold_in(base, i)
            for i, name in enumerate(("weights", "tokens", "round"))}


class Driver:
    # planted faults, as keyword arguments of ``reference``: the second
    # half of every sequence left out of the loss
    FAULTS = {"half_batch": {"keep_half": True}}

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.rnd = self.cfg["round"]
        self.dims = ref.Dims.from_config(self.cfg["model"])
        self.U = self.rnd["workers"]
        self.B = self.traffic["batch"]
        self.S = self.traffic["seq"]
        self.keys = seed_keys(ctx.seed)
        self.layout = ref.Layout(
            jax.eval_shape(lambda k: ref.init_params(k, self.dims),
                           jax.random.PRNGKey(0)),
            self.rnd["chunk"], self.U * self.rnd["pad_chunks_to"])
        self.measures = {}
        # ids below the source's vocab_size; the embedding and head hold
        # its padded rows, ``vocab``
        self._tok = jax.jit(lambda k, t: ref.make_tokens(
            k, t, self.U, self.B, self.S, self.cfg["model"]["vocab_size"]))

    # -- the program -----------------------------------------------------

    def build(self):
        from repro.configs import SSMConfig, TrainConfig, get_config
        from repro.launch import steps
        from repro.launch.mesh import make_zoo_mesh
        from repro.models.registry import build_model
        r, m = self.rnd, self.cfg["model"]
        # every size as this configuration file states it
        mcfg = dataclasses.replace(
            get_config(m["arch"]), num_layers=m["n_layers"],
            d_model=m["d_model"], vocab_size=m["vocab"],
            norm_eps=m["norm_eps"], dtype=m["dtype"],
            ssm=SSMConfig(d_state=m["d_state"], head_dim=m["head_dim"],
                          expand=m["expand"], n_groups=m["n_groups"],
                          chunk_size=m["chunk_size"],
                          conv_width=m["conv_width"]))
        self.tcfg = TrainConfig(
            aggregation="obcsaa", optimizer=r["optimizer"],
            learning_rate=r["lr"], error_feedback=r["error_feedback"],
            cs_chunk=r["chunk"], cs_measure=r["measure"],
            cs_topk=r["topk"], biht_iters=r["biht_iters"],
            cs_tau=r["tau"], cs_packed=r["packed"],
            remat_policy=r["remat"], noise_var=r["noise_var"],
            p_max=r["p_max"])
        mesh = make_zoo_mesh(self.U, r["model_shards"])
        self.zr = steps.make_zoo_train_round(build_model(mcfg), self.tcfg,
                                             mesh)
        zr, dims = self.zr, self.dims
        self._master0 = jax.jit(
            lambda k: zr.chunk_params(ref.init_params(k, dims)))
        self._norms = jax.jit(self.layout.leaf_norms)
        self._change = jax.jit(lambda a, b: self.layout.leaf_norms(a - b))

    def batch(self, t):
        seq = self._tok(self.keys["tokens"], t)
        return self.zr.shard_batch({"tokens": seq[..., :-1],
                                    "targets": seq[..., 1:]})

    def step(self, state, t):
        r = self.rnd
        return self.zr.round_train(state, self.batch(t), t,
                                   self.keys["round"], r["noise_var"],
                                   r["p_max"], r["lr"])

    def setup(self):
        """Build, then the first three rounds through ``round_train``."""
        self.build()
        self.first_steps()

    def first_steps(self):
        """The first three rounds from the seed's weights, through the
        window's own call; keeps their readings and the state."""
        zr = self.zr
        state = zr.shard_state(zr.init_state(self._master0(
            self.keys["weights"])))
        losses = []
        for t in range(STEPS):
            state, st = self.step(state, t)
            losses.append(float(st.loss))
            if t == 0:
                b1 = 0.9   # adam's first moment: m_1 = (1 - b1) g_1
                grad = np.asarray(self._norms(state.opt["m"])) / (1 - b1)
                # the gradient less what the codec sent, on the host
                residual = np.asarray(state.residual).reshape(self.U, -1)
        change = np.asarray(self._change(
            state.master, self._master0(self.keys["weights"])))
        self.readings = {"loss": losses, "grad": grad.tolist(),
                         "change": change.tolist(), "residual": residual}
        self.state, self.t = state, STEPS
        self.nonfinite = sum(not math.isfinite(x) for x in losses)

    # -- the window ------------------------------------------------------

    def window(self, seconds: float, span):
        """Rounds back to back, one in flight, until the first round
        boundary after ``seconds``. Returns the work and its time."""
        done, pending = 0, None
        t0 = time.perf_counter()
        while True:
            with span("batch"):
                batch = self.batch(self.t)
            with span("dispatch"):
                r = self.rnd
                self.state, st = self.zr.round_train(
                    self.state, batch, self.t, self.keys["round"],
                    r["noise_var"], r["p_max"], r["lr"])
            self.t += 1
            if pending is not None:
                with span("block"):
                    loss = float(pending.loss)
                done += 1
                self.nonfinite += not math.isfinite(loss)
                if time.perf_counter() - t0 >= seconds:
                    with span("block"):
                        loss = float(st.loss)
                    done += 1
                    self.nonfinite += not math.isfinite(loss)
                    break
            pending = st
        t1 = time.perf_counter()
        tokens = done * self.U * self.B * self.S
        return {"units": done, "seconds": t1 - t0, "tokens": tokens,
                "metrics": {"train_tokens_per_s": tokens / (t1 - t0)}}

    def probes(self, span, min_span_s: float = 0.25, samples: int = 10):
        """The model's forward and backward alone:
        ``ZooTrainRound.grads_in_layout`` on the current master and a
        batch, ``samples`` timings of enough back-to-back calls to span
        ``min_span_s`` each, in ms per call."""
        batch = self.batch(self.t)
        master = self.state.master
        jax.block_until_ready(self.zr.grads_in_layout(master, batch))
        t0 = time.perf_counter()
        jax.block_until_ready(self.zr.grads_in_layout(master, batch))
        per = max(1, math.ceil(min_span_s / (time.perf_counter() - t0)))
        out = []
        for _ in range(samples):
            with span("probe.model_grad"):
                t0 = time.perf_counter()
                for _ in range(per):
                    g = self.zr.grads_in_layout(master, batch)
                jax.block_until_ready(g)
                out.append(1e3 * (time.perf_counter() - t0) / per)
            del g
        self.measures["model_grad_ms"] = out

    def free(self):
        """Drop the program's state; its compiled programs stay."""
        self.state = None

    def reseed(self, seed: int):
        self.keys = seed_keys(seed)

    # -- the reference ---------------------------------------------------

    def reference(self, policy: str, keep_half: bool = False):
        """The reference's reading over the first three rounds, in
        ``policy`` (float32 or the fp8 control); ``keep_half`` leaves the
        second half of every sequence out of the loss (a planted fault)."""
        r, dims, lay = self.rnd, self.dims, self.layout
        hp = ref.Hyper(r["chunk"], r["measure"], r["topk"],
                       r["decode_topk"], r["biht_iters"], r["tau"],
                       r["phi_seed"], r["noise_var"], r["p_max"], r["lr"])
        q = ref.POLICIES[policy]
        keep = (jnp.arange(self.S) < self.S // 2) if keep_half else None
        master0 = jax.jit(lambda k: lay.to_master(ref.init_params(k, dims)))
        grads = jax.jit(lambda mst, tok: ref.grads(
            mst, tok[..., :-1], tok[..., 1:], lay, dims, q, keep))
        block = r["pad_chunks_to"]
        step = jax.jit(lambda c, g, t: ref.codec_step(
            c, g, t, self.keys["round"], hp, block), donate_argnums=(0,))
        norms = jax.jit(lay.leaf_norms)
        change = jax.jit(lambda a, b: lay.leaf_norms(a - b))
        with jax.default_matmul_precision("highest"):
            carry = ref.init_carry(master0(self.keys["weights"]), self.U)
            losses, gmax = [], None
            for t in range(STEPS):
                loss_u, g = grads(carry.master,
                                  self._tok(self.keys["tokens"], t))
                carry, ghat = step(carry, g, t)
                del g
                losses.append(float(jnp.mean(loss_u)))
                gn = np.asarray(norms(ghat))
                if t == 0:
                    grad = gn
                    residual = np.asarray(carry.residual).reshape(self.U,
                                                                  -1)
                gmax = gn if gmax is None else np.maximum(gmax, gn)
                del ghat
            ch = np.asarray(change(carry.master,
                                   master0(self.keys["weights"])))
        return {"loss": losses, "grad": grad.tolist(), "change": ch.tolist(),
                "ref_grad_max": gmax.tolist(), "residual": residual,
                "leaves": list(zip(lay.offsets, lay.sizes))}

    def check(self):
        """(numbers, reference reading) of the program against the
        float32 reference."""
        want = self.reference(self.cfg["reference_policy"])
        return compare.numbers(self.readings, want), want
