"""Path driver: the zoo-train round of a Zamba2 hybrid model over model
shards, ``ZooTrainRound.round_train`` built by
``repro.launch.steps.make_zoo_train_round`` as ``repro.launch.train``
builds it, on a mesh of ``workers`` x ``model_shards``.

As ``zoo_train``'s driver (set-up, the first three rounds, the window),
with the model read from the configuration file's own keys (those of the
model's ``config.json``), the round's state made on the mesh, and the
plain reference of ``perfbench.reference.zamba2`` over the same three
rounds in the round's flat order over the model shards, its arrays placed
over the cell's chips. The probes add ``probe.shared_block``: one call of
a shared block as the round runs it (its rows of the round's master,
gathered by the round's resolver, the block, its adapter and ``linear``),
forward and backward under the round's remat, on the round's mesh.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench.drivers import zoo_train
from perfbench.reference import compare
from perfbench.reference import zamba2 as ref

STEPS = zoo_train.STEPS


def program_config(c: dict):
    """The program's ModelConfig from a configuration file's keys."""
    from repro.configs import AttentionConfig, SSMConfig, get_config
    hd = c["attention_head_dim"]
    return dataclasses.replace(
        get_config("zamba2-7b"), num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], d_ff=c["ffn_hidden_size"],
        vocab_size=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        dtype=c["dtype"], hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
        num_mem_blocks=c["num_mem_blocks"], adapter_rank=c["adapter_rank"],
        tie_embeddings=True,
        ssm=SSMConfig(d_state=c["mamba_d_state"], head_dim=c["mamba_headdim"],
                      expand=c["mamba_expand"], n_groups=c["mamba_ngroups"],
                      chunk_size=c["chunk_size"], conv_width=c["mamba_d_conv"]),
        attention=AttentionConfig(
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=hd,
            rope_theta=float(c["rope_theta"]),
            softmax_scale=(hd / 2) ** -0.5))


class Driver(zoo_train.Driver):
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.rnd = self.cfg["round"]
        self.dims = ref.Dims.from_config(self.cfg)
        self.U = self.rnd["workers"]
        self.mp = self.rnd["model_shards"]
        self.B = self.traffic["batch"]
        self.S = self.traffic["seq"]
        self.keys = zoo_train.seed_keys(ctx.seed)
        self.shapes = jax.eval_shape(lambda k: ref.init_params(k, self.dims),
                                     jax.random.PRNGKey(0))
        self.layout = ref.Layout(self.shapes, self.rnd["chunk"],
                                 self.U * self.rnd["pad_chunks_to"], self.mp)
        self.measures = {}
        self._tok = jax.jit(lambda k, t: ref.make_tokens(
            k, t, self.U, self.B, self.S, self.cfg["vocab_size"]))
        self._ref = None

    # -- the program -----------------------------------------------------

    def build(self):
        from repro.configs import TrainConfig
        from repro.launch import steps
        from repro.launch.mesh import make_zoo_mesh
        from repro.models.registry import build_model
        r = self.rnd
        self.mcfg = program_config(self.cfg)
        self.tcfg = TrainConfig(
            aggregation="obcsaa", optimizer=r["optimizer"],
            learning_rate=r["lr"], error_feedback=r["error_feedback"],
            cs_chunk=r["chunk"], cs_measure=r["measure"],
            cs_topk=r["topk"], biht_iters=r["biht_iters"],
            cs_tau=r["tau"], cs_packed=r["packed"],
            remat_policy=r["remat"], noise_var=r["noise_var"],
            p_max=r["p_max"])
        mesh = make_zoo_mesh(self.U, self.mp)
        self.model = build_model(self.mcfg)
        self.zr = steps.make_zoo_train_round(self.model, self.tcfg, mesh)
        zr, dims = self.zr, self.dims
        if (zr.n_chunks, zr.D) != (self.layout.n_chunks, self.layout.D):
            raise ValueError(
                f"the round's layout ({zr.n_chunks} chunks, D {zr.D}) is "
                f"not the reference's ({self.layout.n_chunks}, "
                f"{self.layout.D})")
        shard = zr.state_shardings()
        self._master0 = jax.jit(
            lambda k: zr.chunk_params(ref.init_params(k, dims)),
            out_shardings=shard.master)
        self._state0 = jax.jit(
            lambda k: zr.init_state(zr.chunk_params(
                ref.init_params(k, dims))), out_shardings=shard)
        self._norms = jax.jit(self.layout.leaf_norms)
        self._change = jax.jit(lambda a, b: self.layout.leaf_norms(a - b))

    def first_steps(self):
        """The first three rounds from the seed's weights, through the
        window's own call; keeps their readings and the state."""
        state = self._state0(self.keys["weights"])
        losses = []
        for t in range(STEPS):
            state, st = self.step(state, t)
            losses.append(float(st.loss))
            if t == 0:
                b1 = 0.9   # adam's first moment: m_1 = (1 - b1) g_1
                grad = np.asarray(self._norms(state.opt["m"])) / (1 - b1)
                # the gradient less what the codec sent, on the host, each
                # leaf's entries together
                residual = self.layout.tree_order(
                    np.asarray(state.residual).reshape(self.U, -1))
        change = np.asarray(self._change(
            state.master, self._master0(self.keys["weights"])))
        self.readings = {"loss": losses, "grad": grad.tolist(),
                         "change": change.tolist(), "residual": residual}
        self.state, self.t = state, STEPS
        self.nonfinite = sum(not math.isfinite(x) for x in losses)

    # -- the probes ------------------------------------------------------

    def _shared_block_program(self):
        """The probe's program: forward and backward of call 0 of shared
        block 0 as the round runs it, on the round's mesh, from the
        round's master and [h, embedding]. Its weights are that block's
        and that call's rows of the master, cast and sliced as the round
        reads its section and gathered by the round's own layer resolver
        inside the remat."""
        from repro.dist import collectives as coll
        from repro.models import transformer
        zr, cfg = self.zr, self.mcfg
        positions = jnp.arange(self.S, dtype=jnp.int32)

        def row(tree):
            return jax.tree_util.tree_map(lambda a: a[0], tree)

        def body(pl, xe):
            sect = coll.all_gather(pl.astype(zr.compute_dtype), zr.waxes,
                                   tiled=True)
            p = zr.layout.section_to_tree(sect)
            w = (row(p["shared_blocks"]), row(p["hybrid"]))

            def loss(w, xe):
                t, _ = transformer.hybrid_call(
                    zr._layer_resolver(w[0]), zr._layer_resolver(w[1]),
                    xe[0], xe[1], cfg, positions)
                return jnp.sum(t.astype(jnp.float32))

            val, grads = jax.value_and_grad(jax.checkpoint(loss),
                                            argnums=(0, 1))(w, xe)
            # each device's value and gradient shards, one row a device
            return jax.tree_util.tree_map(lambda a: a[None], (val, grads))

        return jax.jit(jax.shard_map(
            body, mesh=zr.mesh, in_specs=(zr.spec, P()),
            out_specs=P(tuple(zr.mesh.axis_names)), check_vma=False))

    def _shared_block_call(self):
        """The probe's call on the round's current master and seeded
        [h, embedding] activations."""
        fn, zr = self._shared_block_program(), self.zr
        xe = jax.jit(lambda k: (0.5 * jax.random.normal(
            k, (2, self.B, self.S, self.mcfg.d_model))).astype(
                zr.compute_dtype),
            out_shardings=NamedSharding(zr.mesh, P()))(jax.random.PRNGKey(0))
        master = self.state.master
        return lambda: fn(master, xe)

    def probes(self, span, min_span_s: float = 0.25, samples: int = 10):
        """``zoo_train``'s model probe, then ``samples`` timings of
        back-to-back calls of one shared block's call, forward and
        backward, ms per call, each span at least ``min_span_s``."""
        super().probes(span, min_span_s, samples)
        call = self._shared_block_call()
        jax.block_until_ready(call())
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        per = max(1, math.ceil(min_span_s / (time.perf_counter() - t0)))
        out = []
        for _ in range(samples):
            with span("probe.shared_block"):
                t0 = time.perf_counter()
                for _ in range(per):
                    g = call()
                jax.block_until_ready(g)
                out.append(1e3 * (time.perf_counter() - t0) / per)
            del g
        self.measures["shared_block_ms"] = out

    # -- the reference ---------------------------------------------------

    def _reference_programs(self):
        """The reference's jitted programs, made once per driver: the
        policy, the positions in the loss, the round and its key are
        arguments, so that every seed, the control and the planted fault
        run the same compiled programs. Their arrays lie over the round's
        chips: rows of the flat layout by model shard, each weight along
        its split dim."""
        if self._ref is not None:
            return self._ref
        r, dims = self.rnd, self.dims
        devs = np.asarray(self.zr.mesh.devices).reshape(-1)
        mesh = Mesh(devs, ("x",)) if devs.size > 1 else None
        lay = ref.Layout(self.shapes, r["chunk"], self.U * r["pad_chunks_to"],
                         self.mp, mesh=mesh)
        hp = ref.Hyper(r["chunk"], r["measure"], r["topk"],
                       r["decode_topk"], r["biht_iters"], r["tau"],
                       r["phi_seed"], r["noise_var"], r["p_max"], r["lr"])

        def place(spec):
            return None if mesh is None else NamedSharding(mesh, spec)

        rows, urows = place(P("x", None)), place(P(None, "x", None))
        carry_sh = None if mesh is None else ref.Carry(rows, rows, rows,
                                                       urows)
        block = r["pad_chunks_to"]
        self._ref = dict(
            layout=lay,
            master0=jax.jit(lambda k: lay.to_master(ref.init_params(k, dims)),
                            out_shardings=rows),
            grads=jax.jit(lambda mst, tok, keep, low: ref.grads(
                mst, tok[..., :-1], tok[..., 1:], lay, dims,
                ref.switched(low), keep), out_shardings=(None, urows)),
            step=jax.jit(lambda c, g, t, key: ref.codec_step(
                c, g, t, key, hp, block, self.mp), donate_argnums=(0,),
                out_shardings=(carry_sh, rows)),
            init=jax.jit(lambda m: ref.init_carry(m, self.U),
                         out_shardings=carry_sh),
            norms=jax.jit(lay.leaf_norms),
            change=jax.jit(lambda a, b: lay.leaf_norms(a - b)))
        return self._ref

    def reference(self, policy: str, keep_half: bool = False):
        """The reference's reading over the first three rounds, in
        ``policy`` (float32 or the fp8 control); ``keep_half`` leaves the
        second half of every sequence out of the loss (a planted fault)."""
        if policy not in ("float32", "fp8"):
            raise ValueError(f"no reference policy {policy!r}")
        f = self._reference_programs()
        lay = f["layout"]
        low = jnp.asarray(policy == "fp8")
        keep = jnp.arange(self.S) < (self.S // 2 if keep_half else self.S)
        with jax.default_matmul_precision("highest"):
            carry = f["init"](f["master0"](self.keys["weights"]))
            losses, gmax = [], None
            for t in range(STEPS):
                loss_u, g = f["grads"](carry.master,
                                       self._tok(self.keys["tokens"], t),
                                       keep, low)
                carry, ghat = f["step"](carry, g, t, self.keys["round"])
                del g
                losses.append(float(jnp.mean(loss_u)))
                gn = np.asarray(f["norms"](ghat))
                if t == 0:
                    grad = gn
                    residual = lay.tree_order(
                        np.asarray(carry.residual).reshape(self.U, -1))
                gmax = gn if gmax is None else np.maximum(gmax, gn)
                del ghat
            ch = np.asarray(f["change"](carry.master,
                                        f["master0"](self.keys["weights"])))
        return {"loss": losses, "grad": grad.tolist(), "change": ch.tolist(),
                "ref_grad_max": gmax.tolist(), "residual": residual,
                "leaves": list(zip(lay.offsets, lay.sizes))}

    def check(self):
        want = self.reference(self.cfg["reference_policy"])
        return compare.numbers(self.readings, want), want
