"""repro.engine.zoo_train — REAL sharded backward passes at zoo scale
(DESIGN.md §16) with stateful optimization carries (DESIGN.md §17).

engine/zoo.py proves the ≥1B-parameter compress→MAC→decode→update round
but drives it with surrogate gradients; this module closes the gap: the
genuine eq. 3 local gradients of a scanned-stacked-layer model, computed
parameter-sharded on the same workers×model mesh, flow into the SAME
round tail with nothing dense at full D ever replicated and zero layout
communication between the backward pass and the compressor.

The scheme (one ``jax.shard_map`` program over the whole mesh):

* The master lives as the zoo round's chunked ``(n_chunks, D_c)`` f32
  array, but its flat order is the :class:`~repro.dist.flat_layout
  .FlatShardLayout` model-major sharded-flat order: section m holds the
  m-th model-axis slice of every leaf. Device (worker d, model m) owns
  chunk rows ``m·n_half + d·n_local`` — exactly the slice of section m
  its own backward pass produces.
* Per round, each device casts its master block to the compute dtype and
  all-gathers over the WORKER axes only — materializing its model
  section, never full D — then views it as per-leaf weight shards by
  local reshapes (``section_to_tree``).
* The forward/backward is *redundant over the model axis*: every device
  in a worker column runs the worker's full loss on the worker's batch,
  resolving weight shards to full per-layer weights one scan step at a
  time through ``lm_forward``'s ``layer_resolver`` hook (the hybrid
  family's shared blocks and per-call weights likewise, one call at a
  time; non-stacked leaves — embedding, final norm — are resolved once
  up front). The resolver's collective is ``collectives.replicated_gather``,
  whose adjoint is a LOCAL slice: replicated compute means replicated
  cotangents, so no cross-device float reduction exists anywhere in the
  backward and the round stays bitwise mesh-invariant. Remat policy
  (``TrainConfig.remat_policy``) bounds activation memory: with "full",
  per-layer gathered weights are recomputed, not saved.
* The resulting cotangents have exactly the shard shapes of
  ``section_to_tree``; flattening them back (``tree_to_section``) IS this
  device's (n_half, D_c) gradient block — grads enter ``compress_chunks``
  already in the layout the compressor consumes, with no host round-trip
  and no gather to full D. The MAC/decode tail is inherited unchanged
  from :class:`~repro.engine.zoo.ZooRound`.

The round carry is a :class:`ZooTrainState` (DESIGN.md §17): next to the
master, momentum/adam moments live as FIRST-CLASS sharded carries in the
SAME model-major ``(n_chunks, D_c)`` chunk rows (``repro.optim``'s
``Optimizer.update`` is elementwise, so it steps the shard-local block
inside ``shard_map`` — nothing dense at full D is ever replicated), and
with ``error_feedback=True`` the per-worker Stich-et-al residual extends
to zoo scale as a ``(U, n_chunks, D_c)`` carry in the grads layout: each
device holds its worker's residual rows for its model section, corrects
its gradient block via the shared ``optim.ef_step``, and feeds the
resulting top-κ sparse vector straight into ``compress_chunks``'s fused
``presparsified`` path (no second selection, DESIGN.md §11).

:meth:`ZooTrainRound.reference_round_train` is the jitted single-device
oracle (full params from ``master_to_tree``, identical op chain — EF
correction, compression, MAC, decode, optimizer update — with the
collectives replaced by their local stand-ins) — the bitwise parity
target of tests/test_zoo_train.py covers masters, moments, AND residuals.
:meth:`ZooTrainRound.run_sweep` lifts the multi-arm grid on top: one
jitted ``scan`` over rounds of ``lax.map`` over arms, so arms ×
zoo-scale params compose into one program. :meth:`save_state` /
:meth:`restore_state` checkpoint the FULL carry (master + moments +
residuals) through ``repro.checkpoint``'s template-strict atomic step
dirs, so a mid-sweep restore resumes bit-for-bit.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.obcsaa import OBCSAAConfig, compress_chunks
from repro.core.sparsify import topk_sparsify, topk_sparsify_bisect
from repro.dist import collectives as coll
from repro.dist.flat_layout import FlatShardLayout
from repro.dist.sharding import STACKED_KEYS, param_shard_dims
from repro.engine.zoo import ZooRound, ZooStats
from repro.launch.mesh import num_workers
from repro.optim import optimizers as optim


class ZooTrainStats(NamedTuple):
    """ZooStats plus the mean local training loss (host-visible)."""
    loss: jnp.ndarray
    n_scheduled: jnp.ndarray
    b_t: jnp.ndarray
    ghat_norm: jnp.ndarray
    budget: object


class ZooTrainState(NamedTuple):
    """The zoo-train round carry (DESIGN.md §17).

    ``master``: (n_chunks, D_c) f32 in the sharded-flat layout.
    ``opt``: optimizer moments over the SAME chunk rows — ``()`` for sgd,
    a (n_chunks, D_c) f32 array for momentum, ``{"m", "v", "t"}`` for
    adam — sharded exactly like the master (scalars replicate).
    ``residual``: per-worker EF residual (U, n_chunks, D_c) f32 in the
    grads layout, or None when the round runs without error feedback.
    The leaf structure is FIXED per round build (like ``EngineState``),
    so jitted programs never retrace on the carry."""
    master: jnp.ndarray
    opt: Any
    residual: Optional[jnp.ndarray]


def _with_loss(st: ZooStats, loss) -> ZooTrainStats:
    return ZooTrainStats(loss=loss, n_scheduled=st.n_scheduled, b_t=st.b_t,
                         ghat_norm=st.ghat_norm, budget=st.budget)


class ZooTrainRound(ZooRound):
    """Zoo round whose gradients come from a real sharded backward pass.

    ``model``: a ``repro.models.registry.Model`` whose params pytree is a
    dict (stacked layer collections under ``dist.sharding.STACKED_KEYS``).
    ``optimizer``: a name from ``repro.optim.optimizers.OPTIMIZERS``
    (sgd | momentum | adam); moments become sharded carry leaves next to
    the master. ``error_feedback`` adds the per-worker residual carry
    (DESIGN.md §17). Inherits the surrogate/array-fed programs, layout
    helpers, and the MAC/decode tail from :class:`ZooRound`; adds
    ``round_train`` / ``grads_in_layout`` / ``reference_round_train`` /
    ``run_sweep``. Programs are built lazily per batch structure."""

    def __init__(self, model, mesh, ob: OBCSAAConfig, *,
                 scheduler: str = "all", const=None, sched_cfg=None,
                 block_chunks: int = 64, compute_dtype=jnp.bfloat16,
                 remat="full", optimizer: str = "sgd", opt_kwargs=None,
                 error_feedback: bool = False):
        self.model = model
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.optimizer_name = optimizer
        self.optimizer = optim.make(optimizer, **(opt_kwargs or {}))
        self.error_feedback = bool(error_feedback)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if not isinstance(shapes, dict):
            raise TypeError("zoo-train expects a dict params pytree, got "
                            f"{type(shapes)}")
        # gran aligns n_half to workers x block_chunks: every device owns a
        # whole number of FULL decode blocks, so block_dec == block_chunks
        # at any D instead of degenerating to a tiny divisor of an
        # unlucky n_local (the ≥1B decode would otherwise crawl through
        # thousands of 1-row lax.map steps)
        self.layout = FlatShardLayout.build(
            shapes, mesh, chunk=ob.chunk,
            gran=num_workers(mesh) * block_chunks)
        self._dims_tree = param_shard_dims(shapes, mesh)
        super().__init__(ob, self.layout.D, mesh, scheduler=scheduler,
                         const=const, sched_cfg=sched_cfg,
                         block_chunks=block_chunks,
                         n_chunks=self.layout.n_chunks)
        # moments live in the master's own (n_chunks, D_c) rows: the
        # optimizer update is elementwise, so the shard-local block update
        # inside shard_map IS the global update (DESIGN.md §17)
        self._opt_shapes = jax.eval_shape(
            self.optimizer.init,
            jax.ShapeDtypeStruct((self.n_chunks, ob.chunk), jnp.float32))
        # optimizer-update block rows, chosen from the MESH-side local row
        # count so the mesh body (n_local rows) and the oracle (n_chunks
        # rows) share one loop-body shape at a trip count >= 2 on both
        # sides — a single-trip map is simplified away and its body
        # re-fused into the surrounding program, un-pinning the update
        # (see _opt_update_blocks)
        self.block_opt = next(
            (x for x in range(min(self.block_dec,
                                  max(self.n_local // 2, 1)), 0, -1)
             if self.n_local % x == 0), 1)
        # per-layer gather dims for each stacked collection, keyed by the
        # per-layer treedef the scan body sees (stacked dim 0 sliced off,
        # so every stacked leaf's gather dim shifts down by one)
        self._resolver_dims = {}
        for key in STACKED_KEYS:
            if key in shapes:
                dleaves, dtd = jax.tree_util.tree_flatten(
                    self._dims_tree[key])
                self._resolver_dims[dtd] = [max(d - 1, -1) for d in dleaves]
        self._programs = {}

    # -- weight resolution --------------------------------------------------

    def _gather_leaf(self, x, dim: int):
        if self.n_model == 1 or dim < 0:
            return x
        return coll.replicated_gather(("model",), self.n_model, dim=dim)(x)

    def _layer_resolver(self, lp):
        """Shard -> full weights for one scanned layer (inside the scan
        body and the remat boundary)."""
        leaves, td = jax.tree_util.tree_flatten(lp)
        dims = self._resolver_dims.get(td)
        if dims is None:
            raise KeyError(
                f"zoo-train layer resolver saw an unknown per-layer "
                f"structure {td}; stacked collections must be registered "
                f"under dist.sharding.STACKED_KEYS {STACKED_KEYS}")
        return jax.tree_util.tree_unflatten(
            td, [self._gather_leaf(x, d) for x, d in zip(leaves, dims)])

    def _materialize(self, p_shards):
        """Resolve NON-stacked leaves to full weights up front; stacked
        collections stay sharded for the per-layer resolver."""
        out = {}
        for key, sub in p_shards.items():
            if key in STACKED_KEYS:
                out[key] = sub
            else:
                out[key] = jax.tree_util.tree_map(
                    self._gather_leaf, sub, self._dims_tree[key])
        return out

    def _local_loss_and_grads(self, pl, batch_u):
        """This device's loss + (n_half, D_c) gradient block, from its
        local master block ``pl`` — the heart of the tentpole."""
        sect = coll.all_gather(pl.astype(self.compute_dtype), self.waxes,
                               tiled=True)
        p_shards = self.layout.section_to_tree(sect)

        def loss_of(p_shards):
            loss, _ = self.model.loss_fn(
                self._materialize(p_shards), batch_u, remat=self.remat,
                layer_resolver=self._layer_resolver
                if self._resolver_dims else None)
            return loss

        loss, g_shards = jax.value_and_grad(loss_of)(p_shards)
        return loss, self.layout.tree_to_section(g_shards)

    def _sparse_approx(self, corrected):
        """approx_fn for ``optim.ef_step``: per-chunk top-κ of the
        corrected gradient chunks, selection following ``ob.spmd_topk``
        like the compression core — the sparse vector is BOTH the lossy
        approximation the residual accumulates against and what the
        compressor transmits (fused presparsified path, DESIGN.md §11)."""
        ob = self.ob
        if ob.spmd_topk:
            sp, _ = topk_sparsify_bisect(corrected, ob.topk,
                                         iters=ob.bisect_iters)
        else:
            sp, _ = topk_sparsify(corrected, ob.topk)
        return sp, sp

    def _compress_blocks(self, g_sect):
        """compress_chunks over (n_half, D_c) in block_chunks blocks (cast
        to f32 per block — the section itself stays in compute dtype)."""
        ob, n_half = self.ob, self.n_half
        nb = n_half // self.block
        signs, mags = jax.lax.map(
            lambda gb: compress_chunks(ob, gb.astype(jnp.float32), None),
            g_sect.reshape(nb, self.block, ob.chunk))
        return signs.reshape((n_half,) + signs.shape[2:]), \
            mags.reshape(n_half)

    def _compress_blocks_ef(self, g_sect, res_u):
        """EF-corrected compression over (n_half, D_c) in the same
        block_chunks blocks: per block, ``optim.ef_step`` corrects the
        f32 gradient chunks with this worker's residual rows, the top-κ
        sparse vector goes straight into the fused presparsified
        compressor, and the dropped remainder becomes the new residual
        (DESIGN.md §17). Returns (signs, mags, residual')."""
        ob, n_half = self.ob, self.n_half
        nb = n_half // self.block

        def one(args):
            gb, rb = args
            sp, r2, _ = optim.ef_step(gb.astype(jnp.float32), rb,
                                      self._sparse_approx)
            signs, mags = compress_chunks(ob, sp, None, presparsified=True)
            return signs, mags, r2

        signs, mags, res2 = jax.lax.map(
            one, (g_sect.reshape(nb, self.block, ob.chunk),
                  res_u.reshape(nb, self.block, ob.chunk)))
        return (signs.reshape((n_half,) + signs.shape[2:]),
                mags.reshape(n_half), res2.reshape(n_half, ob.chunk))

    def _opt_update_blocks(self, ghat, ol, pl, lr):
        """``Optimizer.update`` behind the same ``lax.map`` block-shape
        pinning as ``_decode_blocks``: the update is elementwise, but XLA
        fuses the adam step differently at the mesh's (n_local, D_c) and
        the oracle's (n_chunks, D_c) shapes inside the sweep's scan/map
        wrapper, drifting final ulps. A loop body of identical
        (block_dec, D_c) shape on both sides pins ONE compiled update
        program, keeping moments and master bitwise mesh-invariant
        (DESIGN.md §17). Row-shaped state leaves ride through the map in
        blocks; scalar leaves (adam's step counter) are closed over and
        deduplicated after the map (identical in every block)."""
        b = self.block_opt
        nb = pl.shape[0] // b
        leaves, td = jax.tree_util.tree_flatten(ol)
        rowwise = [getattr(l, "ndim", 0) == 2 for l in leaves]
        blocked = tuple(l.reshape(nb, b, -1)
                        for l, r in zip(leaves, rowwise) if r)

        def one(args):
            # the barriers keep XLA from fusing the update with its
            # producers/consumers — without them a trip-count-1 map (mesh
            # side at small n_local) is simplified away and the re-fused
            # update contracts differently from the oracle's
            gb, pb, sbs = jax.lax.optimization_barrier(args)
            cur, si = [], 0
            for r, l in zip(rowwise, leaves):
                if r:
                    cur.append(sbs[si])
                    si += 1
                else:
                    cur.append(l)
            st = jax.tree_util.tree_unflatten(td, cur)
            p2, st2 = self.optimizer.update(gb, st, pb, lr)
            l2 = jax.tree_util.tree_leaves(st2)
            return jax.lax.optimization_barrier(
                (p2, tuple(x for x, r in zip(l2, rowwise) if r),
                 tuple(x for x, r in zip(l2, rowwise) if not r)))

        p2, rows2, scal2 = jax.lax.map(
            one, (ghat.reshape(nb, b, -1), pl.reshape(nb, b, -1), blocked))
        rows2 = iter(x.reshape(pl.shape[0], -1) for x in rows2)
        scal2 = iter(x[0] for x in scal2)
        out = [next(rows2) if r else next(scal2) for r in rowwise]
        return p2.reshape(pl.shape), jax.tree_util.tree_unflatten(td, out)

    # -- state construction --------------------------------------------------

    def init_state(self, master) -> ZooTrainState:
        """Fresh round carry for a (n_chunks, D_c) master: zero moments in
        the master's own chunk rows, zero EF residual in the grads layout
        (when error feedback is on). Shard with :meth:`shard_state`."""
        res = (jnp.zeros((self.U, self.n_chunks, self.ob.chunk),
                         jnp.float32) if self.error_feedback else None)
        return ZooTrainState(master=master,
                             opt=self.optimizer.init(master), residual=res)

    def init_sweep_state(self, masters) -> ZooTrainState:
        """Arm-stacked carry for (A, n_chunks, D_c) masters (vmapped
        ``init_state``: per-arm moments/residuals, adam's step counter
        becomes an (A,) axis)."""
        A = int(masters.shape[0])
        opt = jax.vmap(self.optimizer.init)(masters)
        res = (jnp.zeros((A, self.U, self.n_chunks, self.ob.chunk),
                         jnp.float32) if self.error_feedback else None)
        return ZooTrainState(master=masters, opt=opt, residual=res)

    def state_template(self, arms: Optional[int] = None) -> ZooTrainState:
        """ShapeDtypeStruct pytree of the carry — the template-strict
        checkpoint restore target (moments + residuals included,
        DESIGN.md §17). ``arms``: arm-stacked sweep carry when set."""
        lead = () if arms is None else (int(arms),)
        sds = jax.ShapeDtypeStruct
        master = sds(lead + (self.n_chunks, self.ob.chunk), jnp.float32)
        opt = jax.tree_util.tree_map(
            lambda l: sds(lead + tuple(l.shape), l.dtype),
            self._opt_shapes)
        res = (sds(lead + (self.U, self.n_chunks, self.ob.chunk),
                   jnp.float32) if self.error_feedback else None)
        return ZooTrainState(master=master, opt=opt, residual=res)

    def state_shardings(self, arms: Optional[int] = None) -> ZooTrainState:
        """NamedSharding pytree matching :meth:`state_template`: master
        and 2-d moments in the model-major master spec, scalars (adam's
        step counter) replicated, residual in the grads spec."""
        lead = (None,) if arms is not None else ()

        def ns(spec):
            return NamedSharding(self.mesh, P(*lead, *spec))

        opt = jax.tree_util.tree_map(
            lambda l: ns(self.spec) if l.ndim == 2 else ns(()),
            self._opt_shapes)
        res = ns(self.grads_spec) if self.error_feedback else None
        return ZooTrainState(master=ns(self.spec), opt=opt, residual=res)

    def shard_state(self, state: ZooTrainState,
                    arms: Optional[int] = None) -> ZooTrainState:
        """device_put every carry leaf onto its mesh sharding."""
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(jnp.asarray(x), s),
            state, self.state_shardings(arms))

    def as_state(self, state) -> ZooTrainState:
        """Accept a ZooTrainState or — for the stateless sgd/no-EF round
        only — a bare (n_chunks, D_c) master (or (A, n_chunks, D_c) arm
        stack), wrapped into the trivial carry."""
        if isinstance(state, ZooTrainState):
            return state
        if getattr(state, "ndim", None) in (2, 3):
            if self.optimizer_name == "sgd" and not self.error_feedback:
                return ZooTrainState(master=state, opt=(), residual=None)
            raise TypeError(
                f"zoo-train round built with "
                f"optimizer={self.optimizer_name!r}, "
                f"error_feedback={self.error_feedback} carries stateful "
                f"moments/residuals; pass the ZooTrainState from "
                f"init_state(master) instead of a bare master array "
                f"(DESIGN.md §17)")
        raise TypeError(
            f"zoo-train round expects a ZooTrainState or a bare "
            f"(n_chunks, D_c) master array, got {type(state).__name__}")

    def _check_state(self, state: ZooTrainState):
        """EF residual-geometry validation, eagerly at the host entry
        points — a wrong carry fails here naming the expected geometry,
        not as an opaque spec error inside shard_map."""
        res = state.residual
        want = (self.U, self.n_chunks, self.ob.chunk)
        if self.error_feedback:
            if res is None:
                raise ValueError(
                    f"ZooTrainRound(error_feedback=True): the round carry "
                    f"has no EF residual; error feedback needs the "
                    f"per-worker (U, n_chunks, D_c) = {want} residual "
                    f"carry in the grads layout — build the carry with "
                    f"init_state(master), or restore a checkpoint written "
                    f"with error feedback on (DESIGN.md §17)")
            shape = tuple(res.shape)[-3:]
            if shape != want:
                raise ValueError(
                    f"ZooTrainRound(error_feedback=True): EF residual has "
                    f"shape {tuple(res.shape)}, expected (U, n_chunks, "
                    f"D_c) = {want} — the residual lives in the same "
                    f"chunk rows as the master, one row block per worker "
                    f"(DESIGN.md §17)")
        elif res is not None:
            raise ValueError(
                "ZooTrainRound(error_feedback=False) got a carry WITH an "
                "EF residual; rebuild the round with error_feedback=True "
                "or drop the residual — silently ignoring it would break "
                "the EF convergence contract (DESIGN.md §17)")

    # -- program construction ----------------------------------------------

    def _batch_key(self, batch):
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in batch.items()))

    def batch_spec(self, batch):
        """Per-leaf PartitionSpec tree: leading (U) worker dim over the
        worker axes, replicated over model."""
        w = self.waxes if len(self.waxes) > 1 else self.waxes[0]
        return {k: P(w, *(None,) * (v.ndim - 1)) for k, v in batch.items()}

    def shard_batch(self, batch):
        """device_put a (U, ...)-stacked batch dict onto the mesh."""
        spec = self.batch_spec(batch)
        return {k: jax.device_put(
            jnp.asarray(v), NamedSharding(self.mesh, spec[k]))
            for k, v in batch.items()}

    def _fns(self, batch):
        key = self._batch_key(batch)
        if key in self._programs:
            return self._programs[key]
        waxes, n_half = self.waxes, self.n_half
        rep, sc = P(None), P()
        bspec = self.batch_spec(batch)
        ef = self.error_feedback
        opt_spec = jax.tree_util.tree_map(
            lambda l: self.spec if l.ndim == 2 else sc, self._opt_shapes)

        def model_idx():
            return (coll.axis_index(("model",))
                    if "model" in self.mesh.axis_names
                    else jnp.zeros((), jnp.int32))

        def body_core(pl, ol, res_u, bl, beta, b_t, noise_key, noise_var,
                      lr):
            """One device's round: backward → (EF-corrected) compress →
            MAC/decode → optimizer update on the local master block, each
            under its ``repro.obs`` phase scope. ``res_u``: this worker's
            (n_half, D_c) residual rows, or None without EF."""
            widx = coll.axis_index(waxes)
            half0 = model_idx() * n_half
            batch_u = jax.tree_util.tree_map(lambda x: x[0], bl)
            with obs.phase("grad"):
                loss, g_sect = self._local_loss_and_grads(pl, batch_u)
            with obs.phase("codec"):
                if res_u is None:
                    signs, mags = self._compress_blocks(g_sect)
                    res2 = None
                else:
                    signs, mags, res2 = self._compress_blocks_ef(g_sect,
                                                                 res_u)
            with obs.phase("mac_decode"):
                ghat, gn2 = self._mac_decode(signs, mags, beta, b_t,
                                             noise_key, noise_var, widx,
                                             half0, None)
            with obs.phase("optim"):
                pl2, ol2 = self._opt_update_blocks(ghat, ol, pl, lr)
            loss_mean = coll.psum(loss, waxes) / jnp.float32(self.U)
            return pl2, ol2, res2, gn2, loss_mean

        if ef:
            def body_train(pl, ol, rl, bl, beta, b_t, nkey, nv, lr):
                pl2, ol2, res2, gn2, loss = body_core(
                    pl, ol, rl[0], bl, beta, b_t, nkey, nv, lr)
                return pl2, ol2, res2[None], gn2, loss

            sm_train = jax.shard_map(
                body_train, mesh=self.mesh,
                in_specs=(self.spec, opt_spec, self.grads_spec, bspec,
                          rep, sc, rep, sc, sc),
                out_specs=(self.spec, opt_spec, self.grads_spec, sc, sc),
                check_vma=False)
        else:
            def body_train(pl, ol, bl, beta, b_t, nkey, nv, lr):
                pl2, ol2, _, gn2, loss = body_core(
                    pl, ol, None, bl, beta, b_t, nkey, nv, lr)
                return pl2, ol2, gn2, loss

            sm_train = jax.shard_map(
                body_train, mesh=self.mesh,
                in_specs=(self.spec, opt_spec, bspec, rep, sc, rep, sc,
                          sc),
                out_specs=(self.spec, opt_spec, sc, sc), check_vma=False)

        def body_grads_out(pl, bl):
            batch_u = jax.tree_util.tree_map(lambda x: x[0], bl)
            loss, g_sect = self._local_loss_and_grads(pl, batch_u)
            return g_sect.astype(jnp.float32)[None], loss[None]

        wspec = self.grads_spec[0]
        sm_grads_out = jax.shard_map(
            body_grads_out, mesh=self.mesh,
            in_specs=(self.spec, bspec),
            out_specs=(self.grads_spec, P(wspec)), check_vma=False)

        def round_impl(state, bl, t, key, noise_var, p_max, lr):
            t, beta, b_t, nkey = self._prologue(t, key, noise_var, p_max)
            nv, lrf = jnp.float32(noise_var), jnp.float32(lr)
            if ef:
                pl2, ol2, rl2, gn2, loss = sm_train(
                    state.master, state.opt, state.residual, bl, beta,
                    b_t, nkey, nv, lrf)
            else:
                pl2, ol2, gn2, loss = sm_train(
                    state.master, state.opt, bl, beta, b_t, nkey, nv, lrf)
                rl2 = None
            st2 = ZooTrainState(master=pl2, opt=ol2, residual=rl2)
            return st2, _with_loss(self._stats(beta, b_t, gn2, noise_var),
                                   loss)

        def ref_impl(state, bl, t, key, noise_var, p_max, lr):
            t, beta, b_t, nkey = self._prologue(t, key, noise_var, p_max)
            cdt = self.compute_dtype
            chunked = state.master
            residual = state.residual
            p_full = self.layout.master_to_tree(chunked.astype(cdt))

            def one(u):
                batch_u = jax.tree_util.tree_map(lambda x: x[u], bl)

                def loss_of(p):
                    loss, _ = self.model.loss_fn(p, batch_u,
                                                 remat=self.remat)
                    return loss

                loss, g = jax.value_and_grad(loss_of)(p_full)
                gm = self.layout.tree_to_master(g, dtype=cdt)
                if residual is None:
                    signs, mags = compress_chunks(
                        self.ob, gm.astype(jnp.float32), None)
                    return loss, signs, mags
                # identical EF chain to the mesh body: shared ef_step,
                # fused presparsified compress (DESIGN.md §17)
                sp, r2, _ = optim.ef_step(gm.astype(jnp.float32),
                                          residual[u], self._sparse_approx)
                signs, mags = compress_chunks(self.ob, sp, None,
                                              presparsified=True)
                return loss, signs, mags, r2

            outs = jax.lax.map(one, jnp.arange(self.U, dtype=jnp.int32))
            if residual is None:
                losses, signs, mags = outs
                res2 = None
            else:
                losses, signs, mags, res2 = outs
            ghat, gn2 = self._reference_mac_decode(signs, mags, beta, b_t,
                                                   nkey, noise_var)
            chunked2, opt2 = self._opt_update_blocks(ghat, state.opt,
                                                     chunked,
                                                     jnp.float32(lr))
            st2 = ZooTrainState(master=chunked2, opt=opt2, residual=res2)
            return st2, _with_loss(self._stats(beta, b_t, gn2, noise_var),
                                   jnp.mean(losses))

        def ref_grads_impl(chunked, bl):
            cdt = self.compute_dtype
            p_full = self.layout.master_to_tree(chunked.astype(cdt))

            def one(u):
                batch_u = jax.tree_util.tree_map(lambda x: x[u], bl)

                def loss_of(p):
                    loss, _ = self.model.loss_fn(p, batch_u,
                                                 remat=self.remat)
                    return loss

                loss, g = jax.value_and_grad(loss_of)(p_full)
                return self.layout.tree_to_master(g, dtype=cdt).astype(
                    jnp.float32), loss

            g, losses = jax.lax.map(one, jnp.arange(self.U,
                                                    dtype=jnp.int32))
            return g, losses

        fns = {
            "round_train": jax.jit(round_impl),
            "round_impl": round_impl,
            "grads_in_layout": jax.jit(sm_grads_out),
            # oracles are jitted for the same reason as ZooRound's: eager
            # f32 fusion drifts final ulps vs the compiled sharded round
            "ref_train": jax.jit(ref_impl),
            "ref_impl": ref_impl,
            "ref_grads": jax.jit(ref_grads_impl),
        }
        self._programs[key] = fns
        return fns

    # -- public entry points -----------------------------------------------

    def round_train(self, state, batch, t, key, noise_var, p_max, lr):
        """One real-gradient round. ``state``: ZooTrainState from
        ``init_state``/``shard_state`` (a bare sharded (n_chunks, D_c)
        master is accepted for the stateless sgd/no-EF round); ``batch``:
        dict of (U, ...)-stacked arrays from ``shard_batch``. Returns
        (state', ZooTrainStats)."""
        state = self.as_state(state)
        self._check_state(state)
        return self._fns(batch)["round_train"](state, batch, t, key,
                                               noise_var, p_max, lr)

    def grads_in_layout(self, master, batch):
        """The real per-worker gradients as the sharded (U, n_chunks, D_c)
        array ``round_from_grads`` consumes — the debug/parity surface for
        "grads produced already in the compressor's layout". Returns
        (grads, per-worker losses)."""
        if isinstance(master, ZooTrainState):
            master = master.master
        return self._fns(batch)["grads_in_layout"](master, batch)

    def reference_round_train(self, state, batch, t, key, noise_var,
                              p_max, lr):
        """Single-device oracle of ``round_train`` (replicated inputs)."""
        state = self.as_state(state)
        self._check_state(state)
        return self._fns(batch)["ref_train"](state, batch, t, key,
                                             noise_var, p_max, lr)

    def reference_grads(self, chunked, batch):
        """Single-device oracle of ``grads_in_layout``."""
        if isinstance(chunked, ZooTrainState):
            chunked = chunked.master
        return self._fns(batch)["ref_grads"](chunked, batch)

    # -- params layout ------------------------------------------------------

    def chunk_params(self, params):
        """Params pytree -> (n_chunks, D_c) in the sharded-flat layout
        (overrides ZooRound's tail-padded flatten: the zoo-train order is
        model-major per-leaf-slice, DESIGN.md §16)."""
        return self.layout.tree_to_master(params)

    def params_from_master(self, chunked):
        """(n_chunks, D_c) -> full params pytree (checkpoint/eval
        interop)."""
        if isinstance(chunked, ZooTrainState):
            chunked = chunked.master
        return self.layout.master_to_tree(jnp.asarray(chunked))

    def unchunk(self, chunked):
        leaves = jax.tree_util.tree_leaves(self.params_from_master(chunked))
        return jnp.concatenate([x.reshape(-1) for x in leaves])

    # -- multi-arm sweep ----------------------------------------------------

    def _sweep_program(self, body, tag, batch, A: int, rounds: int, t0):
        """scan-over-rounds of lax.map-over-arms of ``body``, jitted and
        cached. The mesh sweep and its oracle are built from the SAME
        wrapper so their program structure matches — the wrapping itself
        changes XLA fusion inside the round body, so the bitwise parity
        contract is per-structure: jitted round ↔ jitted reference round,
        jitted sweep ↔ jitted reference sweep (DESIGN.md §16)."""
        def sweep_impl(states, bl, key, nv, pm, lr):
            def one_round(ss, t):
                def one_arm(args):
                    s, nv_a, pm_a, lr_a = args
                    return body(s, bl, t, key, nv_a, pm_a, lr_a)
                s2, st = jax.lax.map(one_arm, (ss, nv, pm, lr))
                return s2, st
            ts = t0 + jnp.arange(rounds, dtype=jnp.int32)
            return jax.lax.scan(one_round, states, ts)

        return self._programs.setdefault(
            (tag, self._batch_key(batch), A, rounds, int(t0)),
            jax.jit(sweep_impl))

    def run_sweep(self, states, batch, arms, rounds: int, *, key, t0=0):
        """Arms × rounds in ONE jitted program: ``lax.scan`` over rounds
        of ``lax.map`` over arms of the shard_map'd round body.

        ``states``: arm-stacked ZooTrainState from ``init_sweep_state``/
        ``shard_state(..., arms=A)`` (bare (A, n_chunks, D_c) masters are
        accepted for the stateless round, see ``shard_masters``);
        ``arms``: dict of (A,) f32 arrays ``noise_var`` / ``p_max`` /
        ``lr``. Returns (states', ZooTrainStats stacked (rounds, A))."""
        states = self.as_state(states)
        self._check_state(states)
        fns = self._fns(batch)
        A = int(arms["noise_var"].shape[0])
        jitted = self._sweep_program(fns["round_impl"], "sweep", batch, A,
                                     rounds, t0)
        return jitted(states, batch, key, arms["noise_var"],
                      arms["p_max"], arms["lr"])

    def reference_sweep(self, states, batch, arms, rounds: int, *, key,
                        t0=0):
        """Single-device oracle of ``run_sweep`` with the identical
        scan/map wrapping (replicated arm-stacked carry)."""
        states = self.as_state(states)
        self._check_state(states)
        fns = self._fns(batch)
        A = int(arms["noise_var"].shape[0])
        jitted = self._sweep_program(fns["ref_impl"], "ref_sweep", batch,
                                     A, rounds, t0)
        return jitted(states, batch, key, arms["noise_var"],
                      arms["p_max"], arms["lr"])

    def shard_masters(self, masters):
        """(A, n_chunks, D_c) arm-stacked masters: chunk axis model-major
        sharded exactly like a single master, arms replicated."""
        spec = P(None, *self.spec)
        return jax.device_put(jnp.asarray(masters),
                              NamedSharding(self.mesh, spec))

    # -- checkpointing -------------------------------------------------------

    def save_state(self, ckpt_dir: str, step: int, state: ZooTrainState,
                   t_next: int) -> str:
        """Snapshot the FULL round carry — master + optimizer moments +
        EF residuals — plus the absolute next-round index, one atomic
        step dir via ``repro.checkpoint`` (DESIGN.md §17). Round RNG and
        schedules fold the absolute round index, so no RNG state needs
        serializing for a bit-for-bit resume."""
        from repro import checkpoint
        host = jax.tree_util.tree_map(np.asarray, state)
        return checkpoint.save(ckpt_dir, step,
                               {"state": host,
                                "t_next": np.int32(t_next)})

    def restore_state(self, ckpt_dir: str, step: Optional[int] = None,
                      arms: Optional[int] = None):
        """(state, t_next) from ``step`` (default: latest), template-
        strict against :meth:`state_template` (leaf count, shapes, AND
        dtypes — moments restore dtype-strict) and device_put onto
        :meth:`state_shardings` — a carry saved on one mesh resumes on a
        differently-shaped one (mesh-elastic, DESIGN.md §14/§17).
        Returns None when the directory holds no steps yet."""
        from repro import checkpoint
        if step is None:
            step = checkpoint.latest_step(ckpt_dir)
            if step is None:
                return None
        like = {"state": self.state_template(arms),
                "t_next": jax.ShapeDtypeStruct((), jnp.int32)}
        shardings = {"state": self.state_shardings(arms),
                     "t_next": NamedSharding(self.mesh, P())}
        tree = checkpoint.restore(ckpt_dir, step, like,
                                  shardings=shardings)
        return tree["state"], int(tree["t_next"])

    # -- host driver --------------------------------------------------------

    def run_rounds_train(self, state, batch, rounds: int, *, key,
                         noise_var, p_max, lr, t0: int = 0,
                         ckpt_dir: Optional[str] = None,
                         ckpt_every: int = 0):
        """Host loop over jitted real-gradient rounds (one compiled
        program, reused) from absolute round ``t0``, optionally snapshot-
        ting the full carry every ``ckpt_every`` rounds. Returns
        (state', list of host ZooTrainStats)."""
        state = self.as_state(state)
        out = []
        for t in range(t0, t0 + rounds):
            state, st = self.round_train(state, batch, t, key, noise_var,
                                         p_max, lr)
            out.append(jax.tree_util.tree_map(np.asarray, st))
            if ckpt_dir and ckpt_every and (t + 1) % ckpt_every == 0:
                self.save_state(ckpt_dir, t + 1, state, t_next=t + 1)
        return state, out


def build_zoo_train_round(model, mesh, ob: OBCSAAConfig,
                          **kw) -> ZooTrainRound:
    """Build the sharded real-backward zoo round for (model, mesh, ob)."""
    return ZooTrainRound(model, mesh, ob, **kw)
