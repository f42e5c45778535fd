"""Mesh-aware sharding: soft constraints + spec/pytree inference.

Three entry points, consumed across core, models, and launch:

- ``constrain(x, axes)`` — ``with_sharding_constraint`` that degrades to a
  no-op when there is no ambient mesh, an axis is absent/manual, or a dim
  isn't divisible; a constraint it does emit and the compiler refuses is
  an error. Model code calls it unconditionally; the same forward
  runs unsharded on one CPU device and sharded on the production mesh.
- ``best_spec(shape, hints, mesh)`` — per-dim axis choice from priority
  hint lists like ``["data", None]``, preferring the largest divisible
  option and falling back to replication.
- ``infer_param_sharding(tree, mesh)`` — pytree-wide ``NamedSharding``
  inference for params / optimizer state: the largest model-divisible dim
  of each leaf is sharded over ``model``; worker axes (pod, data) stay
  replicated because every FL worker holds the full model (DESIGN.md §3).
  Stacked-layer pytrees (leaves whose path goes through a
  ``stacked_keys`` entry, e.g. the transformer's ``layers`` collection
  scanned by ``lax.scan``) never shard their leading dim: that axis is
  the scan axis and must stay whole so ``lax.scan`` can slice one layer
  per step (DESIGN.md §16).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

# Pytree keys whose subtrees hold stacked leaves (layers; the hybrid
# family's shared blocks and per-call weights): dim 0 is the
# lax.scan axis, not a shardable weight dim.
STACKED_KEYS = ("layers", "enc_layers", "shared_blocks", "hybrid")


def _ambient():
    """(ambient abstract mesh or None, frozenset of manual axis names)."""
    view = jax.sharding.get_abstract_mesh()
    if view is None or view.empty:
        return None, frozenset()
    manual = frozenset(a for a, t in zip(view.axis_names, view.axis_types)
                       if t == AxisType.Manual)
    return view, manual


def _axis_sizes(mesh) -> dict:
    return dict(mesh.shape)


def constrain(x, axes):
    """Constrain ``x`` to ``axes`` (one entry per dim: axis name, tuple of
    names, or None) on the ambient mesh; no-op when that is impossible.

    Skipped per-name: names not in the mesh, names already manual (an
    enclosing ``shard_map`` owns them), names already used on an earlier
    dim, and names whose size doesn't divide the dim."""
    shape = getattr(x, "shape", None)
    if shape is None:
        return x
    mesh, manual = _ambient()
    if mesh is None:
        return x
    sizes = _axis_sizes(mesh)
    axes = tuple(axes)[:len(shape)]
    axes = axes + (None,) * (len(shape) - len(axes))
    used = set()
    parts = []
    for dim, hint in zip(shape, axes):
        cand = tuple(hint) if isinstance(hint, (tuple, list)) else (hint,)
        keep = []
        stride = 1
        for name in cand:
            if (name and name in sizes and name not in manual
                    and name not in used and dim % (stride * sizes[name]) == 0):
                keep.append(name)
                stride *= sizes[name]
        used.update(keep)
        parts.append(tuple(keep) if len(keep) > 1
                     else (keep[0] if keep else None))
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def best_spec(shape: Sequence[int], hints, mesh) -> P:
    """Pick a PartitionSpec for ``shape`` from per-dim hint candidates.

    ``hints[i]`` is an axis name, None, or a priority list of candidates.
    For each dim the first candidate that exists in the mesh, is unused,
    and divides the dim wins; the ``data`` hint is widened to the full
    worker-axis product ``("pod", "data")`` on 3-axis meshes when that
    larger factor still divides (global batch is sharded over ALL workers,
    DESIGN.md §3). No candidate fits -> the dim is replicated."""
    sizes = _axis_sizes(mesh)
    used = set()
    parts = []
    for i, dim in enumerate(shape):
        hint = hints[i] if i < len(hints) else None
        cands = list(hint) if isinstance(hint, (list, tuple)) else [hint]
        chosen = None
        for cand in cands:
            if cand is None:
                break
            options = [(cand,)]
            if cand == "data" and "pod" in sizes:
                options.insert(0, ("pod", "data"))
            for opt in options:
                if any(a not in sizes or a in used for a in opt):
                    continue
                total = 1
                for a in opt:
                    total *= sizes[a]
                if dim % total == 0:
                    chosen = opt
                    break
            if chosen:
                break
        if chosen:
            used.update(chosen)
            parts.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            parts.append(None)
    return P(*parts)


def infer_batch_sharding(tree, mesh, *, dim: int = 0):
    """NamedSharding pytree for an (A, ...)-stacked sweep carry/arms tree:
    shard dim ``dim`` of every leaf over the worker axes (via
    ``best_spec``'s ``data`` hint, which widens to ``("pod", "data")`` on
    3-axis meshes) when the arm count divides, replicate otherwise.

    The engine's vmapped arms are embarrassingly parallel over the arm
    axis — no cross-arm collectives — so arm-sharded placement turns the
    sweep into per-device lane groups (DESIGN.md §14). Scalars and
    non-divisible leaves replicate, which is always correct."""

    def spec_of(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) <= dim:
            return NamedSharding(mesh, P())
        hints = [None] * len(shape)
        hints[dim] = "data"
        return NamedSharding(mesh, best_spec(shape, hints, mesh))

    return jax.tree_util.tree_map(spec_of, tree)


def _path_is_stacked(path, stacked_keys) -> bool:
    for entry in path:
        key = getattr(entry, "key", getattr(entry, "name", None))
        if key in stacked_keys:
            return True
    return False


def _best_model_dim(shape, msize, *, skip_leading: bool):
    """Index of the largest ``msize``-divisible dim, or None.

    ``skip_leading`` excludes dim 0 (a stacked leaf's scan axis). Ties go
    to the trailing dim — the contraction/output dim of weight matrices."""
    if msize <= 1 or not shape:
        return None
    best = None
    for i, d in enumerate(shape):
        if skip_leading and i == 0:
            continue
        if d > 1 and d % msize == 0 and (best is None or d >= shape[best]):
            best = i
    return best


def param_shard_dims(tree, mesh, *, model_axis: str = "model",
                     stacked_keys: Sequence[str] = STACKED_KEYS):
    """Per-leaf shard-dim pytree mirroring ``infer_param_sharding``.

    Each leaf maps to the int dim index sharded over ``model_axis``, or
    -1 when the leaf replicates (-1 rather than None so the result stays
    leaf-for-leaf congruent with ``tree``). Consumed by the zoo-train
    layout and layer resolvers, which need the raw dim to slice/gather
    along rather than a NamedSharding."""
    msize = _axis_sizes(mesh).get(model_axis, 1)

    def dim_of(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        best = _best_model_dim(
            shape, msize, skip_leading=_path_is_stacked(path, stacked_keys))
        return -1 if best is None else best

    return jax.tree_util.tree_map_with_path(dim_of, tree)


def infer_param_sharding(tree, mesh, *, model_axis: str = "model",
                         stacked_keys: Sequence[str] = STACKED_KEYS):
    """NamedSharding pytree for params / optimizer state.

    Rule: shard each leaf's largest ``model``-divisible dim over the model
    axis (ties -> the trailing dim, the contraction/output dim of weight
    matrices); everything else — scalars, odd-shaped leaves, meshes with
    no model parallelism — replicates. Worker axes are never used: each
    data shard is an FL worker holding the full (model-sharded) network.

    Leaves under a ``stacked_keys`` path (layer stacks stepped by
    ``lax.scan``) keep dim 0 whole — the scan axis is sliced one layer per
    step and sharding it would split layers across devices instead of
    splitting weights within a layer."""
    msize = _axis_sizes(mesh).get(model_axis, 1)

    def spec_of(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        best = _best_model_dim(
            shape, msize, skip_leading=_path_is_stacked(path, stacked_keys))
        if best is None:
            return NamedSharding(mesh, P())
        parts = [None] * len(shape)
        parts[best] = model_axis
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(spec_of, tree)
