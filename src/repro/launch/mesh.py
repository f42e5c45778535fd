"""Production mesh definitions (MULTI-POD DRY-RUN spec).

Functions, not module-level constants — importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    Newer jax defaults to ``Explicit`` axes, which put shardings into the
    array types and refuse reshapes of sharded dims (the flat-layout
    views of ``dist/flat_layout.py``) even on a one-device mesh. The repo
    places data with ``NamedSharding``/``shard_map`` and lets the
    partitioner propagate the rest, which is what ``Auto`` means.
    ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


# the device_kind JAX reports for the chips of the production mesh
PRODUCTION_DEVICE_KIND = "TPU v5 lite"     # TPU v5e


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return auto_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


def make_zoo_mesh(n_workers: int = 0, model_parallel: int = 0):
    """Mesh for sharded model-zoo rounds (engine/zoo.py, DESIGN.md §14):
    ``(n_workers, model_parallel)`` over ``("data", "model")`` on the
    local devices. Zeros pick defaults — every device used, model
    parallelism 2 when the device count allows it (the ≥1B CPU bench
    geometry: 4 FL workers × 2 model shards on an 8-device host mesh)."""
    n = len(jax.devices())
    if not model_parallel:
        model_parallel = 2 if n % 2 == 0 and n > 1 else 1
    if not n_workers:
        n_workers = n // model_parallel
    if n_workers * model_parallel != n:
        raise ValueError(
            f"make_zoo_mesh: {n_workers} workers x {model_parallel} model "
            f"shards != {n} local devices")
    return auto_mesh((n_workers, model_parallel), ("data", "model"))


def worker_axes(mesh) -> tuple:
    """Mesh axes that enumerate FL workers (DESIGN.md §3)."""
    return tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)


def num_workers(mesh) -> int:
    n = 1
    for ax in worker_axes(mesh):
        n *= mesh.shape[ax]
    return n
