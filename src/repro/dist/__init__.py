"""Distributed execution substrate.

``repro.dist`` is the layer between the paper's math (``repro.core``) and
physical meshes (``repro.launch.mesh``):

- ``repro.dist.sharding`` — mesh-aware sharding-constraint + inference
  helpers (``constrain``, ``best_spec``, ``infer_param_sharding``) used by
  every model family and by the step builders.
- ``repro.dist.collectives`` — worker-axis collectives. The over-the-air
  MAC superposition (paper eq. 8-12) IS ``psum`` over the mesh axes that
  enumerate FL workers (DESIGN.md §3).
"""
from repro.dist import collectives
from repro.dist.sharding import (best_spec, constrain, infer_param_sharding,
                                 param_shard_dims)

__all__ = ["best_spec", "collectives", "constrain", "infer_param_sharding",
           "param_shard_dims"]
