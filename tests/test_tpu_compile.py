"""Every Pallas kernel compiles for a TPU v5e chip at real geometry.

The chip is described, not attached (``jax.experimental.topologies``): the
TPU compiler refuses here what Mosaic would refuse on the chip (block
shapes off the (8, 128) tiling, unsupported casts and reductions, VMEM
overruns), which the interpret-mode tests cannot see. Nothing runs.

Geometries: the paper round (D_c=4096, S_c=1024) and the zoo round
(D_c=16384, S_c=32); the scheduler sweep at B=64, U=8192.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and the test
workers must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import backproject as bp
from repro.kernels import cs_project as cs
from repro.kernels import prefix_eval as pe
from repro.kernels import topk_select as tk
from repro.kernels.sign import PACK

GEOMETRIES = {"paper": (4096, 1024, 80), "zoo": (16384, 32, 8)}
ROWS = 128   # chunk rows per call: one cs_project / backproject row tile


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_specs(name, d, s, sh):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=sh)
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                              sharding=sh)
    phi, x, w = f32(s, d), f32(ROWS, d), s // PACK
    return {
        "cs_sign": (lambda p, c: cs.project(p, c, mode="sign"), phi, x),
        "cs_pack": (lambda p, c: cs.project(p, c, mode="pack"), phi, x),
        "cs_pack_sign_residual": (
            lambda p, c, y: cs.project(p, c, mode="pack_sign_residual", y=y),
            phi, x, u32(ROWS, w)),
        "backproject": (lambda c, r, p: bp.backproject(c, r, p, 0.1),
                        x, f32(ROWS, s), phi),
        "backproject_packed": (
            lambda c, a, b, p: bp.backproject_packed(c, a, b, p, 0.1),
            x, u32(ROWS, w), u32(ROWS, w), phi),
    }[name]


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("kernel", ["cs_sign", "cs_pack",
                                    "cs_pack_sign_residual", "backproject",
                                    "backproject_packed"])
def test_codec_kernel_compiles_for_v5e(one_chip, kernel, geometry):
    d, s, _ = GEOMETRIES[geometry]
    fn, *specs = _kernel_specs(kernel, d, s, one_chip)
    assert "tpu_custom_call" in _compile_text(fn, *specs)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_topk_select_compiles_for_v5e(one_chip, geometry):
    d, _, k = GEOMETRIES[geometry]
    x = jax.ShapeDtypeStruct((ROWS, d), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(
        lambda c: tk.topk_select(c, k), x)


def test_prefix_eval_compiles_for_v5e(one_chip):
    B, U = 64, 8192
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                               sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(
        pe.prefix_eval, spec(B, U), spec(B, U), spec(B, pe.N_COEF))
