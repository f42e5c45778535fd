"""repro.decode: registry dispatch, fused-Pallas IHT bit-parity with the
seed einsum decoder, warm-start NMSE gains on correlated gradients, and
sharded decode == single-device decode on an 8-device CPU mesh
(subprocess, same pattern as test_dist_sharding.py)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.measurement import make_phi
from repro.decode import (DecodeConfig, decode, fused_iht, get_decoder, iht,
                          list_decoders, register_decoder)
from repro.decode import registry as dec_registry
from repro.kernels.ref import topk_select_ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _measurements(n=8, s=512, d=1024, k_true=60, noise=0.01, seed=0):
    phi = make_phi(seed + 3, s, d)
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    x_true, _ = topk_select_ref(x, k_true)
    y = jnp.einsum("sd,nd->ns", phi, x_true)
    y = y + noise * jax.random.normal(jax.random.PRNGKey(seed + 1), (n, s))
    return y, phi, x_true


# --- registry ---------------------------------------------------------------------

def test_registry_builtins_present():
    names = set(list_decoders())
    assert {"iht", "biht", "niht", "iht_warm", "iht_fused"} <= names


def test_registry_unknown_decoder_raises():
    with pytest.raises(ValueError, match="unknown decoder"):
        get_decoder("does_not_exist")
    y, phi, _ = _measurements(n=2, s=128, d=256)
    with pytest.raises(ValueError, match="registered"):
        decode(y, phi, 8, DecodeConfig(algorithm="nope"))


def test_registry_dispatch_matches_direct_call():
    y, phi, _ = _measurements()
    cfg = DecodeConfig(algorithm="iht", iters=6, tau=1.0)
    got = decode(y, phi, 40, cfg)
    want = iht(y, phi, 40, iters=6, tau=1.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_registry_custom_decoder_roundtrip():
    @register_decoder("test_zero")
    def _zero(y, phi, k, cfg, x0):
        return jnp.zeros(y.shape[:-1] + (phi.shape[1],), y.dtype)

    try:
        y, phi, _ = _measurements(n=2, s=128, d=256)
        out = decode(y, phi, 8, DecodeConfig(algorithm="test_zero"))
        assert not np.asarray(out).any()
        assert "test_zero" in list_decoders()
    finally:
        del dec_registry._REGISTRY["test_zero"]


def test_warm_state_withheld_from_cold_decoders():
    """decode() forwards x0 only to warm-capable decoders (DESIGN.md §9)."""
    y, phi, x_true = _measurements()
    junk = 100.0 * jax.random.normal(jax.random.PRNGKey(9), x_true.shape)
    cold_cfg = DecodeConfig(algorithm="iht", iters=6, tau=1.0)
    a = decode(y, phi, 40, cold_cfg)
    b = decode(y, phi, 40, cold_cfg, x0=junk)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    warm_cfg = DecodeConfig(algorithm="iht_warm", iters=6, tau=1.0)
    c = decode(y, phi, 40, warm_cfg, x0=junk)
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_validate_raise_catches_divergent_tau():
    """Fixed-step IHT silently diverges past the restricted stability edge
    τ·λ̂ ≥ 2 (DESIGN.md §13) — validate='raise' turns that into a
    ValueError naming the measured λ̂ and the safe τ range."""
    y, phi, _ = _measurements()
    with pytest.raises(ValueError, match="unstable"):
        decode(y, phi, 256, DecodeConfig(algorithm="iht", iters=30, tau=1.0,
                                         validate="raise"))
    # the divergence the guard prevents is real: unguarded it blows up
    raw = decode(y, phi, 256, DecodeConfig(algorithm="iht", iters=30,
                                           tau=1.0))
    assert float(jnp.max(jnp.abs(raw))) > 1e6


def test_validate_passes_stable_tau_bitwise():
    """A stable τ decodes through the guard bit-identically to the
    unguarded path — the guard is trace-invisible when it doesn't fire."""
    y, phi, _ = _measurements()
    a = decode(y, phi, 256, DecodeConfig(algorithm="iht", iters=30,
                                         tau=0.25, validate="raise"))
    b = decode(y, phi, 256, DecodeConfig(algorithm="iht", iters=30,
                                         tau=0.25))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_validate_fallback_swaps_in_niht():
    y, phi, _ = _measurements()
    f = decode(y, phi, 256, DecodeConfig(algorithm="iht", iters=30, tau=1.0,
                                         validate="fallback"))
    n = decode(y, phi, 256, DecodeConfig(algorithm="niht", iters=30))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(n))
    assert bool(jnp.all(jnp.isfinite(f)))


def test_validate_under_jit_is_a_cond():
    """Traced decode cannot raise — both modes become a lax.cond between
    the requested decoder and NIHT, selected by the traced predicate."""
    y, phi, _ = _measurements()
    n = decode(y, phi, 256, DecodeConfig(algorithm="niht", iters=30))
    bad = jax.jit(lambda yy, pp: decode(yy, pp, 256, DecodeConfig(
        algorithm="iht", iters=30, tau=1.0, validate="raise")))(y, phi)
    np.testing.assert_array_equal(np.asarray(bad), np.asarray(n))
    ok = jax.jit(lambda yy, pp: decode(yy, pp, 256, DecodeConfig(
        algorithm="iht", iters=30, tau=0.25, validate="fallback")))(y, phi)
    eager = decode(y, phi, 256, DecodeConfig(algorithm="iht", iters=30,
                                             tau=0.25))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(eager))


def test_validate_unknown_mode_raises():
    y, phi, _ = _measurements()
    with pytest.raises(ValueError, match="validate"):
        decode(y, phi, 64, DecodeConfig(algorithm="iht", validate="maybe"))


def test_restricted_spectral_estimate_brackets_divergence():
    """The guard's λ̂ is calibrated: the empirical blow-up τ sits inside
    (1/λ̂ is safe, 2/λ̂ is the edge) — see IHT_STABILITY_BOUND."""
    from repro.decode.iht import (IHT_STABILITY_BOUND, iht_step_stable,
                                  restricted_spectral_estimate)
    y, phi, x_true = _measurements()
    lam = float(restricted_spectral_estimate(phi, 256))
    assert 3.0 < lam < 6.0
    safe_tau = 0.5 / lam
    edge_tau = (IHT_STABILITY_BOUND + 0.5) / lam
    assert bool(iht_step_stable(phi, 256, safe_tau))
    assert not bool(iht_step_stable(phi, 256, edge_tau))
    out = iht(y, phi, 256, iters=40, tau=safe_tau)
    assert float(jnp.max(jnp.abs(out))) < 1e3
    out = iht(y, phi, 256, iters=40, tau=edge_tau)
    assert float(jnp.max(jnp.abs(out))) > 1e3


def test_ht_bisect_matches_sort_on_generic_values():
    y, phi, _ = _measurements()
    a = decode(y, phi, 40, DecodeConfig(algorithm="iht", iters=6, tau=1.0,
                                        ht="sort"))
    b = decode(y, phi, 40, DecodeConfig(algorithm="iht", iters=6, tau=1.0,
                                        ht="bisect"))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# --- fused-Pallas IHT parity ------------------------------------------------------

def test_fused_iht_bitwise_matches_seed_iht():
    """Cold-start parity: the fused kernel loop == the einsum decoder
    bit for bit in interpret mode (DESIGN.md §9 tiling policy)."""
    y, phi, _ = _measurements(n=13, s=512, d=1024)  # odd n exercises row pad
    ref = jax.jit(lambda y: iht(y, phi, 64, iters=8, tau=1.0))(y)
    got = jax.jit(lambda y: fused_iht(y, phi, 64, iters=8, tau=1.0,
                                      interpret=True))(y)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.slow
def test_fused_iht_bitwise_paper_chunk_scale():
    """Same parity at the paper's chunk geometry (D_c=4096, S_c=1024,
    13 chunks = D=50,890 padded, κ̄=512)."""
    y, phi, _ = _measurements(n=13, s=1024, d=4096, k_true=409)
    ref = jax.jit(lambda y: iht(y, phi, 512, iters=5, tau=0.25))(y)
    got = jax.jit(lambda y: fused_iht(y, phi, 512, iters=5, tau=0.25,
                                      interpret=True))(y)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_fused_iht_warm_start_consumed():
    y, phi, x_true = _measurements()
    cold = fused_iht(y, phi, 64, iters=2, tau=1.0, interpret=True)
    warm = fused_iht(y, phi, 64, iters=2, tau=1.0, x0=x_true,
                     interpret=True)
    assert not np.array_equal(np.asarray(cold), np.asarray(warm))
    # warm from the truth after 2 iterations must be at least as accurate
    err_c = float(jnp.linalg.norm(cold - x_true))
    err_w = float(jnp.linalg.norm(warm - x_true))
    assert err_w <= err_c


# --- warm start on correlated rounds ----------------------------------------------

def test_warm_start_improves_nmse_on_correlated_gradients():
    """Round t's decode seeded with round t−1's estimate beats cold start
    at the same (small) iteration budget — the temporal-correlation gain
    the warm-start decoder exists for (DESIGN.md §9)."""
    n, s, d, k_true, k = 6, 512, 1024, 60, 128
    tau = 0.25      # stable fixed step at this decode budget (k = S/4; see
    # benchmarks/decoders_bench.py on the restricted operator norm)
    phi = make_phi(11, s, d)
    x_prev, _ = topk_select_ref(
        jax.random.normal(jax.random.PRNGKey(0), (n, d)), k_true)
    innov = 0.15 * jax.random.normal(jax.random.PRNGKey(1), (n, d))
    x_next_dense = x_prev + innov * (x_prev != 0)     # support-preserving drift
    x_next, _ = topk_select_ref(x_next_dense, k_true)
    y_prev = jnp.einsum("sd,nd->ns", phi, x_prev)
    y_next = jnp.einsum("sd,nd->ns", phi, x_next)

    # round t−1 estimate (well-converged), then a tight budget for round t
    x0 = decode(y_prev, phi, k, DecodeConfig("iht", iters=30, tau=tau))
    cold = decode(y_next, phi, k, DecodeConfig("iht", iters=3, tau=tau))
    warm = decode(y_next, phi, k, DecodeConfig("iht_warm", iters=3, tau=tau),
                  x0=x0)

    def nmse(xh):
        return float(jnp.sum((xh - x_next) ** 2) / jnp.sum(x_next ** 2))

    assert nmse(warm) < nmse(cold)


# --- sharded decode (8-device CPU mesh, subprocess) -------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.measurement import make_phi
    from repro.core.obcsaa import OBCSAAConfig, reconstruct_chunks
    from repro.decode import DecodeConfig, decode
    from repro.kernels.ref import topk_select_ref
    from repro.launch.mesh import auto_mesh

    n, s, d, k = 16, 256, 512, 64
    phi = make_phi(5, s, d)
    x_true, _ = topk_select_ref(
        jax.random.normal(jax.random.PRNGKey(0), (n, d)), 32)
    y = jnp.einsum("sd,nd->ns", phi, x_true)

    cfgs = [DecodeConfig("iht", iters=8, tau=1.0, ht="bisect"),
            DecodeConfig("niht", iters=8, ht="bisect"),
            DecodeConfig("biht", iters=8, ht="bisect")]

    # single-device reference (no mesh): constrain degrades to a no-op
    refs = [np.asarray(jax.jit(lambda y, c=c: decode(y, phi, k, c))(y))
            for c in cfgs]

    # chunk-sharded: the chunk dim rides the model axis (DESIGN.md §4/§9).
    # Rows are decoded independently, but per-layout GEMM blocking may
    # round differently — allclose, not bitwise.
    mesh = auto_mesh((1, 8), ("data", "model"))
    ysh = jax.device_put(y, NamedSharding(mesh, P("model", None)))
    with jax.set_mesh(mesh):
        for c, ref in zip(cfgs, refs):
            got = jax.jit(lambda y, c=c: decode(y, phi, k, c))(ysh)
            assert len(got.sharding.device_set) == 8, (c.algorithm,
                                                       got.sharding)
            assert np.allclose(np.asarray(got), ref, atol=1e-2), (
                c.algorithm, np.abs(np.asarray(got) - ref).max())

    # end-to-end reconstruct_chunks under the mesh matches off-mesh
    ob = OBCSAAConfig(chunk=512, measure=256, topk=32, biht_iters=8,
                      spmd_topk=True, phi_seed=5)
    mags = jnp.ones((n,))
    ref_flat = np.asarray(jax.jit(
        lambda y: reconstruct_chunks(ob, y, mags, phi))(y))
    with jax.set_mesh(mesh):
        got_flat = np.asarray(jax.jit(
            lambda y: reconstruct_chunks(ob, y, mags, phi))(ysh))
    assert np.allclose(got_flat, ref_flat, atol=1e-2), np.abs(
        got_flat - ref_flat).max()
    print("SHARDED_DECODE_OK")
""")


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=560)


@pytest.mark.slow
def test_sharded_decode_matches_single_device():
    r = _run(SHARDED_SCRIPT)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    assert "SHARDED_DECODE_OK" in r.stdout
