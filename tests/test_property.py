"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    # offline container without hypothesis: run the same properties over a
    # deterministic example sweep instead of skipping the module
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.power_control import feasible, max_bt, tx_power
from repro.core.quantize import pack_bits, sign_pm1, unpack_bits
from repro.core.sparsify import topk_sparsify, topk_sparsify_chunked
from repro.dist.flat_layout import FlatShardLayout
from repro.kernels.sign import pack_signs, unpack_signs
from repro.models.layers import chunked_cross_entropy
from repro.models.registry import cross_entropy

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


class _StubMesh:
    """Just enough mesh for ``FlatShardLayout.build``: the layout consumes
    only ``dict(mesh.shape)`` (via ``dist.sharding._axis_sizes``), so
    property tests can sweep mesh shapes without allocating devices."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


@given(st.integers(1, 63), st.integers(0, 2 ** 31 - 1))
def test_topk_keeps_exactly_k_and_largest(k, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,))
    sx, mask = topk_sparsify(x, k)
    assert int(mask.sum()) == k
    kept_min = float(jnp.min(jnp.where(mask, jnp.abs(x), jnp.inf)))
    dropped_max = float(jnp.max(jnp.where(mask, -jnp.inf, jnp.abs(x))))
    assert kept_min >= dropped_max - 1e-7
    np.testing.assert_array_equal(np.asarray(sx != 0), np.asarray(mask))


@given(st.integers(1, 15), st.integers(1, 6), st.integers(0, 2 ** 31 - 1))
def test_topk_chunked_per_chunk_budget(k, nc, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (nc * 32,))
    _, mask = topk_sparsify_chunked(x, min(k, 32), 32)
    per_chunk = np.asarray(mask).reshape(nc, 32).sum(axis=1)
    assert (per_chunk == min(k, 32)).all()


@given(st.integers(0, 2 ** 31 - 1))
def test_sign_never_zero(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (128,))
    x = x.at[:7].set(0.0)
    s = sign_pm1(x)
    assert bool(jnp.all(jnp.abs(s) == 1.0))


@given(st.integers(1, 16).map(lambda n: n * 8), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_roundtrip(n, seed):
    s = sign_pm1(jax.random.normal(jax.random.PRNGKey(seed), (n,)))
    assert np.array_equal(np.asarray(unpack_bits(pack_bits(s), n)),
                          np.asarray(s))


@given(st.integers(2, 12), st.integers(0, 2 ** 31 - 1),
       st.floats(0.1, 100.0))
def test_max_bt_is_tight_and_feasible(u, seed, pmax):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(np.abs(rng.normal(size=u)) + 1e-3, jnp.float32)
    kw = jnp.asarray(rng.uniform(1, 100, u), jnp.float32)
    beta = jnp.asarray((rng.random(u) > 0.3).astype(np.float32))
    if float(beta.sum()) == 0:
        beta = beta.at[0].set(1.0)
    bt = max_bt(beta, kw, h, pmax)
    assert bool(feasible(beta, kw, bt, h, pmax))
    p = tx_power(beta, kw, bt, h)
    assert np.isclose(float(jnp.max(p)), pmax, rtol=1e-4)


@given(st.integers(0, 2), st.integers(1, 4), st.integers(1, 3),
       st.integers(0, 2 ** 31 - 1))
def test_flat_layout_chunk_unchunk_roundtrip(mp_exp, gran, cw, seed):
    """The model-major sharded-flat layout (dist.flat_layout, DESIGN.md
    §16/§17) is lossless and gran-aligned over randomized parameter
    structures and mesh shapes:

    - ``master_to_tree(tree_to_master(p))`` returns every leaf bitwise;
    - ``n_half`` is a whole multiple of ``gran`` (every worker owns whole
      chunk rows) and ``n_chunks == mp * n_half``;
    - section padding is exactly zero;
    - the device-local ``section_to_tree``/``tree_to_section`` pair
      round-trips each m-section bitwise — the invariant that makes
      layout conversion zero-communication in the round."""
    rng = np.random.default_rng(seed)
    mp, chunk = 2 ** mp_exp, 16 * cw
    shapes, params = {}, {}
    for i in range(int(rng.integers(1, 5))):
        r, c = int(rng.integers(1, 5)), int(rng.integers(1, 5))
        shape = ((mp * r, c), (c, mp * r), (mp * r,))[int(rng.integers(3))]
        shapes[f"w{i}"] = jax.ShapeDtypeStruct(shape, jnp.float32)
        params[f"w{i}"] = rng.standard_normal(shape).astype(np.float32)
    layout = FlatShardLayout.build(shapes, _StubMesh(data=gran, model=mp),
                                   chunk=chunk, gran=gran)
    assert layout.n_half % gran == 0
    assert layout.n_chunks == mp * layout.n_half
    assert layout.D == sum(v.size for v in params.values())
    assert layout.D_pad >= layout.D

    master = layout.tree_to_master(params)
    assert master.shape == (layout.n_chunks, chunk)
    back = layout.master_to_tree(master)
    for k in params:
        assert np.array_equal(np.asarray(back[k]), params[k]), k

    sections = np.asarray(master).reshape(mp, layout.n_half * chunk)
    assert (sections[:, layout.sec_elems:] == 0).all()   # pad is zero
    for m in range(mp):
        sect = master.reshape(mp, layout.n_half, chunk)[m]
        again = layout.tree_to_section(layout.section_to_tree(sect))
        assert np.array_equal(np.asarray(again), np.asarray(sect)), m


def test_flat_layout_indivisible_leaf_message():
    """A leaf with no model-divisible dim fails at build, naming the
    leaf (DESIGN.md §16)."""
    shapes = {"odd": jax.ShapeDtypeStruct((3, 5), jnp.float32)}
    with pytest.raises(ValueError, match=r"odd.*divisible by the "
                                         r"model-axis size 2"):
        FlatShardLayout.build(shapes, _StubMesh(data=1, model=2), chunk=8)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_signs_roundtrip_with_signed_zeros(rows, words, seed):
    """The 32-per-uint32 packed codec (kernels.sign, DESIGN.md §13):
    ``unpack_signs(pack_signs(s)) == s`` bitwise on ±1 symbols, and the
    fused sign+pack on RAW values agrees with sign-then-pack — including
    x == +0.0 and x == -0.0, both of which the repo-wide sign convention
    maps to +1 (the ``x >= 0`` predicate is signed-zero-blind)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, 32 * words)).astype(np.float32)
    flat = x.reshape(-1)
    idx = rng.choice(flat.size, size=min(8, flat.size), replace=False)
    flat[idx[0::2]] = 0.0
    flat[idx[1::2]] = -0.0
    x = jnp.asarray(flat.reshape(x.shape))
    s = sign_pm1(x)
    packed = pack_signs(x)
    assert np.array_equal(np.asarray(packed), np.asarray(pack_signs(s)))
    assert np.array_equal(np.asarray(unpack_signs(packed)), np.asarray(s))
    assert (np.asarray(s).reshape(-1)[idx] == 1.0).all()   # sign(±0) = +1


def test_pack_signs_misaligned_axis_message():
    """A sign axis that does not pack into whole uint32 words fails
    loudly with the offending length (DESIGN.md §13)."""
    with pytest.raises(ValueError, match=r"multiple of 32; got 40"):
        pack_signs(jnp.ones((2, 40)))


@given(st.integers(1, 4), st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_chunked_ce_equals_dense_ce(b, nb, seed):
    """The chunked-CE memory optimization is mathematically exact."""
    S, V, d = nb * 16, 37, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, S, d))
    emb = jax.random.normal(ks[1], (V, d))
    tgt = jax.random.randint(ks[2], (b, S), 0, V)
    dense = cross_entropy(x @ emb.T, tgt)
    chunked = chunked_cross_entropy(x, tgt, embedding=emb, seq_chunk=16)
    np.testing.assert_allclose(float(dense), float(chunked), rtol=1e-5)
