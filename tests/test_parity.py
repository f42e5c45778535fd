"""The sharded-vs-reference comparison of ``repro.engine.parity``: what it
lets through (reordered f32 sums, a chunk-local flip) and what it must
not (non-finite state, a fault in one worker or model shard, a layout
error), plus the roofline's device-kind lookup."""
import numpy as np
import pytest

from benchmarks import roofline
from repro.engine import parity

ROWS, D_C = 100, 64
TOL = parity.TOLERANCES


def _want():
    return np.random.default_rng(0).standard_normal((ROWS, D_C)).astype(
        np.float32)


def _ulps(w):
    return w * np.float32(1 + 3e-7)


def _flip_one_chunk(w):
    g = w.copy()
    g[17] = -g[17]
    return g


def _nan_everywhere(w):
    return np.full_like(w, np.nan)


def _one_nan(w):
    g = w.copy()
    g[3, 5] = np.nan
    return g


def _one_inf(w):
    g = w.copy()
    g[3, 5] = np.inf
    return g


def _one_shard_of_16(w):
    """A wrong model shard (or worker) on a 16-way axis: 1/16 of rows."""
    g = w.copy()
    g[: -(-ROWS // 16)] = 0.0
    return g


def _layout_shift(w):
    return np.roll(w, 1, axis=-1)


@pytest.mark.parametrize("leaf", sorted(TOL))
@pytest.mark.parametrize("make_got, ok", [
    (lambda w: w, True),
    (_ulps, True),
    (_flip_one_chunk, True),
    (_nan_everywhere, False),
    (_one_nan, False),
    (_one_inf, False),
    (_one_shard_of_16, False),
    (_layout_shift, False),
], ids=["equal", "ulps", "one-chunk-flip", "all-nan", "one-nan", "one-inf",
        "one-shard-of-16", "layout-shift"])
def test_compare_leaf(leaf, make_got, ok):
    want = _want()
    rep = parity.compare_leaf(leaf, make_got(want), want, TOL[leaf])
    assert rep.ok is ok, rep.line()


def test_compare_leaf_nan_reference_fails():
    """A NaN in the reference is no free pass either."""
    want = _nan_everywhere(_want())
    rep = parity.compare_leaf("master", want, want, TOL["master"])
    assert not rep.ok, rep.line()


@pytest.mark.parametrize("delta, ok", [(0, True), (1, False)])
def test_compare_leaf_integer_exact(delta, ok):
    want = np.arange(12, dtype=np.int32).reshape(3, 4)
    rep = parity.compare_leaf("step", want + delta, want, TOL["moment"])
    assert rep.ok is ok, rep.line()


def test_compare_leaf_base_sees_small_update():
    """With ``base`` the change is compared, so an update lost under large
    weights is still caught."""
    base = 1e3 * np.ones((ROWS, D_C), np.float32)
    want = base + 1e-3 * _want()
    assert parity.compare_leaf("master", want, want, TOL["master"],
                               base=base).ok
    assert not parity.compare_leaf("master", base, want, TOL["master"],
                                   base=base).ok


@pytest.mark.parametrize("kind", [None, "TPU v4"])
def test_roofline_needs_a_known_device_kind(kind):
    rec = {"arch": "gemma2-2b", "shape": "train_4k", "n_devices": 256,
           "mesh": "16x16", "collectives": {"total_bytes": 0}}
    if kind is not None:
        rec["device_kind"] = kind
    with pytest.raises(ValueError, match="no peaks for device kind"):
        roofline.analyze(rec)
