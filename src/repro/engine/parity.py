"""Sharded zoo round vs its single-device reference: what must agree, and
how closely (ROADMAP Design 3).

Bitwise equality holds only where it is exact by construction: the packed
int32 MAC (integer adds of ±1 are associative), the sign codec, and the
serve cache. Everything downstream of a float reduction is compared
within the tolerances below, each next to its reason. The same comparison
gates the 4x2 host-mesh tests and ``chip_smoke.py --four-chips``.

A row is one chunk (the last axis, D_c elements). A row matches when every
element satisfies ``|got - want| <= atol + rtol * |want|`` with
``atol = atol_frac * max|want|`` over the whole leaf; a NaN on either side
never matches. A leaf passes when at least ``min_rows`` of its rows match
and its max|diff| and relative L2 difference are finite.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Tolerance(NamedTuple):
    rtol: float
    atol_frac: float
    min_rows: float
    reason: str


# The sharded program and the reference sum the same f32 terms in another
# order (psum over devices vs a local sum, fusion chosen per shape), which
# moves final ulps: ~1e-7 relative per reduction, compounded over the
# decode's iterations and a few rounds. Read on the 4x2 host-mesh gates:
# every leaf without a flipped chunk within 2e-7 relative L2, max|diff|
# 2.4e-7; rtol 1e-3 is over three orders above that.
_REORDER = ("f32 reductions reordered across devices and fusions; "
            "~1e-7 relative each, compounded over decode iterations")
# An ulp difference can move a discrete choice: the kappa-th largest
# magnitude of a chunk (top-kappa), or the sign of a projection within an
# ulp of zero. Either changes that one chunk's decoded update, by up to lr
# per element, and no other chunk. Readings (rows matched, worst leaf):
# the 4x2 host-mesh gates 0.9999 (a 2-arm sweep's master), every other
# leaf 1.0; the 2x2 chip round 0.9998 for master and moments, 0.9840 for
# the EF residual, whose top-kappa of a corrected gradient ties more often.
# A fault in one worker or one model shard moves 1/U or 1/n_model of the
# rows, a layout error every row; the limits sit between the readings and
# those shares: master and moments 0.99, so a fault on an axis of up to 100
# fails; the residual 0.97, an axis of up to 33.
_FLIPS = ("a near-tie in a chunk's top-kappa or a projection within an ulp "
          "of zero flips that chunk alone; a fault in one worker or model "
          "shard moves 1/U or 1/n_model of the chunks")

TOLERANCES = {
    "master": Tolerance(1e-3, 1e-4, 0.99, f"{_REORDER}; {_FLIPS}"),
    "moment": Tolerance(1e-3, 1e-4, 0.99, f"{_REORDER}; {_FLIPS}"),
    "residual": Tolerance(1e-3, 1e-4, 0.97,
                          "residual = corrected grad - its top-kappa: "
                          f"{_FLIPS}"),
}
# The loss is telemetry: the mesh reduces it as psum/U, the reference as a
# mean over a lax.map, so only the reduction order differs.
LOSS_RTOL = 1e-5


class LeafReport(NamedTuple):
    name: str
    max_abs: float
    rel_l2: float
    rows_matched: float
    ok: bool

    def line(self) -> str:
        return (f"{self.name}: max|diff|={self.max_abs:.3e} "
                f"rel_l2={self.rel_l2:.3e} rows_matched="
                f"{self.rows_matched:.4f} {'ok' if self.ok else 'FAIL'}")


@jax.jit
def _leaf_stats(got, want, base, rtol, atol_frac):
    """(max|diff|, ‖diff‖, ‖want‖, fraction of rows matched), reduced
    on the device: a zoo master is ~10^8 elements, too many to pull to the
    host for every leaf of every round."""
    got, want = got - base, want - base
    diff = jnp.abs(got - want)
    scale = jnp.abs(want)
    atol = atol_frac * jnp.max(scale, initial=0.0)
    # written so that a NaN on either side is bad: every comparison with
    # NaN is False
    bad = ~(diff <= atol + rtol * scale)
    rows = bad.reshape(-1, bad.shape[-1]) if bad.ndim else bad.reshape(1, 1)
    return (jnp.max(diff, initial=0.0), jnp.linalg.norm(diff),
            jnp.linalg.norm(want), 1.0 - jnp.mean(jnp.any(rows, axis=-1)))


def compare_leaf(name, got, want, tol: Tolerance, *, base=None) -> LeafReport:
    """Compare one carry leaf (``base`` given: compare the change from
    ``base``, so a small update is not hidden under large weights).
    Integer leaves (adam's step counter) must be equal. The arithmetic is
    float32 on one device of ``want``; every operand is copied there."""
    want = jnp.asarray(want)
    dev = min(want.devices(), key=lambda d: d.id)
    want, got = (jax.device_put(jnp.asarray(x), dev) for x in (want, got))
    if got.shape != want.shape:
        raise ValueError(f"{name}: shape {got.shape} != {want.shape}")
    if not jnp.issubdtype(want.dtype, jnp.floating):
        same = bool(jnp.array_equal(got, want))
        return LeafReport(name, 0.0 if same else float("inf"),
                          0.0 if same else float("inf"), float(same), same)
    base = (jnp.zeros((), want.dtype) if base is None
            else jax.device_put(jnp.asarray(base), dev))
    max_abs, n_diff, n_want, matched = map(float, _leaf_stats(
        got, want, base, tol.rtol, tol.atol_frac))
    rel = n_diff / n_want if n_want else max_abs
    ok = (matched >= tol.min_rows and math.isfinite(max_abs)
          and math.isfinite(rel))
    return LeafReport(name, max_abs, rel, matched, ok)


def compare_states(got, want, *, master0=None, tag: str = ""):
    """Reports for every leaf of two ``ZooTrainState`` carries: the
    master (as its change from ``master0`` when given), each optimizer
    moment, and the EF residual."""
    pre = f"{tag} " if tag else ""
    out = [compare_leaf(pre + "master", got.master, want.master,
                        TOLERANCES["master"], base=master0)]
    for i, (g, w) in enumerate(zip(jax.tree_util.tree_leaves(got.opt),
                                   jax.tree_util.tree_leaves(want.opt))):
        out.append(compare_leaf(f"{pre}opt[{i}]", g, w, TOLERANCES["moment"]))
    if want.residual is not None:
        out.append(compare_leaf(pre + "residual", got.residual,
                                want.residual, TOLERANCES["residual"]))
    return out


def assert_reports(reports):
    """Raise AssertionError naming every leaf outside its tolerance."""
    bad = [r.line() for r in reports if not r.ok]
    if bad:
        raise AssertionError("outside tolerance:\n" + "\n".join(bad))
