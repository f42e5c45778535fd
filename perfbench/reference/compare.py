"""The numbers that decide ``correct``: what the timed path produced over
its first three training steps against the plain reference.

A reading is a dict of host lists:

- ``loss``: the loss of each of the first three steps;
- ``grad``: per leaf, the norm of the first step's gradient as the
  optimizer got it (worked out from the optimizer state after one step);
- ``change``: per leaf, the norm of the parameters' change after three
  steps;
- ``ref_grad_max`` (the reference only): per leaf, the largest norm of the
  gradient the reference's optimizer got over the three steps; a leaf under
  ``EXCLUDE_BELOW`` times the median of it moves by round-off alone and is
  left out of ``change_gap``;
- ``b_t`` (cells that schedule): the power scale of each step;
- ``update`` with ``chunk`` (cells whose decode sets each chunk's norm):
  the first step's update as one flat vector, and the codec's chunk
  length;
- ``residual`` with ``leaves`` (cells with error feedback): the error-
  feedback residual after the first step, (workers, entries), which is
  the model's gradient less the entries the codec sent, and each leaf's
  (offset, size) in it.

Every gap is a gap of norms, measured against the reference's norm of
that leaf or of the median leaf, whichever is larger, and taken by the
worst leaf (``grad_gap``, ``change_gap``) or by the median leaf
(``grad_gap_median``, ``change_gap_median``). ``update_gap`` compares
directions instead: where the decode scales every chunk to a received
norm, a gap of norms cannot see a chunk decoded the wrong way, so it is
the norm of the difference of the two updates in each chunk over the
reference's norm there, taken by the median chunk. ``residual_gap``
compares the model's own gradient before the codec's choices alike: per
leaf, the norm of the difference of the two residuals over the
reference's norm of that leaf or of the median leaf, by the median leaf.
A cell's limits file names the numbers it compares; the others are
readings for setting limits.
"""
from __future__ import annotations

import numpy as np

EXCLUDE_BELOW = 1e-3


def _leaf_gaps(got, want, counted=None):
    """Per leaf: |got - want| over max(want, median of want)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if counted is not None:
        got, want = got[counted], want[counted]
    if not np.all(np.isfinite(got)):
        return np.asarray([np.inf])
    base = np.maximum(want, np.median(want))
    return np.abs(got - want) / np.maximum(base, 1e-30)


def chunk_gaps(got, want, chunk: int):
    """Per chunk of ``chunk`` entries (the last zero-padded): the norm of
    got - want over the norm of want."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return np.asarray([np.inf])
    pad = -want.size % chunk
    got = np.pad(got, (0, pad)).reshape(-1, chunk)
    want = np.pad(want, (0, pad)).reshape(-1, chunk)
    return (np.linalg.norm(got - want, axis=1)
            / np.maximum(np.linalg.norm(want, axis=1), 1e-30))


def _diff_leaf_gaps(got, want, leaves):
    """Per leaf of (offset, size) in the rows of got and want: the norm of
    got - want over the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    diff, base = [], []
    for o, n in leaves:
        a, b = got[:, o:o + n], want[:, o:o + n]
        if not np.all(np.isfinite(a)):
            return np.asarray([np.inf])
        diff.append(float(np.linalg.norm(a - b)))
        base.append(float(np.linalg.norm(b)))
    base = np.asarray(base)
    return np.asarray(diff) / np.maximum(np.maximum(base, np.median(base)),
                                         1e-30)


def numbers(prog: dict, ref: dict) -> dict:
    """{name: gap} of the program's reading against the reference's."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))
           if np.all(np.isfinite(lp)) else float("inf")}
    gaps = _leaf_gaps(prog["grad"], ref["grad"])
    out["grad_gap"] = float(np.max(gaps))
    out["grad_gap_median"] = float(np.median(gaps))
    gmax = np.asarray(ref["ref_grad_max"], np.float64)
    counted = gmax >= EXCLUDE_BELOW * np.median(gmax)
    gaps = _leaf_gaps(prog["change"], ref["change"], counted)
    out["change_gap"] = float(np.max(gaps))
    out["change_gap_median"] = float(np.median(gaps))
    if "b_t" in ref:
        bp = np.asarray(prog["b_t"], np.float64)
        br = np.asarray(ref["b_t"], np.float64)
        out["bt_gap"] = float(np.max(np.abs(bp - br) / np.abs(br)))
    if "residual" in ref:
        out["residual_gap"] = float(np.median(_diff_leaf_gaps(
            prog["residual"], ref["residual"], ref["leaves"])))
    if "update" in ref:
        out["update_gap"] = float(np.median(chunk_gaps(
            prog["update"], ref["update"], ref["chunk"])))
    return out


def judge(nums: dict, limits: dict):
    """(correct, lines): every number the limits name at or under its
    limit; a limit with no number, or no limits at all, is not correct."""
    lines, ok = [], bool(limits)
    for name in sorted(limits):
        value = nums.get(name)
        limit = limits.get(name, {}).get("limit")
        passed = (value is not None and limit is not None
                  and np.isfinite(value) and value <= limit)
        ok &= passed
        lines.append((name, value, limit, passed))
    return ok, lines
