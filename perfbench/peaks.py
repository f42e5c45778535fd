"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. A device
kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {"TPU v5 lite": Peak(197e12, 393e12, 819e9, 16e9)}


def peak(kind: str) -> Peak:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]
