"""Roofline analysis (deliverable g): derive the three terms per
(arch x shape x mesh) from the dry-run artifacts.

  compute    = HLO_FLOPs / peak_FLOPs            (per device)
  memory     = HLO_bytes / HBM_bw                (per device)
  collective = wire_bytes / (links x link_bw)    (per device)

Peaks are per chip, keyed by the ``device_kind`` each dry-run record
names (the kind of the production mesh it was lowered for, from
``launch/mesh.py``). TPU v5e (Google Cloud "TPU v5e": 197 TFLOP/s bf16,
819 GB/s HBM; ~50 GB/s/link ICI, 4 links in a 2D torus) is the only kind;
a record of another kind, or of none, is an error.
MODEL_FLOPS = 6·N·D (dense; N_active for MoE) for the useful-compute ratio.
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config

# device_kind -> (bf16 FLOP/s, HBM B/s, B/s per ICI link, ICI links), per chip
PEAKS = {"TPU v5 lite": (197e12, 819e9, 50e9, 4)}     # TPU v5e

DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def active_params(cfg) -> int:
    """Activated parameters per token (MoE: shared + top-k of routed)."""
    n = cfg.param_count()
    if cfg.moe is None:
        return n
    m = cfg.moe
    n_mats = 3 if cfg.gated_mlp else 2
    per_expert = n_mats * cfg.d_model * cfg.d_ff
    routed_total = cfg.num_layers * m.num_experts * per_expert
    routed_active = cfg.num_layers * m.top_k * per_expert
    return n - routed_total + routed_active


def model_flops(cfg, shape, kind: str) -> float:
    """6·N_active·tokens for train; 2·N_active·tokens for inference."""
    na = active_params(cfg)
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * na * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * na * tokens
    return 2.0 * na * shape.global_batch      # decode: one token per seq


def analyze(rec: dict) -> dict:
    from benchmarks.analytic import bytes_per_device, flops_per_device
    kind = rec.get("device_kind")
    if kind not in PEAKS:
        raise ValueError(f"roofline: no peaks for device kind {kind!r} "
                         f"(known: {sorted(PEAKS)})")
    peak_flops, hbm_bw, link_bw, n_links = PEAKS[kind]
    cfg = get_config(rec["arch"])
    shape = INPUT_SHAPES[rec["shape"]]
    agg = rec.get("agg") or "mean"
    n_dev = rec["n_devices"]
    # compute/memory: analytic napkin models (XLA aggregate cost_analysis
    # counts scan bodies once — see analytic.py); collectives: exact HLO
    # parse with while trip-count scaling.
    flops_dev = flops_per_device(cfg, shape, n_dev, agg)
    bytes_dev = bytes_per_device(cfg, shape, n_dev, agg)
    wire = rec["collectives"].get("total_wire_bytes",
                                  rec["collectives"]["total_bytes"])
    t_comp = flops_dev / peak_flops
    t_mem = bytes_dev / hbm_bw
    t_coll = wire / (n_links * link_bw)
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    mf_dev = model_flops(cfg, shape, shape.kind) / n_dev
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        **{k: round(v, 6) for k, v in terms.items()},
        "bottleneck": dom.replace("_s", ""),
        "model_flops_per_dev": mf_dev,
        "useful_ratio": round(mf_dev / flops_dev, 3) if flops_dev else None,
        "hlo_flops_dev": rec["cost"].get("flops", 0.0),
        "step_time_bound_s": round(max(terms.values()), 6),
    }


def signal_path_rows():
    """Bytes moved through the 1-bit signal path, f32 vs packed codec
    (DESIGN.md §13) — STATIC accounting from the paper geometry, no
    dry-run artifacts needed, so the flags are deterministic for CI.

    Projection writes the sign measurements (f32 4 B/sym → packed
    1/8 B/sym: 32x); backprojection reads the sign-consistency residual
    (f32 4 B/sym → two uint32 bit-planes, 1/4 B/sym: 16x). Both clear the
    ≥4x reduction bar (``ge4`` flag)."""
    rows = []
    n_chunks, S = 13, 1024          # paper §V: D=50,890, D_c=4096, S_c=1024
    n_sym = n_chunks * S
    for name, f32_b, packed_b in (
            ("projection_out", 4 * n_sym, n_sym // 8),
            ("backprojection_resid_in", 4 * n_sym, 2 * (n_sym // 8))):
        ratio = f32_b / packed_b
        rows.append((f"roofline/signal_bytes/{name}", float(packed_b),
                     f"bytes_f32={f32_b};bytes_packed={packed_b};"
                     f"ratio={ratio:.1f};ge4={ratio >= 4.0}"))
    return rows


def main():
    rows = signal_path_rows()
    for arch in ASSIGNED_ARCHS:
        for shape in INPUT_SHAPES:
            for mesh_tag in ("single",):
                for agg in ("obcsaa", "mean"):
                    p = DRYRUN_DIR / f"{arch}__{shape}__{mesh_tag}__{agg}.json"
                    if not p.exists():
                        continue
                    rec = json.loads(p.read_text())
                    if rec.get("status") != "ok":
                        rows.append((f"roofline/{arch}/{shape}/{agg}", 0.0,
                                     rec.get("status")))
                        continue
                    a = analyze(rec)
                    rows.append((
                        f"roofline/{arch}/{shape}/{agg}",
                        a["step_time_bound_s"] * 1e6,
                        f"bottleneck={a['bottleneck']};"
                        f"compute={a['compute_s']:.4f}s;"
                        f"memory={a['memory_s']:.4f}s;"
                        f"collective={a['collective_s']:.4f}s;"
                        f"useful={a['useful_ratio']}"))
                    break   # one agg per pair in the table
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    main()
