"""A throwaway benchmark directory at a size a CPU test can run: the real
drivers and metric readers, with tiny configurations, traffic and limits
written beside them, named ``tiny-zoo`` and ``tiny-paper``."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# limits for the tiny cells on the CPU, set as the cells' own are: above
# what sound runs read, below the control and the faults (seeds 1-3).
# tiny-zoo: program residual_gap <= 0.0096, grad_gap_median <= 0.0031,
# change_gap_median <= 0.0065; fp8 control residual_gap >= 0.25; half
# batch grad_gap_median >= 0.37. tiny-paper: program update_gap <= 1e-6,
# change_gap <= 1.2e-8, bt_gap <= 4e-8; the control in three bfloat16
# passes change_gap >= 2.8e-7; half batch update_gap >= 1.
LIMITS = {
    "tiny-zoo": {"residual_gap": 0.05, "grad_gap_median": 0.01,
                 "change_gap_median": 0.015},
    "tiny-paper": {"update_gap": 1e-3, "change_gap": 6e-8,
                   "bt_gap": 1e-6},
}


def make(d: Path, extra_metrics=()) -> Path:
    """Write the tiny benchmark under ``d``; returns its BENCHMARK.json.
    ``extra_metrics``: (entry, reader source) pairs added as new files."""
    for sub in ("drivers", "metrics"):
        shutil.copytree(BENCH / sub, d / sub)
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir()
    zoo = json.loads((BENCH / "configs/mamba2-2.7b-zoo.json").read_text())
    zoo["model"].update(d_model=64, n_layers=2, vocab=128, vocab_size=120,
                        d_state=16, head_dim=16, n_groups=2, chunk_size=16,
                        dtype="float32")
    zoo["round"].update(chunk=1024)
    paper = json.loads((BENCH / "configs/mnist-mlp-paper.json").read_text())
    paper["round"].update(workers=4, samples_per_worker=64)
    (d / "configs/tiny-zoo.json").write_text(json.dumps(zoo))
    (d / "configs/tiny-paper.json").write_text(json.dumps(paper))
    (d / "traffic/tiny-seq.json").write_text(json.dumps(
        {"batch": 1, "seq": 32}))
    (d / "traffic/tiny-sweep.json").write_text(json.dumps(
        {"sweep_rounds": 6, "eval_every": 3, "eval_samples": 64}))
    for cell, lim in LIMITS.items():
        (d / f"limits/{cell}.json").write_text(json.dumps(
            {"numbers": {k: {"limit": v} for k, v in lim.items()}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": "tiny-zoo", "config": "tiny-zoo", "traffic": "tiny-seq",
         "chips": 1, "why": "a tiny zoo-train round"},
        {"name": "tiny-paper", "config": "tiny-paper",
         "traffic": "tiny-sweep", "chips": 1, "why": "a tiny paper round"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            train = "train" in m["name"] or "train" in m.get("moves", "")
            m["workloads"] = ["tiny-zoo" if train else "tiny-paper"]
    for entry, source in extra_metrics:
        bench["per_layer"].append(entry)
        (d / "metrics" / f"{entry['name']}.py").write_text(source)
    path = d / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def run(d: Path, cell: str, seed: int = 1, trace: int = 0,
        extra_metrics=()):
    """One CPU run of a tiny cell through the harness, without the
    persistent compile cache; the result. A traced run reads the peaks of
    the device kind "cpu", which the caller adds to the table."""
    from perfbench import harness
    bench = make(d, extra_metrics)
    return harness.run(["--workload", cell, "--seed", str(seed),
                        "--seconds", "0.5", "--trace", str(trace)],
                       bench_path=bench, bench_dir=d, require_tpu=False,
                       cache=False)
