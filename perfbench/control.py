"""Readings from which a cell's limits are set: the program's sound runs,
the control and the planted faults, over many seeds in one process.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \
        [--control] [--faults] [--first N] [--out <file.jsonl>]

Per seed it drives the program through its first three steps (the same
driver and compiled programs as a benchmark run, no window), frees it,
and reads the reference (``reference_policy`` of the configuration). With
``--control`` it also reads the reference computed in the configuration's
``control_policy`` (the nearest precision below the stated one) in the
program's place; with ``--faults`` the reference with each of the
driver's planted ``FAULTS`` (half of every batch left out of the loss on
every path), and a state returned unchanged (which needs no run: its
gradient and change read zero). Each is compared with the reference
by ``reference.compare.numbers``. One JSON line per seed.

Benchmark runs never run this; it needs a TPU as they do.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # the checkout root, not this directory, heads the import path
    sys.path[0] = str(Path(__file__).resolve().parents[1])

import numpy as np  # noqa: E402

from perfbench import harness  # noqa: E402
from perfbench.reference import compare  # noqa: E402


def frozen(ref: dict) -> dict:
    """The reading of a step that returns its state unchanged."""
    out = dict(ref, grad=[0.0] * len(ref["grad"]),
               change=[0.0] * len(ref["change"]))
    for key in ("update", "residual"):
        if key in ref:
            out[key] = np.zeros_like(ref[key])
    out.pop("ref_grad_max", None)
    return out


def light(reading: dict) -> dict:
    """A reading without its flat vectors, for the JSON line."""
    return {k: v for k, v in reading.items()
            if not isinstance(v, np.ndarray)}


def readings(driver, cfg: dict, seed: int, control: bool, faults: bool):
    t0 = time.time()
    prog = driver.readings
    driver.free()
    want = driver.reference(cfg["reference_policy"])
    row = {"seed": seed, "program": light(prog), "reference": light(want),
           "numbers": {"program": compare.numbers(prog, want)}}
    if control:
        got = driver.reference(cfg["control_policy"])
        row["control"] = light(got)
        row["numbers"]["control"] = compare.numbers(got, want)
    if faults:
        for name, kw in driver.FAULTS.items():
            got = driver.reference(cfg["reference_policy"], **kw)
            row[name] = light(got)
            row["numbers"][name] = compare.numbers(got, want)
        row["numbers"]["frozen"] = compare.numbers(frozen(want), want)
    row["seconds"] = time.time() - t0
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--first", type=int, default=None,
                    help="read the control and faults on the first N "
                         "seeds only")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    first = len(seeds) if args.first is None else args.first
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    ctx = harness.Context(bench, args.workload, seeds[0])
    harness.find_devices(ctx.cell["chips"], require_tpu=True)
    harness.enable_cache()
    sys.path.insert(0, str(harness.ROOT / "src"))
    out = open(args.out, "a") if args.out else None
    driver = None
    try:
        for i, seed in enumerate(seeds):
            ctx = harness.Context(bench, args.workload, seed)
            cls = harness.driver_class(ctx)
            if driver is None or not hasattr(driver, "reseed"):
                driver = cls(ctx)
                driver.setup()
            else:
                driver.reseed(seed)
                driver.first_steps()
            row = readings(driver, ctx.config, seed,
                           args.control and i < first,
                           args.faults and i < first)
            line = json.dumps(row)
            print(json.dumps({"seed": seed, "numbers": row["numbers"],
                              "seconds": row["seconds"]}), flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
