"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run              # quick set
  PYTHONPATH=src python -m benchmarks.run --full       # paper-scale rounds
  PYTHONPATH=src python -m benchmarks.run --only fig1,roofline
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale round counts (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset: fig1..fig5,kernels,"
                         "decoders,sched,engine,theory,ablations,roofline,"
                         "zoo,serve")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(",")) if args.only else None
    rounds = 300 if args.full else 60

    from benchmarks import (ablations, decoders_bench, engine_bench,
                            fig1_sparsification, fig2_dimension,
                            fig3_scheduling, fig4_samples, fig5_noise,
                            kernels_bench, roofline, sched_bench,
                            serve_bench, theory_bench, zoo_bench)

    from benchmarks.common import cached_suite

    suites = {
        "fig1": lambda: fig1_sparsification.main(rounds=rounds),
        "fig2": lambda: fig2_dimension.main(rounds=rounds),
        "fig3": lambda: fig3_scheduling.main(rounds=max(40, rounds // 2)),
        "fig4": lambda: fig4_samples.main(rounds=max(40, rounds // 2)),
        "fig5": lambda: fig5_noise.main(rounds=max(40, rounds // 2)),
        "kernels": kernels_bench.main,
        "decoders": decoders_bench.main,
        "sched": sched_bench.main,
        "engine": engine_bench.main,
        "theory": theory_bench.main,
        "ablations": lambda: ablations.main(rounds=max(40, rounds // 2)),
        "roofline": roofline.main,   # cheap, always fresh (reads dryrun/)
        "zoo": lambda: zoo_bench.main(full=args.full),
        "serve": lambda: serve_bench.main(full=args.full),
    }
    # kernels + sched + engine + theory + roofline + zoo + serve always
    # run fresh: they are the CI smoke steps and must exercise real code,
    # not replay experiments/bench_cache.json (zoo and serve manage their
    # own expensive cached rows — ≥1B zoo, 1M-cell serve — while their
    # CI-scale rows, including every parity gate, run live)
    fresh = {"kernels", "sched", "engine", "theory", "roofline", "zoo",
             "serve"}
    # fig/ablation suites moved to engine arms sweeps (v2): the v1 cache
    # rows were produced by the pre-engine loop AND its half-normal
    # channel draw — keys are bumped so a full run regenerates them
    vkey = {"fig1": 2, "fig2": 2, "fig3": 2, "fig4": 2, "fig5": 2,
            "ablations": 2}
    print("name,us_per_call,derived", flush=True)
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        try:
            if name in fresh:
                fn()
            else:
                key = f"{name}:v{vkey[name]}:r{rounds}" if name in vkey \
                    else f"{name}:r{rounds}"
                cached_suite(key, fn)
        except Exception as e:  # run the other suites, then fail
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}",
                  file=sys.stdout, flush=True)
    sys.stdout.flush()
    if failed:
        raise SystemExit(f"benchmarks.run: suites raised: {','.join(failed)}")


if __name__ == "__main__":
    main()
