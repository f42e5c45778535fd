"""One run of one benchmark cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``perfbench/configs/<config>.json``, which names its path
driver ``perfbench/drivers/<driver>.py``) and its traffic
(``perfbench/traffic/<traffic>.json``); its limits are in
``perfbench/limits/<cell>.json``; each per-layer metric is read by
``perfbench/metrics/<metric>.py``. Adding any of them adds files only.

A run: find the chips (a run without a TPU, or with fewer chips than the
cell asks for, exits non-zero and prints no result), keep JAX's compile
cache in ``<checkout>/.jax_cache``, set up (the path driver builds the program,
warms every shape of the window and drives the first three steps, whose
readings it keeps), measure for ``--seconds``, read the peak device
memory, then with ``--trace 1`` trace a short window and run the
per-layer probes, free the program and compare with the reference. The
numbers compared are printed with their limits as the last lines of
standard error and under ``checks`` in the result, the last line of
standard output.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_MIN_S = 2.0

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Fail(Exception):
    """A run that cannot produce a result; exits non-zero."""


class CompileClock:
    """XLA compile seconds per program (persistent-cache reads included)
    and persistent-cache hits, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.programs, self.hits = [], 0

        def on_duration(event, duration, **_):
            if event == _COMPILE_EVENT:
                self.programs.append(duration)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def span(name: str):
    """A host span around a call the benchmark makes; while a trace runs it
    is written into the trace as ``bench.<name>``."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


class Context:
    """What a driver is given: the cell's files and the seed."""

    def __init__(self, bench: dict, workload: str, seed: int,
                 bench_dir: Path = BENCH_DIR):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise Fail(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.seed = seed
        self.bench_dir = Path(bench_dir)
        self.config = load_json(self.bench_dir / "configs"
                                / f"{self.cell['config']}.json")
        self.traffic = load_json(self.bench_dir / "traffic"
                                 / f"{self.cell['traffic']}.json")
        lim = self.bench_dir / "limits" / f"{workload}.json"
        self.limits = load_json(lim)["numbers"] if lim.exists() else {}


def load_json(path: Path) -> dict:
    if not path.exists():
        raise Fail(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_class(ctx: Context):
    path = ctx.bench_dir / "drivers" / f"{ctx.config['driver']}.py"
    if not path.exists():
        raise Fail(f"missing path driver {path}")
    return load_module(path, f"perfbench_driver_{ctx.config['driver']}"
                       ).Driver


def cell_metrics(bench: dict, cell: str, kind: str):
    """The metrics of ``kind`` (end_to_end | per_layer) this cell reports:
    those that list it, and those that list no cells while the cell
    reports the end-to-end metric they move."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def find_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Fail(f"needs a TPU; JAX found {devs[0].platform} "
                   f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise Fail(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def enable_cache():
    """JAX's persistent compile cache at one fixed path in the checkout,
    every program written to it and none evicted (a size limit from the
    environment would evict a run's own set-up programs)."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced_window(driver, trace_dir: Path):
    """A short traced window of whole units (at least TRACE_MIN_S) and its
    reduction. Returns the trace summary."""
    import jax
    from perfbench import trace
    jax.profiler.start_trace(str(trace_dir))
    try:
        with span("window"):
            driver.window(TRACE_MIN_S, span)
    finally:
        jax.profiler.stop_trace()
    return trace.summarize(trace.load(trace_dir))


def read_metrics(ctx: Context, metrics, reading: dict) -> dict:
    out = {}
    for m in metrics:
        path = ctx.bench_dir / "metrics" / f"{m['name']}.py"
        if not path.exists():
            raise Fail(f"missing metric reader {path}")
        value = load_module(path, "perfbench_metric_"
                            + m["name"].replace(".", "_")).read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(argv=None, *, bench_path: Path = None, bench_dir: Path = None,
        require_tpu: bool = True, t_start: float = None,
        cache: bool = True) -> dict:
    """One run; returns the result object (also printed by ``main``).
    Tests pass ``require_tpu=False`` and ``cache=False`` to run on the CPU
    without the persistent cache."""
    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise Fail(f"no system under test: {ROOT / 'src' / 'repro'} is "
                   "missing")
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    ctx = Context(bench, args.workload, args.seed,
                  bench_dir or BENCH_DIR)
    devs = find_devices(ctx.cell["chips"], require_tpu)
    if cache:
        enable_cache()
    sys.path.insert(0, str(ROOT / "src"))
    clock = CompileClock()
    driver = driver_class(ctx)(ctx)
    driver.setup()
    setup_s = time.time() - t_start
    setup_programs = len(clock.programs)
    setup_compile_s = sum(clock.programs)
    print(f"setup: {setup_s:.2f} s; {setup_programs} programs compiled or "
          f"read from the persistent cache in {setup_compile_s:.2f} s, "
          f"{clock.hits} cache hits", file=sys.stderr)
    win = driver.window(args.seconds, span)
    in_window = len(clock.programs) - setup_programs
    memory = peak_bytes(devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    if args.trace:
        with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
            summary = traced_window(driver, Path(d))
        driver.probes(span)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        reading = {"summary": summary, "window": win,
                   "measures": driver.measures, "config": ctx.config,
                   "traffic": ctx.traffic, "device": device,
                   "compile": {"seconds": setup_compile_s,
                               "programs": setup_programs},
                   "chips": len(devs)}
        metrics = read_metrics(ctx, cell_metrics(bench, ctx.name,
                                                 "per_layer"), reading)
    else:
        metrics = {}
        for m in cell_metrics(bench, ctx.name, "end_to_end"):
            value = (setup_s if m["name"] == "setup_s"
                     else win["metrics"].get(m["name"]))
            if value is None:
                raise Fail(f"the driver measured no {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    driver.free()
    nums, _ = driver.check()
    from perfbench.reference import compare
    _, checks = compare.judge(nums, ctx.limits)
    checks += [("compiles_in_window", in_window, 0, in_window == 0),
               ("nonfinite_losses", driver.nonfinite, 0,
                driver.nonfinite == 0)]
    result = {"correct": all(ok for *_, ok in checks),
              "attempted": win["units"], "failed": int(driver.nonfinite),
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = summary["breakdown"]
    # the numbers compared, beside their limits: the last lines of
    # standard error, and the last key of the result
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ in checks}
    return result


def main(argv=None, t_start: float = None) -> int:
    try:
        result = run(argv, t_start=t_start)
    except Fail as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result, default=_jsonable))
    return 0


def _jsonable(x):
    if hasattr(x, "item"):
        return x.item()
    raise TypeError(f"not JSON: {type(x)}")
