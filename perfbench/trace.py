"""Reduction of a profiler trace to device busy time, idle share,
collective share and the breakdown of a traced window.

``load`` reads the ``.xplane.pb`` the JAX profiler writes: device
operations from each device plane's "XLA Ops" line, and the benchmark's
host spans (``bench.<name>`` annotations) from the host plane, all on the
trace's one clock. ``summarize`` works on that plain form, so it can be
checked on a hand-built trace.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import List, NamedTuple, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "window"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|psum")
TOP = 10
NAME_CHARS = 120


class Trace(NamedTuple):
    ops: List[Tuple[str, str, int, int]]      # (device, name, start, end) ns
    spans: List[Tuple[str, int, int]]         # (name, start, end) ns
    lines: List[str]                          # "plane / line (events)"


def load(trace_dir) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, spans, lines = [], [], []
    for f in files:
        pd = ProfileData.from_file(str(f))
        for plane in pd.planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                events = list(line.events)
                lines.append(f"{plane.name} / {line.name} ({len(events)})")
                for e in events:
                    start = int(e.start_ns)
                    end = start + int(e.duration_ns)
                    if device and line.name == OPS_LINE:
                        ops.append((plane.name, e.name, start, end))
                    elif not device and e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], start,
                                      end))
    return Trace(ops, spans, lines)


def merge(intervals):
    """Union of [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def self_times(events):
    """(name, self ns) of properly nested [start, end) events on one line:
    an event's length less that of the events directly inside it (a while
    loop's body ops are reported inside the loop's own event)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n, s0, e0, inner = stack.pop()
            out.append((n, e0 - s0 - inner))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    out += [(n, e0 - s0 - inner) for n, s0, e0, inner in stack]
    return out


def short(name: str) -> str:
    return name if len(name) <= NAME_CHARS else name[:NAME_CHARS] + "..."


def summarize(tr: Trace) -> dict:
    """busy_s (mean over devices of the union of op intervals inside the
    window), window_s, the idle share, the collective share of op self
    time, and the breakdown: the device ops with the most self time
    (averaged over devices) and the longest idle gaps of the first device,
    each named by the host span that overlaps it most."""
    windows = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if windows:
        w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    elif tr.ops:
        w0, w1 = min(o[2] for o in tr.ops), max(o[3] for o in tr.ops)
    else:
        raise ValueError("trace has neither a window span nor device ops")
    devices = sorted({d for d, *_ in tr.ops})
    busy, per_op, coll = {}, {}, 0
    for d in devices:
        iv = [(name, *_clip(s, e, w0, w1)) for dd, name, s, e in tr.ops
              if dd == d]
        iv = [(n, s, e) for n, s, e in iv if e > s]
        busy[d] = merge([(s, e) for _, s, e in iv])
        for name, t in self_times(iv):
            per_op[name] = per_op.get(name, 0) + t
            if COLLECTIVE.search(name.split(" = ")[0]):
                coll += t
    n_dev = max(len(devices), 1)
    busy_ns = sum(e - s for d in devices for s, e in busy[d]) / n_dev
    window_ns = w1 - w0
    gaps = []
    if devices:
        prev = w0
        for s, e in busy[devices[0]] + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    named = []
    host = [(n, s, e) for n, s, e in tr.spans if n != WINDOW_SPAN]
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, label = 0, "none"
        for n, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, label = ov, n
        named.append([label, (g1 - g0) / 1e9])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    all_op_ns = sum(per_op.values())
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "collective_share": coll / all_op_ns if all_op_ns else None,
        "devices": devices,
        "n_ops": len(tr.ops),
        "breakdown": {"device_ops": [[short(n), t / n_dev / 1e9]
                                     for n, t in top],
                      "idle_gaps": named},
    }
