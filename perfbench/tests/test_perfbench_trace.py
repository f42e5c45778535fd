"""The reduction of a profiler trace to busy time, idle share, collective
share and the breakdown, on a hand-built trace and on a recorded one."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import trace

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def test_merge_unions_overlaps():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def hand_trace():
    ops = [(D0, "fusion.1", 100, 150),
           (D0, "all-gather.3", 150, 300),
           (D0, "fusion.2", 400, 500),
           (D0, "fusion.9", 50, 100),        # before the window
           (D1, "fusion.1", 100, 300)]
    spans = [("window", 100, 600), ("dispatch", 300, 340),
             ("block", 340, 420), ("batch", 500, 600)]
    return trace.Trace(ops, spans, [])


def test_summarize_busy_idle_and_collectives():
    s = trace.summarize(hand_trace())
    # device 0 busy [100, 300] + [400, 500] = 300 ns, device 1 200 ns
    assert s["busy_s"] == pytest.approx(250e-9)
    assert s["window_s"] == pytest.approx(500e-9)
    assert s["idle_share"] == pytest.approx(0.5)
    # op time inside the window: 50 + 150 + 100 + 200; all-gather 150
    assert s["collective_share"] == pytest.approx(150 / 500)
    assert s["devices"] == [D0, D1]


def test_breakdown_names_gaps_by_host_span():
    s = trace.summarize(hand_trace())
    # device 0 gaps: [300, 400] (block overlaps 60, dispatch 40) and
    # [500, 600] (batch)
    assert s["breakdown"]["idle_gaps"] == [["block", pytest.approx(1e-7)],
                                           ["batch", pytest.approx(1e-7)]]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(125e-9)   # (50 + 200) / 2
    assert list(ops)[0] == "fusion.1"


def test_gap_with_no_host_span_is_none():
    tr = trace.Trace([(D0, "f", 0, 10), (D0, "f", 50, 60)],
                     [("window", 0, 60)], [])
    s = trace.summarize(tr)
    assert s["breakdown"]["idle_gaps"] == [["none", pytest.approx(40e-9)]]
    assert s["idle_share"] == pytest.approx(40 / 60)


def test_load_reads_bench_spans_from_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(tmp_path)
    names = [n for n, _, _ in tr.spans]
    assert "window" in names and "dispatch" in names
    assert tr.lines


def test_nested_ops_count_self_time():
    # a loop op whose body ops are reported inside it
    tr = trace.Trace([(D0, "%while.1 = (...) while(...)", 0, 100),
                      (D0, "%fusion.2 = f32[] fusion(...)", 10, 40),
                      (D0, "%all-gather.3 = f32[] all-gather(...)", 50, 70)],
                     [("window", 0, 100)], [])
    s = trace.summarize(tr)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["%while.1 = (...) while(...)"] == pytest.approx(50e-9)
    assert ops["%fusion.2 = f32[] fusion(...)"] == pytest.approx(30e-9)
    assert s["collective_share"] == pytest.approx(20 / 100)
    assert s["idle_share"] == pytest.approx(0.0)
