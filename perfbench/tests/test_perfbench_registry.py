"""BENCHMARK.json against the shape its harness expects, every cell, configuration,
traffic, limit, driver and metric found by name, and a throwaway cell and
metric added as new files only."""
import json
import re

import pytest

from perfbench import harness, peaks
from perfbench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_lines():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert one_line(c["source"]) and one_line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert one_line(m["layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_files(cell):
    ctx = harness.Context(BENCH, cell, 1)
    configs = {c["name"]: c for c in BENCH["configs"]}
    entry = configs[ctx.cell["config"]]
    assert (tiny.ROOT / entry["file"]).resolve() == (
        tiny.BENCH / "configs" / f"{ctx.cell['config']}.json")
    assert ctx.config["name"] == ctx.cell["config"]
    assert set(entry["reduced"]) == set(ctx.config["reduced"])
    assert harness.driver_class(ctx).__name__ == "Driver"
    assert ctx.limits, f"no limits for {cell}"
    for name, lim in ctx.limits.items():
        assert lim["limit"] > 0 and lim["lower"] < lim["limit"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                   "end_to_end")}
    layer = harness.cell_metrics(BENCH, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e


def test_every_metric_has_a_reader_and_every_config_a_cell():
    for m in BENCH["per_layer"]:
        path = tiny.BENCH / "metrics" / f"{m['name']}.py"
        assert callable(harness.load_module(path, "m").read)
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in BENCH["workloads"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_throwaway_cell_and_metric_are_new_files_only(tmp_path, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        peaks.Peak(1e12, 1e12, 1e11, 1e9))
    entry = {"name": "window_units.paper", "unit": "sweeps",
             "better": "higher", "source": "host_clock",
             "layer": "round programs", "moves": "paper_rounds_per_s",
             "workloads": ["tiny-paper"]}
    source = ("def read(r):\n"
              "    return r['window']['units']\n")
    res = tiny.run(tmp_path, "tiny-paper", trace=1,
                   extra_metrics=[(entry, source)])
    assert res["metrics"]["window_units.paper"]["value"] >= 1
    assert res["metrics"]["window_units.paper"]["unit"] == "sweeps"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
