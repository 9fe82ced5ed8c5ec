"""``BENCHMARK.json`` against the limits of the benchmark's contract that a
file check can see, and against the files it names."""

import json
import os
import re

import pytest

from conftest import BENCH_DIR, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    RAW = fh.read()
BENCH = json.loads(RAW)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert len(RAW) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(line(w) for w in BENCH["command"])
    assert BENCH["paths"] == ["benchmark"]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names)), f"a name twice in {key}"
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as fh:
            config = json.load(fh)
        assert config["name"] == c["name"]
        assert config["source"] == c["source"] and config["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(BENCH_DIR, "drivers", config["driver"] + ".py"))
        assert config["guarantees"]


def test_workloads():
    assert 1 <= len(BENCH["workloads"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"] for c in BENCH["configs"]}
    four = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        four += w["chips"] == 4
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def cells_of(metric):
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_metrics():
    e2e, layers = BENCH["end_to_end"], BENCH["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"))
    setup = next(m for m in e2e if m["name"] == "setup_s")
    assert "workloads" not in setup
    by_name = {m["name"]: m for m in e2e}
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in by_name and m["moves"] != "setup_s"
        # each cell that reports the layer metric reports the metric it moves
        assert set(cells_of(m)) <= set(cells_of(by_name[m["moves"]]))
        assert os.path.exists(os.path.join(BENCH_DIR, "layers", m["name"] + ".py"))
    for m in e2e + layers:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(cells_of(m)) <= cells
    for cell in cells:
        assert sum(1 for m in e2e if cell in cells_of(m)) >= 2, cell  # setup_s and one more
        assert any(cell in cells_of(m) for m in layers), cell


@pytest.mark.parametrize("folder", ["benchmark"])
def test_file_names(folder):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, _dirs, files in os.walk(os.path.join(ROOT, folder)):
        if "__pycache__" in base:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert ok.match(rel), rel
