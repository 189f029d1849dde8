"""BENCHMARK.json against its contract, and every name in it against the
files the harness finds by that name."""

import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = _load(ROOT, "BENCHMARK.json")
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
END = {m["name"]: m for m in MANIFEST["end_to_end"]}
LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}


def _cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["perfbench", "tests/perfbench"]
    assert MANIFEST["command"] == ["python3", "perfbench/run.py"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_no_duplicate_names():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
    metrics = [m["name"] for m in MANIFEST["end_to_end"]] + \
        [m["name"] for m in MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", sorted(END) + sorted(LAYER))
def test_metric_entry(name):
    m = END.get(name) or LAYER[name]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    assert set(_cells_of(m)) <= set(CELLS)
    if name in END:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        # `moves` is one end-to-end metric that each of its cells reports
        assert m["moves"] in END
        assert set(_cells_of(m)) <= set(_cells_of(END[m["moves"]]))


@pytest.mark.parametrize("name", sorted(LAYER))
def test_layer_metric_file_resolves_and_agrees(name):
    spec = _load(BENCH, "layer_metrics", name + ".json")
    entry = LAYER[name]
    for key in ("layer", "unit", "moves", "source"):
        assert spec[key] == entry[key], key
    assert spec["workloads"] == _cells_of(entry)
    reader = importlib.import_module("readers." + spec["reader"]["kind"])
    assert callable(reader.read)
    if spec["reader"].get("roofline"):
        roof = importlib.import_module(
            "roofline." + spec["reader"]["roofline"])
        assert callable(roof.work_per_event)
    if entry["unit"] == "%" and name.endswith("_roofline"):
        assert entry["source"] == "device_trace"


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_resolves(name):
    c = CONFIGS[name]
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(name) and len(c["reduced"]) <= 16
    assert c["file"] == f"perfbench/configs/{name}.json"
    spec = _load(ROOT, c["file"])
    assert spec["source"] == c["source"] and 1 <= len(c["source"]) <= 200
    assert set(c["reduced"]) == set(spec["reduced"])
    for key in ("guarantees", "flush_policy", "assumed", "daemons", "env",
                "expect", "chips"):
        assert key in spec, key
    assert any(w["config"] == name for w in MANIFEST["workloads"])
    files = [x["file"] for x in MANIFEST["configs"]]
    assert files.count(c["file"]) == 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_resolves(name):
    w = CELLS[name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["chips"] == _load(ROOT, CONFIGS[w["config"]]["file"])["chips"]
    traffic = _load(BENCH, "traffic", w["traffic"] + ".json")
    driver = importlib.import_module("drivers." + traffic["driver"])
    for fn in ("prepare", "window", "verify"):
        assert callable(getattr(driver, fn))
    # the cell reports setup_s, another end-to-end metric and a layer metric
    ends = {m for m in END if name in _cells_of(END[m])}
    assert "setup_s" in ends and len(ends) >= 2
    assert set(traffic["reports"]) == ends - {"setup_s"}
    assert any(name in _cells_of(m) for m in LAYER.values())


def test_at_most_half_the_cells_ask_for_four_chips():
    four = sum(1 for w in CELLS.values() if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_paths_hold_only_allowed_file_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert ok.match(rel), rel
