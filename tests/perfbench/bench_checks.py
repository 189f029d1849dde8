"""The structural checks of a benchmark tree, callable on any root: the
repo's own (`test_perfbench_manifest.py`, `test_perfbench_stage_metrics.py`)
and a copy that a test has extended by files and manifest entries
(`test_perfbench_extend.py`).  What holds for the accepted tree has to hold
for a tree that a later PR grew by data alone, and nothing here names a
count of entries: the per-layer list may grow, its accepted head may not
change."""

import importlib.util
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_rehearsal import ROOT  # noqa: E402

BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:    # the harness's own modules (cluster, reference)
    sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE_NAME = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# The per-layer names the driver has accepted, in the order of
# BENCHMARK.json: PR 24's fourteen, PR 26's eleven, PR 28's two.  They stay
# the head of `per_layer`; what a later PR appends after them is free.  Only
# a `benchmark` PR lengthens this list.
ACCEPTED_PER_LAYER = (
    "volume_get_ms", "assign_ms", "put_p50_ms", "get_p50_ms",
    "encode_read_s_per_gib", "encode_dispatch_s_per_gib",
    "recover_decode_ms", "recover_fetch_ms", "recover_cache_hit_share",
    "degraded_get_mean_ms", "h2d_bytes_per_user_byte",
    "encode_kernel_roofline", "recover_kernel_us", "compiles_in_window",
    "encode_dat_read_s_per_gib", "encode_data_write_s_per_gib",
    "encode_write_s_per_gib", "encode_d2h_wait_s_per_gib",
    "d2h_bytes_per_user_byte", "recover_decode_queue_ms",
    "recover_decode_h2d_ms", "recover_decode_apply_ms", "recover_serve_ms",
    "recover_stack_blocks", "device_init_s",
    "encode_read_slot_wait_s_per_gib", "encode_read_overlap",
)


class Tree:
    """BENCHMARK.json of one root and the files its names resolve to."""

    def __init__(self, root: str):
        self.root = root
        self.bench = os.path.join(root, "perfbench")
        self.manifest = self.load("BENCHMARK.json")
        self.cells = {w["name"]: w for w in self.manifest["workloads"]}
        self.end = {m["name"]: m for m in self.manifest["end_to_end"]}
        self.layer = {m["name"]: m for m in self.manifest["per_layer"]}
        self.configs = {c["name"]: c for c in self.manifest["configs"]}

    def load(self, *parts):
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def cells_of(self, metric: dict) -> list:
        return metric.get("workloads", list(self.cells))

    def ends_of(self, cell: str) -> set:
        return {n for n, m in self.end.items() if cell in self.cells_of(m)}

    def layers_of(self, cell: str) -> set:
        return {n for n, m in self.layer.items() if cell in self.cells_of(m)}

    def module(self, package: str, name: str):
        """`perfbench/<package>/<name>.py` of THIS tree, by its path: a
        copy's new reader is not on the repo's import path."""
        path = os.path.join(self.bench, package, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"_checked_{package}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module


def check_top_level(tree: Tree):
    m = tree.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["perfbench", "tests/perfbench"]
    assert m["command"] == ["python3", "perfbench/run.py"]
    assert os.path.getsize(
        os.path.join(tree.root, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    rs = m["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def check_no_duplicate_names(tree: Tree):
    m = tree.manifest
    for group in ("configs", "workloads"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names)), group
    metrics = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def check_metric_entry(tree: Tree, name: str):
    m = tree.end.get(name) or tree.layer[name]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    assert set(tree.cells_of(m)) <= set(tree.cells)
    if name in tree.end:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        # `moves` is one end-to-end metric that each of its cells reports
        assert m["moves"] in tree.end
        assert set(tree.cells_of(m)) <= set(
            tree.cells_of(tree.end[m["moves"]]))


def check_layer_metric_file(tree: Tree, name: str):
    spec = tree.load("perfbench", "layer_metrics", name + ".json")
    entry = tree.layer[name]
    for key in ("layer", "unit", "moves", "source"):
        assert spec[key] == entry[key], key
    assert spec["workloads"] == tree.cells_of(entry)
    assert callable(tree.module("readers", spec["reader"]["kind"]).read)
    if spec["reader"].get("roofline"):
        roof = tree.module("roofline", spec["reader"]["roofline"])
        assert callable(roof.work_per_event)
    if entry["unit"] == "%" and name.endswith("_roofline"):
        assert entry["source"] == "device_trace"


def check_config(tree: Tree, name: str):
    c = tree.configs[name]
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(name) and len(c["reduced"]) <= 16
    assert c["file"] == f"perfbench/configs/{name}.json"
    spec = tree.load(c["file"])
    assert spec["source"] == c["source"] and 1 <= len(c["source"]) <= 200
    assert set(c["reduced"]) == set(spec["reduced"])
    for key in ("guarantees", "flush_policy", "assumed", "daemons", "env",
                "expect", "chips"):
        assert key in spec, key
    assert any(w["config"] == name for w in tree.cells.values())
    files = [x["file"] for x in tree.configs.values()]
    assert files.count(c["file"]) == 1


def check_cell(tree: Tree, name: str):
    w = tree.cells[name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["chips"] == tree.load(tree.configs[w["config"]]["file"])["chips"]
    traffic = tree.load("perfbench", "traffic", w["traffic"] + ".json")
    driver = tree.module("drivers", traffic["driver"])
    for fn in ("prepare", "window", "verify"):
        assert callable(getattr(driver, fn))
    # the cell reports setup_s, another end-to-end metric and a layer metric
    ends = tree.ends_of(name)
    assert "setup_s" in ends and len(ends) >= 2
    assert set(traffic["reports"]) == ends - {"setup_s"}
    assert tree.layers_of(name), "no per-layer metric lists this cell"


def check_four_chip_share(tree: Tree):
    four = sum(1 for w in tree.cells.values() if w["chips"] == 4)
    assert four <= max(1, len(tree.cells) // 2)


def check_file_names(tree: Tree):
    for path in tree.manifest["paths"]:
        top = os.path.join(tree.root, path)
        for dirpath, dirnames, files in os.walk(top):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, f), tree.root)
                assert FILE_NAME.match(rel), rel


def check_accepted_prefix(tree: Tree):
    """The accepted per-layer entries are the head of the list, in their
    order, none dropped or renamed; the tail belongs to later PRs."""
    names = [m["name"] for m in tree.manifest["per_layer"]]
    head = names[:len(ACCEPTED_PER_LAYER)]
    assert head == list(ACCEPTED_PER_LAYER)
    assert not set(names[len(ACCEPTED_PER_LAYER):]) & set(head)


def _each(check, names):
    def over_all(tree: Tree):
        for name in sorted(getattr(tree, names)):
            check(tree, name)
    return over_all


# every structural check, by the name the extended-tree test reports it
# under; those that take a name run over every name the tree has
CHECKS = {
    "top_level": check_top_level,
    "no_duplicate_names": check_no_duplicate_names,
    "end_to_end_entries": _each(check_metric_entry, "end"),
    "per_layer_entries": _each(check_metric_entry, "layer"),
    "layer_metric_files": _each(check_layer_metric_file, "layer"),
    "configs": _each(check_config, "configs"),
    "cells": _each(check_cell, "cells"),
    "four_chip_share": check_four_chip_share,
    "file_names": check_file_names,
    "accepted_prefix": check_accepted_prefix,
}
