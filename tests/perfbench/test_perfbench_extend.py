"""A later PR adds a configuration, a traffic mix, a cell, a kind of
reader and a per-layer metric as NEW files plus manifest entries, and the
harness picks them up without an edit to any file that was there."""

import hashlib
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402


def _tree_digest(root):
    out = {}
    for dirpath, dirnames, files in os.walk(os.path.join(root, "perfbench")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_data_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("weed.py", "seaweedfs_tpu", "native"):   # the program
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    before = _tree_digest(root)
    bench = os.path.join(root, "perfbench")

    # a configuration: the one-chip deployment with a smaller LRU
    config = json.load(open(os.path.join(
        bench, "configs", "warm-ec-rs10.4-1chip.json")))
    config["name"] = "warm-ec-small-lru"
    config["env"]["WEED_EC_RECOVER_CACHE_MB"] = "8"
    json.dump(config, open(os.path.join(
        bench, "configs", "warm-ec-small-lru.json"), "w"))
    # a traffic mix: the degraded read with other parameters, data only
    traffic = json.load(open(os.path.join(
        bench, "traffic", "degraded-get.json")))
    traffic["rehearse"]["clients"] = 2
    json.dump(traffic, open(os.path.join(
        bench, "traffic", "degraded-get-2.json"), "w"))
    # a new kind of reader and a per-layer metric that uses it
    with open(os.path.join(bench, "readers", "spans_per_s.py"), "w") as f:
        f.write("def read(spec, ctx):\n"
                "    spans = ctx['spans'].get(spec['span'])\n"
                "    return len(spans) / spec['seconds'] if spans else None\n")
    json.dump({"name": "get_rate", "layer": "Volume server (Python)",
               "unit": "ops/s", "moves": "op_p50_ms",
               "workloads": ["degraded-get-2"], "source": "host_clock",
               "reader": {"kind": "spans_per_s", "span": "get_sealed",
                          "seconds": 2}},
              open(os.path.join(bench, "layer_metrics", "get_rate.json"),
                   "w"))
    # and one manifest entry each
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    manifest["configs"].append({
        "name": "warm-ec-small-lru", "source": config["source"],
        "file": "perfbench/configs/warm-ec-small-lru.json",
        "reduced": ["volumes"], "why": "an 8 MiB recovered-block LRU"})
    manifest["workloads"].append({
        "name": "degraded-get-2", "config": "warm-ec-small-lru",
        "traffic": "degraded-get-2", "chips": 1, "why": "two callers"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("op_p50_ms", "op_p95_ms"):
            m["workloads"].append("degraded-get-2")
    manifest["per_layer"].append({
        "name": "get_rate", "unit": "ops/s", "better": "higher",
        "source": "host_clock", "layer": "Volume server (Python)",
        "moves": "op_p50_ms", "workloads": ["degraded-get-2"]})
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))

    proc, result = run_cell("degraded-get-2", "--trace", "1", root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=True)
    assert result["correct"] is True
    assert result["metrics"]["get_rate"]["unit"] == "ops/s"
    assert "2 closed-loop clients" in proc.stdout
    # nothing that was there was edited
    after = _tree_digest(root)
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 4
