"""A later PR adds a per-layer metric, or a configuration, a traffic mix, a
cell, a kind of reader and a per-layer metric, as NEW files plus manifest
entries.  The harness picks them up without an edit to any file that was
there, and the grown tree passes every structural check that the repo's
own tree passes (`bench_checks.CHECKS`): that is what later PRs depend on."""

import hashlib
import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402


# names no later PR would pick, so that its tree still grows without a clash
NEW_METRIC = "extend_test.encode_batches_per_gib"
NEW_CONFIG = "extend-test.small-lru"
NEW_CELL = "extend-test.degraded-get-2"
NEW_CELL_METRIC = "extend_test.get_rate"
REPO = checks.Tree(ROOT)


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


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f)


def _add_metric(root, manifest, name, cells, unit, better, source, reader,
                layer="Volume server (Python)", moves="op_p50_ms"):
    """One data file and one entry appended after whatever is there."""
    _dump({"name": name, "layer": layer, "unit": unit, "moves": moves,
           "workloads": cells, "source": source, "reader": reader},
          root, "perfbench", "layer_metrics", name + ".json")
    manifest["per_layer"].append({
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves, "workloads": cells})


def _grow_by_a_metric(root, manifest):
    """A metric over a cell that is there: the seals of a window, counted
    from the replies the driver already keeps."""
    _add_metric(root, manifest, NEW_METRIC, ["seal"],
                "1/GiB", "lower", "program_counter",
                {"kind": "harness_record", "record": "seal",
                 "key": "stage_stats.batches", "per": "gib"},
                layer="Encode pipeline", moves="bulk_rate")
    return {"files_added": 1, "cell": "seal", "metric": NEW_METRIC,
            "unit": "1/GiB"}


def _grow_by_a_cell(root, manifest):
    bench = os.path.join(root, "perfbench")
    # a configuration: the one-chip deployment with a smaller LRU
    config = _load(bench, "configs", "warm-ec-rs10.4-1chip.json")
    config["name"] = NEW_CONFIG
    config["env"]["WEED_EC_RECOVER_CACHE_MB"] = "8"
    _dump(config, bench, "configs", NEW_CONFIG + ".json")
    # a traffic mix: the degraded read with other parameters, data only
    traffic = _load(bench, "traffic", "degraded-get.json")
    traffic["rehearse"]["clients"] = 2
    _dump(traffic, bench, "traffic", NEW_CELL + ".json")
    # a new kind of reader and a per-layer metric that uses it
    with open(os.path.join(bench, "readers", "spans_per_s.py"), "w") as f:
        f.write("def read(spec, ctx):\n"
                "    spans = ctx['spans'].get(spec['span'])\n"
                "    return len(spans) / spec['seconds'] if spans else None\n")
    _add_metric(root, manifest, NEW_CELL_METRIC, [NEW_CELL], "ops/s",
                "higher", "host_clock",
                {"kind": "spans_per_s", "span": "get_sealed", "seconds": 2})
    # and one manifest entry each
    manifest["configs"].append({
        "name": NEW_CONFIG, "source": config["source"],
        "file": f"perfbench/configs/{NEW_CONFIG}.json",
        "reduced": ["volumes"], "why": "an 8 MiB recovered-block LRU"})
    manifest["workloads"].append({
        "name": NEW_CELL, "config": NEW_CONFIG,
        "traffic": NEW_CELL, "chips": 1, "why": "two callers"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("op_p50_ms", "op_p95_ms"):
            m["workloads"].append(NEW_CELL)
    return {"files_added": 4, "cell": NEW_CELL, "metric": NEW_CELL_METRIC,
            "unit": "ops/s", "says": "2 closed-loop clients"}


GROWN_BY = {"a-metric": _grow_by_a_metric, "a-cell": _grow_by_a_cell}


@pytest.fixture(scope="module", params=sorted(GROWN_BY))
def grown(request, tmp_path_factory):
    """A copy of the repo's benchmark, grown the way a later PR may grow
    it; the program is linked in beside it."""
    root = str(tmp_path_factory.mktemp(request.param))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for path in REPO.manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("weed.py", "seaweedfs_tpu", "native"):   # the program
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    before = _tree_digest(root)
    manifest = _load(root, "BENCHMARK.json")
    added = GROWN_BY[request.param](root, manifest)
    _dump(manifest, root, "BENCHMARK.json")
    return {"root": root, "before": before, **added}


@pytest.mark.parametrize("check", sorted(checks.CHECKS))
def test_grown_tree_passes_every_structural_check(grown, check):
    checks.CHECKS[check](checks.Tree(grown["root"]))


def test_grown_tree_was_grown_by_new_files_alone(grown):
    after = _tree_digest(grown["root"])
    assert {k: after[k] for k in grown["before"]} == grown["before"]
    assert len(after) == len(grown["before"]) + grown["files_added"]
    names = list(checks.Tree(grown["root"]).layer)
    assert names[:-1] == list(REPO.layer)    # appended, nothing moved


@pytest.mark.parametrize("grown", ["a-cell"], indirect=True)
def test_harness_runs_what_was_added(grown):
    """A traced rehearsal of the new cell reports the new metric through
    the new reader, with no edit to the harness.  (One rehearsal, as before
    PR 28: the tree grown by a metric alone differs from it by a data file
    of a kind that `test_perfbench_stage_metrics.py` rehearses.)"""
    cell, metric = grown["cell"], grown["metric"]
    proc, result = run_cell(cell, "--trace", "1", root=grown["root"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=True)
    assert result["correct"] is True
    assert result["metrics"][metric]["unit"] == grown["unit"]
    assert result["metrics"][metric]["value"] > 0
    assert grown.get("says", "") in proc.stdout
    # a cell that was there reports what it reported and not the new cell's
    assert set(result["metrics"]) <= checks.Tree(grown["root"]).layers_of(cell)
