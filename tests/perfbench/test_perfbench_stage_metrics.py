"""The per-layer metrics that read the program's stage spans (PR 26's
eleven, PR 28's two): their files against the manifest, the
`prometheus_value` reader, and REHEARSALS of all three traffic files on
the CPU backend, traced and not, in which every such metric of the cell is
either reported or logged as "nothing to read".  A rehearsal proves the
flow, never the chip.  Nothing here counts the manifest's entries: the
accepted head of `per_layer` is guarded in `bench_checks.py`, and what a
later PR appends needs no edit here."""

import functools
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import check_result_line, run_cell  # noqa: E402

TREE = checks.Tree(checks.ROOT)
LAYER = TREE.layer

NEW = {
    "encode_dat_read_s_per_gib": "stage_stats.read_dat",
    "encode_data_write_s_per_gib": "stage_stats.read_data_write",
    "encode_write_s_per_gib": "stage_stats.write",
    "encode_d2h_wait_s_per_gib": "stage_stats.d2h_wait",
    "d2h_bytes_per_user_byte": "SeaweedFS_volumeServer_ec_device_d2h_bytes_total",
    "recover_decode_queue_ms": "decode_queue_seconds",
    "recover_decode_h2d_ms": "decode_h2d_seconds",
    "recover_decode_apply_ms": "decode_apply_seconds",
    "recover_serve_ms": "serve_seconds",
    "recover_stack_blocks": "decode_blocks",
    "device_init_s": "SeaweedFS_volumeServer_startup_seconds",
    # PR 28: the read stage's two counters of PR 27
    "encode_read_slot_wait_s_per_gib": "stage_stats.read_slot_wait",
    "encode_read_overlap": "stage_stats.read_worker_busy",
}
# the cells this file rehearses; a cell a later PR adds brings its own
REHEARSED = ("seal", "degraded-get", "put-get-open")


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def test_stage_metrics_are_accepted_entries_and_the_list_may_grow():
    """Every metric this file holds is in the manifest and among the
    accepted names, whose place at the head of `per_layer` is the prefix
    guard's to keep (`bench_checks.check_accepted_prefix`, run in
    `test_perfbench_manifest.py`); how many entries follow is free."""
    assert set(NEW) <= set(LAYER)
    assert set(NEW) <= set(checks.ACCEPTED_PER_LAYER)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_lists_its_cells_and_reads_the_named_source(name):
    entry, spec = LAYER[name], _spec(name)
    # an entry without `workloads` would be read in every cell
    assert entry["workloads"] and spec["workloads"] == entry["workloads"]
    reader = spec["reader"]
    assert NEW[name] in (reader.get("key"), reader.get("family"))
    # data files only, but for the one reader this PR brings
    assert reader["kind"] in ("harness_record", "admin_json", "prometheus",
                              "prometheus_value")


def test_prometheus_value_reads_the_last_scrape_and_nothing_else():
    from readers import prometheus_value

    fam = "SeaweedFS_volumeServer_startup_seconds"
    spec = {"family": fam, "labels": {"phase": "device_init"}}
    before = [(fam, {"phase": "device_init"}, 1.0)]
    after = [(fam, {"phase": "import"}, 3.0),
             (fam, {"phase": "device_init"}, 6.5)]
    assert prometheus_value.read(spec, {"prom": [before, after]}) == 6.5
    assert prometheus_value.read({**spec, "scale": 1000},
                                 {"prom": [before, after]}) == 6500.0


@pytest.mark.parametrize("ctx", [
    {}, {"prom": None}, {"prom": []},
    {"prom": [[], [("SeaweedFS_other", {}, 1.0)]]},
    {"prom": [[], [("SeaweedFS_volumeServer_startup_seconds",
                    {"phase": "load"}, 1.0)]]}],
    ids=["no-prom", "untraced", "no-scrape", "no-family", "other-phase"])
def test_prometheus_value_reads_nothing_and_never_raises(ctx):
    """The parent has no such gauge: the reader returns None there."""
    from readers import prometheus_value

    spec = {"family": "SeaweedFS_volumeServer_startup_seconds",
            "labels": {"phase": "device_init"}}
    assert prometheus_value.read(spec, ctx) is None


@pytest.mark.parametrize("name", sorted(
    set(NEW) - {"encode_write_s_per_gib", "recover_serve_ms"}))  # old keys
def test_reader_of_a_new_metric_reads_nothing_from_a_parent(name):
    """Laid over the parent's checkout the new files meet a program
    without the span or counter: every reader returns None, none raises."""
    import importlib

    reader = _spec(name)["reader"]
    module = importlib.import_module("readers." + reader["kind"])
    parent_ctx = {
        "records": {"seal": [{"gib": 0.9, "stage_stats": {
            "read": 0.9, "dispatch": 0.04, "encode_crc": 0.3}}]},
        "admin": {"/admin/ec/recover_stats": [
            {"decode_seconds": 1.0, "cache_misses": 5},
            {"decode_seconds": 2.0, "cache_misses": 9}]},
        "prom": [[], [("SeaweedFS_volumeServer_request_seconds_sum",
                       {"type": "read"}, 1.0)]],
        "counts": {"sealed_bytes": 1 << 30},
    }
    assert module.read(reader, parent_ctx) is None


@functools.lru_cache(maxsize=None)
def _rehearsal(cell: str, trace: int):
    proc, result = run_cell(cell, "--trace", str(trace))
    return proc.returncode, proc.stdout, proc.stderr, result


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", REHEARSED)
def test_rehearsal_exits_zero_with_the_new_files(cell, trace):
    code, out, err, result = _rehearsal(cell, trace)
    assert code == 0, err[-2000:]
    check_result_line(result, trace=bool(trace))
    assert result["correct"] is True and result["failed"] == 0
    if not trace:    # an untraced line carries no per-layer metric
        assert not set(result["metrics"]) & set(LAYER)


@pytest.mark.parametrize("cell,name", [
    (cell, name) for cell in REHEARSED for name in NEW
    if cell in LAYER[name]["workloads"]])
def test_traced_rehearsal_reports_the_new_metric_or_says_nothing_to_read(
        cell, name):
    code, out, err, result = _rehearsal(cell, 1)
    assert code == 0, err[-2000:]
    if name in result["metrics"]:
        assert result["metrics"][name]["unit"] == LAYER[name]["unit"]
        assert result["metrics"][name]["value"] >= 0.0
    else:
        assert f"per-layer {name}: nothing to read" in out
    # on the CPU the program has every span: only a queue nobody stood
    # in may read as nothing
    if name != "recover_decode_queue_ms":
        assert name in result["metrics"], out[-1500:]


@pytest.mark.parametrize("cell", REHEARSED)
def test_traced_rehearsal_keeps_every_accepted_metric_it_had(cell):
    """`per_layer` gains keys and loses none."""
    code, out, err, result = _rehearsal(cell, 1)
    # (h2d_bytes_per_user_byte reads nothing on the CPU: the staging
    # slot is the device buffer there, so nothing is uploaded)
    had = {"seal": {"encode_read_s_per_gib", "encode_dispatch_s_per_gib"},
           "degraded-get": {"recover_decode_ms", "recover_fetch_ms",
                            "recover_cache_hit_share", "volume_get_ms",
                            "degraded_get_mean_ms", "compiles_in_window"},
           "put-get-open": {"volume_get_ms", "assign_ms", "put_p50_ms",
                            "get_p50_ms"}}[cell]
    assert had <= set(result["metrics"]), sorted(result["metrics"])
