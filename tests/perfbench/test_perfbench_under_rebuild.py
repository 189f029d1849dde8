"""What PR 38 added to the benchmark as new files: the configuration
`warm-ec-rs10.4-1chip-1lost-repairing`, the cell
`degraded-get-under-rebuild` with its driver `reads_under_rebuild` (made of
`zipf_sealed_reads`' and `rebuild_restore`'s steps, neither edited) and its
per-layer metrics.  The manifest's additions against every structural
check; the driver's own arithmetic (which GETs overlap a rebuild, the
widened draw, volume 1's lookups alone); the readers over a parent's
replies; and REHEARSALS on the CPU backend (tiny volumes, no chip, no
timing assertion) with both controls."""

import functools
import importlib
import os
import random
import re
import sys
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402

from drivers import reads_under_rebuild as driver  # noqa: E402
from drivers import rebuild_restore, zipf_sealed_reads  # noqa: E402

TREE = checks.Tree(ROOT)
LAYER = TREE.layer
CELL = "degraded-get-under-rebuild"
CONFIG = "warm-ec-rs10.4-1chip-1lost-repairing"
READS_OF, REPAIR_OF = "degraded-get-cached", "rebuild-4lost"

# from the driver's own records
DRIVER_METRICS = ("get_beside_rebuild_p50_ms", "get_between_rebuilds_p50_ms",
                  "get_beside_rebuild_p95_ms", "repair_duty_share")
# from the program's new counters: absent on a parent
COUNTER_METRICS = {
    "sealed_get_beside_job_share": "read_stats.needles_beside_job",
    "sealed_get_local_fallbacks": "read_stats.local_fallbacks",
    "recover_decode_apply_beside_job_ms": "decode_apply_seconds_beside_job",
    "rebuild_foreground_reads": "stage_stats.foreground_reads",
}
# accepted readers under this cell's names (an accepted entry's
# `workloads` is closed): the name they are accepted under
RENAMED = {
    "volume_get_ms": f"volume_get_ms.{READS_OF}",
    "sealed_get_locate_ms": "sealed_get_locate_ms",
    "sealed_get_shard_read_ms": "sealed_get_shard_read_ms",
    "sealed_get_assemble_ms": "sealed_get_assemble_ms",
    "recover_cache_hit_share": f"recover_cache_hit_share.{READS_OF}",
    "recover_decode_ms": f"recover_decode_ms.{READS_OF}",
    "recover_decode_queue_ms": f"recover_decode_queue_ms.{READS_OF}",
    "recover_kernel_us": f"recover_kernel_us.{READS_OF}",
    "rebuild_read_s_per_gib": "rebuild_read_s_per_gib",
    "rebuild_dispatch_s_per_gib": "rebuild_dispatch_s_per_gib",
    "rebuild_d2h_wait_s_per_gib": "rebuild_d2h_wait_s_per_gib",
    "rebuild_crc_s_per_gib": "rebuild_crc_s_per_gib",
    "rebuild_write_s_per_gib": "rebuild_write_s_per_gib",
    "rebuild_kernel_us": "rebuild_kernel_us",
    "rebuild_kernel_roofline": "rebuild_kernel_roofline",
    "compiles_in_window": f"compiles_in_window.{READS_OF}",
    "device_init_s": f"device_init_s.{READS_OF}",
    # the rest of a recovery's stages, so that the decodes beside a job
    # have the window's own to be read against
    "recover_decode_apply_ms": "recover_decode_apply_ms",
    "recover_decode_h2d_ms": "recover_decode_h2d_ms",
    "recover_fetch_ms": "recover_fetch_ms",
    "recover_serve_ms": "recover_serve_ms",
    "recover_stack_blocks": f"recover_stack_blocks.{READS_OF}",
    "sealed_get_recovered_share": "sealed_get_recovered_share",
}
ALL_METRICS = DRIVER_METRICS + tuple(COUNTER_METRICS) + tuple(
    f"{stem}.{CELL}" for stem in RENAMED)

COMPARED = (
    "reads_not_equal_to_their_put", "operations_failed", "device_fallbacks",
    "windows_without_device_decodes", "windows_without_lru_hits",
    "block_lookups_of_volume_1_off_the_reference",
    "windows_without_a_completed_rebuild",
    "windows_without_a_read_begun_inside_a_rebuild",
    "rebuilt_shards_of_the_window_differ_from_the_sealed",
    "setup_seal_of_volume_1_not_on_device-pooled-swar_x1",
    # the repair's, as rebuild-4lost compares them
    "rebuilds_not_on_device-apply-xla_x1",
    "setup_seal_of_volume_2_not_on_device-pooled-swar_x1",
    "rebuilds_refused", "h2d_bytes_short_of_survivor_bytes",
    "rebuilt_files_differ_from_the_sealed", "survivor_files_changed",
    "shard_crc32c_differ_from_vif", "parity_bytes_differ_from_reference",
    "data_shard_bytes_differ_from_dat", "stripe_sample_bytes_short",
    "rebuilt_bytes_differ_from_reconstruction",
    "reconstruction_sample_bytes_short", "objects_not_read_back",
    "objects_read_back_through_a_recover", "pristine_dat_changed",
)


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def _traffic(name=CELL):
    return TREE.load("perfbench", "traffic", name + ".json")


# -- the manifest's new entries -----------------------------------------------

def test_the_cell_the_configuration_and_the_metrics_are_in_the_manifest():
    cell = TREE.cells[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": CELL, "chips": 1}
    assert list(TREE.cells)[-1] == CELL and list(TREE.configs)[-1] == CONFIG
    # both halves of the cell end to end: what the repair costs the
    # clients, and what the clients cost the repair
    assert TREE.ends_of(CELL) == {"op_p50_ms", "op_p95_ms", "bulk_rate",
                                  "setup_s"}
    assert "repair_rate_mib_s" not in LAYER
    assert set(_traffic()["reports"]) == TREE.ends_of(CELL) - {"setup_s"}
    for end in TREE.ends_of(CELL) - {"setup_s"}:
        assert TREE.end[end]["workloads"][-1] == CELL    # appended
    # the cell's metrics are the tail of the list, behind the accepted 51
    names = list(LAYER)
    assert TREE.layers_of(CELL) >= set(ALL_METRICS)
    assert tuple(names[51:51 + len(ALL_METRICS)]) == ALL_METRICS
    assert names[50] == "sealed_get_index_preads"
    assert sum(1 for w in TREE.cells.values() if w["chips"] == 4) == 1
    for word in ("8 closed-loop", "zipf 0.99", "1 in 50", "ec.rebuild",
                 "one GIL", "one chip"):
        assert word in cell["why"], word


@pytest.mark.parametrize("check,name", [
    ("cells", CELL), ("configs", CONFIG),
    *[("per_layer_entries", m) for m in ALL_METRICS],
    *[("layer_metric_files", m) for m in ALL_METRICS]],
    ids=lambda v: v)
def test_structural_check_on_each_new_name(check, name):
    one = {"cells": checks.check_cell, "configs": checks.check_config,
           "per_layer_entries": checks.check_metric_entry,
           "layer_metric_files": checks.check_layer_metric_file}[check]
    one(TREE, name)


@pytest.mark.parametrize("check", sorted(checks.CHECKS))
def test_whole_tree_passes_with_the_cell_in_it(check):
    checks.CHECKS[check](TREE)


def test_configuration_keeps_the_waiting_deployment_and_states_what_it_adds():
    new = TREE.load("perfbench", "configs", CONFIG + ".json")
    old = TREE.load("perfbench", "configs",
                    "warm-ec-rs10.4-1chip-1lost.json")
    four = TREE.load("perfbench", "configs",
                     "warm-ec-rs10.4-1chip-4lost.json")
    for key in ("code", "volume_size_limit_mb", "volume_shape",
                "flush_policy", "daemons", "env", "rehearse_env", "chips",
                "lost_shards"):
        assert new[key] == old[key], key
    assert new["lost_shards"] == [0] and new["volumes"] == 2
    assert old["expect"].items() <= new["expect"].items()
    assert old["rehearse_expect"].items() <= new["rehearse_expect"].items()
    for side in ("expect", "rehearse_expect"):
        for key in ("rebuild_backend", "rebuild_devices"):
            assert new[side][key] == four[side][key], (side, key)
    assert new["expect"]["rebuild_backend"] == "device-apply-xla"
    # every guarantee of the waiting deployment, availability widened to
    # the transitions, and the repair's own
    assert set(old["guarantees"]) | {"rebuild"} == set(new["guarantees"])
    for key in set(old["guarantees"]) - {"availability"}:
        assert new["guarantees"][key] == old["guarantees"][key], key
    for words in ("either volume", "while it is rebuilt", "across the mount",
                  "no read is refused"):
        assert words in new["guarantees"]["availability"], words
    assert "byte-identical" in new["guarantees"]["rebuild"]
    assert ".vif" in new["guarantees"]["rebuild"]
    assert set(old["assumed"]) < set(new["assumed"])
    assert new["assumed"]["WEED_MAINT"].startswith("0:")
    assert "survivor_reads" in new["assumed"]
    assert new["env"] == {"WEED_EC_DEVICE_SHARD": "1", "WEED_MAINT": "0"}
    assert set(new["reduced"]) == {"volumes"}
    assert len(new["source"]) <= 200 and new["source"] != old["source"]
    for words in ("master.maintenance", "ec.rebuild", "configs 2 and 3"):
        assert words in new["source"], words


def test_traffic_is_the_two_accepted_mixes_side_by_side():
    t, reads, repair = _traffic(), _traffic(READS_OF), _traffic(REPAIR_OF)
    assert t["driver"] == "reads_under_rebuild"
    for key in ("collection", "clients", "volume", "large_from_bytes",
                "zipf", "recover_block_bytes", "warm_reads", "warm_stacks"):
        assert t[key] == reads[key], key
    for key in ("collection", "volume", "parity_sample_bytes",
                "reconstruction_sample_bytes", "readback_objects",
                "master_wait_s"):
        assert t[key] == repair[key], key
    assert t["clients"] == 8 and t["zipf"]["rank_seed_offset"] == 4
    assert t["repairing_volume"]["read_share"] == 1 / 50
    assert t["repairing_volume"]["draw"] == "uniform"
    assert t["rehearse"]["volume"] == reads["rehearse"]["volume"] \
        == repair["rehearse"]["volume"]
    assert "lost_shards" not in t            # the configuration's
    assert t["admin_snapshots"] == ["/admin/ec/recover_stats"]


@pytest.mark.parametrize("name", ALL_METRICS)
def test_new_metric_lists_the_cell_alone_and_reads_the_named_source(name):
    entry, spec = LAYER[name], _spec(name)
    assert entry["workloads"] == spec["workloads"] == [CELL]
    reader = spec["reader"]
    # data only: every kind of reader was there
    assert reader["kind"] in ("harness_record", "admin_json", "prometheus",
                              "prometheus_value", "trace", "count")
    if name in DRIVER_METRICS:
        assert reader == {"kind": "count", "count": name}
        assert entry["source"] == "host_clock"
    elif name in COUNTER_METRICS:
        assert reader["key"] == COUNTER_METRICS[name]
        assert entry["source"] in ("program_counter", "program_span")
    else:
        accepted = RENAMED[name[:-len(CELL) - 1]]
        assert reader == _spec(accepted)["reader"]
        for key in ("unit", "better", "source", "layer"):
            assert entry[key] == LAYER[accepted][key], key
        assert entry["moves"] == LAYER[accepted]["moves"]
    # the repair's side moves the repair's rate
    if name.startswith(("rebuild_", "repair_")):
        assert entry["moves"] == spec["moves"] == "bulk_rate"
    if name.startswith("rebuild_kernel_roofline"):
        assert reader["roofline"] == "rs_rebuild"      # no new kernel


# -- the driver is the two accepted drivers' steps -------------------------------

def test_repair_side_is_a_private_copy_of_rebuild_restore_over_volume_2():
    assert driver.rr is not rebuild_restore
    assert driver.rr.VID == driver.REPAIRING_VID == 2
    assert rebuild_restore.VID == 1             # the accepted module's own
    assert driver.WAITING_VID == zipf_sealed_reads.VID == 1
    assert issubclass(driver.Repairer, driver.rr.Rebuilder)
    assert not issubclass(driver.Repairer, rebuild_restore.Rebuilder)
    with open(driver.rr.__file__) as a, open(rebuild_restore.__file__) as b:
        assert a.read() == b.read()
    with open(driver.__file__) as f:
        src = f.read()
    assert "import seaweedfs_tpu" not in src and "from seaweedfs" not in src


def test_overlaps_against_a_walk_over_every_pair():
    rng = random.Random(38)
    walls, t = [], 0.0
    for _ in range(12):                 # one after another, gaps between
        t += rng.uniform(0.05, 0.3)
        end = t + rng.uniform(0.2, 0.5)
        walls.append((t, end))
        t = end
    gets = []
    for _ in range(3000):
        s = rng.uniform(-0.5, t + 0.5)
        gets.append((s, s + rng.choice([0.002, 0.004, 0.02, 0.7])))
    # a GET that ends where a rebuild begins, or begins where one ends,
    # does not overlap it; one that begins with it began inside it
    a, b = walls[3]
    gets += [(a - 0.01, a), (b, b + 0.01), (a, a + 0.001)]
    beside, began = driver.overlaps(np.array(gets), np.array(walls))
    want_beside = [any(s < wb and e > wa for wa, wb in walls)
                   for s, e in gets]
    want_began = [any(wa <= s < wb for wa, wb in walls) for s, e in gets]
    assert beside.tolist() == want_beside
    assert began.tolist() == want_began
    assert beside.tolist()[-3:] == [False, False, True]
    assert 0 < sum(want_began) < sum(want_beside) < len(gets)
    for walls_ in (np.zeros((0, 2)), np.array([])):
        none, none_began = driver.overlaps(np.array(gets), walls_)
        assert not none.any() and not none_began.any()
    empty, _ = driver.overlaps(np.zeros((0, 2)), np.array(walls))
    assert empty.shape == (0,)


def _fake_run(admin):
    return SimpleNamespace(admin={zipf_sealed_reads.RECOVER_STATS: admin},
                           log=lambda msg: None)


def _fake_state():
    reads = SimpleNamespace(
        reads=Counter({"1,a": 10, "1,b": 3, "2,x": 4, "2,y": 1}),
        lookups={"1,a": 2, "1,b": 0, "2,x": 3, "2,y": 0})
    return SimpleNamespace(reads=reads, of_volume_2={"2,x", "2,y"})


@pytest.mark.parametrize("made_1,off", [(20, 0), (21, 1), (18, 2)])
def test_volume_1_s_lookups_are_compared_alone(made_1, off):
    admin = [{"volumes": {"1": {"lookups": 100}, "2": {"lookups": 7}}},
             {"volumes": {"1": {"lookups": 100 + made_1},
                          "2": {"lookups": 19}}}]
    assert driver._lookups_off(_fake_run(admin), _fake_state()) == off


def test_a_parent_keeps_no_count_a_volume_and_there_is_nothing_to_compare():
    said = []
    run = SimpleNamespace(log=said.append, admin={
        zipf_sealed_reads.RECOVER_STATS: [
            {"volumes": {"1": {"cache_blocks": 3}}},
            {"volumes": {"1": {"cache_blocks": 5}}}]})
    assert driver._lookups_off(run, _fake_state()) == 0
    assert "nothing to compare with the 20 the reference needs" in said[0]


# -- laid over the parent's checkout ------------------------------------------

PARENT_CTX = {
    # a parent's window: its replies lack the new keys, everything else
    # is there
    "records": {
        "sealed_read": [{"windows": 1, "read_stats": {
            "locate_seconds": 0.02, "shard_seconds": 0.7,
            "assemble_seconds": 0.5, "needles": 40000, "timed_needles": 1000,
            "intervals": 55000, "intervals_recovered": 4600,
            "index_preads": 0}}],
        "rebuild": [{"rebuilds": 1, "gib": 0.9376, "stage_stats": {
            "read": 0.2, "dispatch": 0.05, "d2h_wait": 0.1, "crc": 0.01,
            "write": 0.1, "batch_units": 6, "devices": 1, "batches": 17,
            "h2d_bytes": 17 * 6 * 10 * (1 << 20), "missing": [0]}}] * 3},
    "admin": {"/admin/ec/recover_stats": [
        {"decode_seconds": 1.0, "decode_queue_seconds": 0.1,
         "decode_apply_seconds": 0.4, "decode_h2d_seconds": 0.1,
         "fetch_seconds": 0.2, "serve_seconds": 0.05, "cache_misses": 5,
         "decode_blocks": 5, "decode_batches": 4},
        {"decode_seconds": 2.0, "decode_queue_seconds": 0.3,
         "decode_apply_seconds": 0.9, "decode_h2d_seconds": 0.3,
         "fetch_seconds": 0.6, "serve_seconds": 0.15, "cache_misses": 9,
         "decode_blocks": 9, "decode_batches": 7}]},
    "prom": [
        [("SeaweedFS_volumeServer_ec_recover_cache_total",
          {"result": "hit"}, 10.0)],
        [("SeaweedFS_volumeServer_ec_recover_cache_total",
          {"result": "hit"}, 110.0),
         ("SeaweedFS_volumeServer_ec_recover_cache_total",
          {"result": "miss"}, 4.0),
         ("SeaweedFS_volumeServer_request_seconds_sum", {"type": "read"},
          2.0),
         ("SeaweedFS_volumeServer_request_seconds_count", {"type": "read"},
          1000.0),
         ("SeaweedFS_volumeServer_startup_seconds",
          {"phase": "device_init"}, 6.5)]],
    "counts": {"programs_built_in_window": 0,
               **dict.fromkeys(DRIVER_METRICS, 5.0)},
    "trace": {"modules": [(0, "jit__apply_pallas(5)", 0.1, 0.00004),
                          (0, "jit_rebuild_apply(7)", 0.2, 0.001)]},
    "peaks": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12},
    "log": print,
}


@pytest.mark.parametrize("name", ALL_METRICS)
def test_reader_over_a_parent_s_window(name):
    """The parent's replies lack the new counters: the four metrics over
    them read None and none raises; every other reads what the parent
    always gave; with nothing kept at all, all read None."""
    reader = _spec(name)["reader"]
    module = importlib.import_module("readers." + reader["kind"])
    value = module.read(reader, PARENT_CTX)
    if name in COUNTER_METRICS:
        assert value is None
    else:
        assert value is not None and value >= 0
    if name.startswith("rebuild_kernel_roofline"):
        assert 0 < value < 105      # one lost row of the reply's `missing`
    assert module.read(reader, {}) is None


def test_counter_readers_over_a_change_s_replies():
    ctx = {
        "records": {
            "sealed_read": [{"windows": 1, "read_stats": {
                "needles": 40000, "needles_beside_job": 26000,
                "local_fallbacks": 1}}],
            "rebuild": [{"rebuilds": 1,
                         "stage_stats": {"foreground_reads": n}}
                        for n in (380, 420, 400)]},
        "admin": {"/admin/ec/recover_stats": [
            {"decode_apply_seconds_beside_job": 0.1,
             "decode_blocks_beside_job": 10},
            {"decode_apply_seconds_beside_job": 0.5,
             "decode_blocks_beside_job": 110}]}}
    got = {}
    for name in COUNTER_METRICS:
        reader = _spec(name)["reader"]
        got[name] = importlib.import_module(
            "readers." + reader["kind"]).read(reader, ctx)
    assert got == {"sealed_get_beside_job_share": pytest.approx(65.0),
                   "sealed_get_local_fallbacks": 1.0,
                   "recover_decode_apply_beside_job_ms": pytest.approx(4.0),
                   "rebuild_foreground_reads": 400.0}


# -- rehearsals of the cell ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _rehearsal(trace: int, control: str = ""):
    extra = ("--control", control) if control else ()
    proc, result = run_cell(CELL, "--trace", str(trace), *extra)
    return proc.returncode, proc.stdout, proc.stderr, result


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_exits_zero_with_a_whole_result_line(trace):
    code, out, err, result = _rehearsal(trace)
    assert code == 0, err[-2000:]
    assert "REHEARSAL" in out
    check_result_line(result, trace=bool(trace))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 10
    assert result["device"]["platform"] == "cpu"
    assert "programs built inside the window: 0 " in out
    if trace:
        assert set(result["metrics"]) <= TREE.layers_of(CELL)
    else:
        assert set(result["metrics"]) == TREE.ends_of(CELL)
        assert 0 < result["metrics"]["op_p50_ms"]["value"] \
            <= result["metrics"]["op_p95_ms"]["value"]
        assert result["metrics"]["bulk_rate"]["value"] > 0


@pytest.mark.parametrize("name", COMPARED)
def test_rehearsal_prints_every_number_compared_beside_its_limit(name):
    code, out, err, result = _rehearsal(0)
    assert result["compared"][name] == {"value": 0, "limit": 0}
    assert f"compared {name}: 0 (limit 0)" in err
    assert list(result["compared"]) == list(COMPARED)


def test_rehearsal_reads_both_volumes_beside_whole_rebuilds():
    code, out, err, result = _rehearsal(0)
    assert "volume 1 sealed as device-pooled-swar, shards [0] deleted" in out
    assert re.search(r"volume 2 sealed as device-pooled-swar; warm-up "
                     r"rebuild of shards \[0\] took \S+ s as "
                     r"device-apply-xla", out), out[-3000:]
    assert "2.0% an object of volume 2, uniform over its 133" in out
    m = re.search(r"block lookups of volume 1: the program made (\d+), the "
                  r"reference needs (\d+) for its (\d+) reads completed",
                  out)
    assert m and m.group(1) == m.group(2) and int(m.group(1)) > 0
    gets = int(re.search(r"window: 4 closed-loop callers for \S+ s: (\d+) "
                         r"reads", out).group(1))
    assert 0 < int(m.group(3)) <= gets
    m = re.search(r"(\d+) whole rebuilds of \d+ volume bytes .* (\d+) "
                  r"refused", out)
    rebuilds, refused = map(int, m.groups())
    assert rebuilds >= 1 and refused == 0
    assert result["attempted"] == gets + rebuilds
    m = re.search(r"(\d+) GETs overlapped a rebuild's span .* (\d+) none "
                  r".*; (\d+) began inside one", out)
    beside, between, began = map(int, m.groups())
    assert beside + between == gets and 0 < began <= beside
    m = re.search(r"(\d+) rebuilt shards kept by a hard link before their "
                  r"delete, (\d+) digested beside the loop, (\d+) differ",
                  out)
    # every rebuild but the last, which stays mounted for the checks
    assert tuple(map(int, m.groups())) == (rebuilds - 1, rebuilds - 1, 0)
    assert re.search(r"read back \d+ of 133 objects: 0 wrong, 0 through a "
                     r"recover", out)


@pytest.mark.parametrize("name", ALL_METRICS)
def test_traced_rehearsal_reports_the_metric_or_says_nothing_to_read(name):
    code, out, err, result = _rehearsal(1)
    assert code == 0, err[-2000:]
    # no TPU plane in a CPU trace; and nobody need have stood in a queue,
    # or read between two rebuilds, in two seconds
    may_be_silent = ("recover_kernel_us", "rebuild_kernel_us",
                     "rebuild_kernel_roofline", "recover_decode_queue_ms",
                     "get_between_rebuilds_p50_ms",
                     "recover_decode_apply_beside_job_ms")
    if name not in result["metrics"]:
        assert name.startswith(may_be_silent), name
        assert f"per-layer {name}: nothing to read" in out
        return
    assert not name.startswith(may_be_silent[:3])
    assert result["metrics"][name]["unit"] == LAYER[name]["unit"]
    assert result["metrics"][name]["value"] >= 0.0
    if name in ("repair_duty_share", "sealed_get_beside_job_share",
                "recover_cache_hit_share." + CELL):
        assert 0 < result["metrics"][name]["value"] <= 100
    if name == "rebuild_foreground_reads":
        assert result["metrics"][name]["value"] > 0


def test_flipped_get_body_byte_makes_correct_false():
    """The control: an answer altered where the client receives it; the
    server served the read whole, so volume 1's lookups still add up."""
    code, out, err, result = _rehearsal(0, "get_body")
    assert code == 0, err[-2000:]
    assert "CONTROL: one byte of one GET body flipped" in out
    assert result["correct"] is False and result["failed"] == 1
    bad = {k for k, c in result["compared"].items()
           if c["value"] > c["limit"]}
    assert bad == {"reads_not_equal_to_their_put"}
    assert result["compared"]["reads_not_equal_to_their_put"]["value"] == 1


def test_flipped_shard_file_byte_makes_correct_false():
    """The control: one byte of a survivor of the repaired volume flipped
    after the window: the file changed, its CRC misses the `.vif`."""
    code, out, err, result = _rehearsal(0, "shard_file")
    assert code == 0, err[-2000:]
    assert re.search(r"CONTROL: one byte of bench_2\.ec11 flipped", out)
    assert result["correct"] is False and result["failed"] == 0
    bad = {k for k, c in result["compared"].items()
           if c["value"] > c["limit"]}
    assert {"survivor_files_changed", "shard_crc32c_differ_from_vif"} <= bad
    assert not bad & set(COMPARED[:10])      # the reads' side stands
