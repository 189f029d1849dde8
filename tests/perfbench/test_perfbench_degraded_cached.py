"""What PR 36 added to the benchmark as new files: the configuration
`warm-ec-rs10.4-1chip-1lost`, the cell `degraded-get-cached` with its
driver `zipf_sealed_reads`, the reference of a sealed read
(`reference_reads.py`) and twelve per-layer metrics.  The deployment in
numbers, reckoned from the layout; the rank map and the draw streams; the
reference against a brute-force walk; the readers over a parent's replies;
and REHEARSALS on the CPU backend (a tiny volume, no chip, no timing
assertion) with the control.  The structural checks are `bench_checks.py`'s,
run here on the cell's own names."""

import functools
import importlib
import os
import random
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402

import reference  # noqa: E402
import reference_reads  # noqa: E402
import volumes  # noqa: E402
from drivers import zipf_sealed_reads as driver  # noqa: E402

TREE = checks.Tree(ROOT)
LAYER = TREE.layer
CELL = "degraded-get-cached"
CONFIG = "warm-ec-rs10.4-1chip-1lost"

# the program's account of its sealed reads: new in PR 36, absent on a parent
READ_STATS_METRICS = {
    "sealed_get_locate_ms": "read_stats.locate_seconds",
    "sealed_get_shard_read_ms": "read_stats.shard_seconds",
    "sealed_get_assemble_ms": "read_stats.assemble_seconds",
    "sealed_get_recovered_share": "read_stats.intervals_recovered",
}
# accepted readers under a name of the new cell: an accepted entry's
# `workloads` cannot grow
RENAMED = ("volume_get_ms", "recover_cache_hit_share", "recover_decode_ms",
           "recover_decode_queue_ms", "recover_stack_blocks",
           "recover_kernel_us", "compiles_in_window", "device_init_s")
ALL_METRICS = tuple(READ_STATS_METRICS) + tuple(
    f"{name}.{CELL}" for name in RENAMED)

COMPARED = (
    "reads_not_equal_to_their_put", "operations_failed", "device_fallbacks",
    "windows_without_device_decodes", "windows_without_lru_hits",
    "windows_without_plain_reads", "windows_without_reads_of_a_lost_block",
    "block_lookups_off_the_reference",
    "setup_seal_not_on_device-pooled-swar_x1",   # the CPU's encode path
)


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def _traffic():
    return TREE.load("perfbench", "traffic", CELL + ".json")


# -- the manifest's new entries -----------------------------------------------

def test_the_cell_the_configuration_and_the_metrics_are_in_the_manifest():
    cell = TREE.cells[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": CELL, "chips": 1}
    assert TREE.ends_of(CELL) == {"op_p50_ms", "op_p95_ms", "setup_s"}
    # `>=`: a later PR may list the cell in a metric of its own
    assert TREE.layers_of(CELL) >= set(ALL_METRICS)
    names = list(LAYER)
    assert names.index(ALL_METRICS[0]) >= len(checks.ACCEPTED_PER_LAYER)
    at = names.index(ALL_METRICS[0])
    assert tuple(names[at:at + len(ALL_METRICS)]) == ALL_METRICS
    for end in ("op_p50_ms", "op_p95_ms"):
        assert TREE.end[end]["workloads"][:2] == ["degraded-get",
                                                  "put-get-open"]
    assert len(TREE.cells) >= 6
    assert sum(1 for w in TREE.cells.values() if w["chips"] == 4) == 1
    for word in ("8 closed-loop", "zipf 0.99", "shard 0", "LRU", "trickle"):
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


def test_configuration_keeps_the_source_s_shape_and_states_what_it_adds():
    new = TREE.load("perfbench", "configs", CONFIG + ".json")
    old = TREE.load("perfbench", "configs", "warm-ec-rs10.4-1chip.json")
    for key in ("code", "volume_size_limit_mb", "volume_shape",
                "flush_policy", "daemons", "env", "expect",
                "rehearse_expect", "chips"):
        assert new[key] == old[key], key
    assert old["guarantees"].items() <= new["guarantees"].items()
    assert "while shard 0 is gone" in new["guarantees"]["availability"]
    assert set(old["assumed"]) < set(new["assumed"])
    for key in ("WEED_MAINT", "WEED_EC_RECOVER_CACHE_MB", "shard_reads",
                "zipf_constant", "rank_order"):
        assert key in new["assumed"], key
    # nothing of the recovery is set: the program's defaults serve
    assert new["env"] == {"WEED_EC_DEVICE_SHARD": "1", "WEED_MAINT": "0"}
    assert old["rehearse_env"].items() <= new["rehearse_env"].items()
    assert new["lost_shards"] == [0]
    assert set(new["reduced"]) == {"volumes"}
    assert len(new["source"]) <= 200 and "config 2" in new["source"]
    # the volume is the one `degraded-get` reads, object for object
    traffic, other = _traffic(), TREE.load("perfbench", "traffic",
                                           "degraded-get.json")
    assert traffic["volume"] == other["volume"]
    assert traffic["rehearse"]["volume"] == other["rehearse"]["volume"]
    assert traffic["warm_stacks"] == other["warm_stacks"]
    assert traffic["clients"] == 8 and traffic["warm_reads"] == 2000
    assert traffic["zipf"]["constant"] == 0.99
    assert traffic["zipf"]["rank_seed_offset"] == 4
    assert traffic["recover_block_bytes"] == reference_reads.RECOVER_BLOCK
    assert "lost_shards" not in traffic      # the configuration's


@pytest.mark.parametrize("name", ALL_METRICS)
def test_new_metric_lists_the_cell_and_reads_the_named_source(name):
    entry, spec = LAYER[name], _spec(name)
    assert entry["workloads"] == spec["workloads"] == [CELL]
    reader = spec["reader"]
    if name in READ_STATS_METRICS:
        assert entry["layer"] == "Sealed read"
        assert reader["kind"] == "harness_record"
        assert reader["record"] == "sealed_read"
        assert reader["key"] == READ_STATS_METRICS[name]
        # a stage's seconds are of the needles that were timed
        assert reader["per"] == ("read_stats.intervals" if entry["unit"] == "%"
                                 else "read_stats.timed_needles")
    else:
        accepted = name[:-len(CELL) - 1]
        assert reader == _spec(accepted)["reader"]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == LAYER[accepted][key], key
    # data only: every kind of reader was there
    assert reader["kind"] in ("harness_record", "admin_json", "prometheus",
                              "prometheus_value", "trace", "count")


# -- which object a rank is, and what the seed draws ---------------------------

def _layout():
    """The 1 GB volume's extents reckoned without writing it: needle k
    lies behind needle k - 1, each `needle_disk_size` of its body (data +
    a 4-byte length + a flag byte), the first behind the 8-byte
    superblock.  Held to the configuration's stated `.dat` size."""
    sizes = volumes.object_sizes(_traffic()["volume"]["objects"], 1)
    pos, extents = 8, []
    for nbytes in sizes:
        length = reference.needle_disk_size(nbytes + 5)
        extents.append((pos, length))
        pos += length
    return sizes, extents, pos


def test_the_deployment_in_numbers():
    sizes, extents, dat_size = _layout()
    shape = TREE.load("perfbench", "configs", CONFIG + ".json")[
        "volume_shape"]
    assert f"{dat_size:,} B" in shape and dat_size == 1_006_723_848
    assert reference_reads.shard_file_size(dat_size) == 97 << 20
    t = _traffic()
    plans = [reference_reads.read_plan(offset, length, dat_size, [0])
             for offset, length in extents]
    degraded = [k for k, p in enumerate(plans) if p["blocks"]]
    large = {k for k, n in enumerate(sizes) if n >= t["large_from_bytes"]}
    assert len(sizes) == 2272 and len(large) == 224
    assert len(degraded) == 325 and len(large & set(degraded)) == 115
    # the lost blocks are 1.5 times the 64 MiB cache
    blocks = {b for p in plans for b in p["blocks"]}
    assert len(blocks) == 385
    assert sum(b[2] for b in blocks) == 385 * (256 << 10) > 1.5 * (64 << 20)
    # a large object holds at most one interval of shard 0, of 1 MiB
    assert max(len(p["recovered"]) for p in plans) == 1
    assert max(len(p["blocks"]) for p in plans) == 4
    perm = driver.rank_order(len(sizes), t["zipf"]["rank_seed_offset"])
    zipf = driver.Zipf(len(sizes), t["zipf"]["constant"])
    on_large = zipf.mass(r for r, k in enumerate(perm) if k in large)
    on_degraded = zipf.mass(r for r, k in enumerate(perm) if plans[k]["blocks"])
    # op_p95_ms lies inside the large reads' mode, op_p50_ms deep inside
    # the small reads': the mass on large objects is held to a band
    assert 0.085 <= on_large <= 0.093
    assert 0.11 <= on_degraded <= 0.125
    # of the first six shuffles, + 4 puts the most mass on large objects
    others = [driver.Zipf(len(sizes), 0.99).mass(
        r for r, k in enumerate(driver.rank_order(len(sizes), off))
        if k in large) for off in range(6)]
    assert max(others) == others[4] == on_large


def test_rank_map_is_one_for_every_seed_and_the_streams_differ():
    n = 2272
    perm = driver.rank_order(n, 4)
    assert sorted(perm) == list(range(n)) and perm != list(range(n))
    assert perm == driver.rank_order(n, 4)     # no run seed in it
    want = list(range(n))
    random.Random(volumes.LAYOUT_SEED + 4).shuffle(want)
    assert perm == want
    assert perm != driver.rank_order(n, 3)
    zipf = driver.Zipf(n, 0.99)

    def stream(seed, caller, k=200):
        rng = random.Random(seed * 7919 + caller)   # the driver's rule
        return [zipf.draw(rng) for _ in range(k)]

    assert stream(123, 0) == stream(123, 0)
    assert stream(123, 0) != stream(124, 0) != stream(123, 1)
    assert all(0 <= r < n for r in stream(2**31 + 5, 7))


def test_zipf_draws_follow_the_weights():
    zipf = driver.Zipf(100, 0.99)
    assert zipf.weights[0] == 1.0
    assert zipf.weights[9] == pytest.approx(10 ** -0.99)
    assert zipf.mass(range(100)) == pytest.approx(1.0)
    rng = random.Random(36)
    draws = [zipf.draw(rng) for _ in range(20000)]
    assert draws.count(0) / len(draws) == pytest.approx(zipf.mass([0]),
                                                        abs=0.01)
    assert max(draws) <= 99

    class Top:      # random() never gives 1.0; a rounding that did is held
        def random(self):
            return 1.0
    assert zipf.draw(Top()) == 99


# -- the reference of a sealed read ---------------------------------------------

def test_reference_reads_imports_nothing_of_the_program():
    with open(os.path.join(TREE.bench, "reference_reads.py")) as f:
        src = f.read()
    assert "import seaweedfs_tpu" not in src and "from seaweedfs" not in src
    imports = re.findall(r"^(?:import|from) (\S+)", src, re.M)
    assert set(imports) == {"__future__", "reference"}


LARGE, SMALL, BLOCK = 4096, 256, 64      # a small layout with large rows


def _walk(offset, length, dat_size):
    """Byte by byte, by the definition of the striping: where each byte
    of the extent lies, gathered into runs."""
    large_rows = (dat_size + 10 * SMALL) // (LARGE * 10)
    large_end = large_rows * LARGE * 10
    runs = []
    for p in range(offset, offset + length):
        if p < large_end:
            index, inner = divmod(p, LARGE)
            at = (index // 10) * LARGE + inner
            block = ("large", index)
        else:
            index, inner = divmod(p - large_end, SMALL)
            at = large_rows * LARGE + (index // 10) * SMALL + inner
            block = ("small", index)
        if runs and runs[-1][3] == block:
            runs[-1][2] += 1
        else:
            runs.append([index % 10, at, 1, block])
    return [tuple(r[:3]) for r in runs]


@pytest.mark.parametrize("dat_size", [100_000, 2_000, 45_000, 83_000])
def test_intervals_and_blocks_against_a_walk_byte_by_block(dat_size):
    rng = random.Random(dat_size)
    shard_size = reference_reads.shard_file_size(dat_size, LARGE, SMALL)
    large_rows = (dat_size + 10 * SMALL) // (LARGE * 10)
    assert shard_size == large_rows * LARGE + -(-max(
        0, dat_size - large_rows * LARGE * 10) // (10 * SMALL)) * SMALL
    for _ in range(60):
        offset = rng.randrange(dat_size)
        length = rng.randrange(1, min(3 * LARGE, dat_size - offset) + 1)
        walk = _walk(offset, length, dat_size)
        got = reference_reads.intervals_of_extent(offset, length, dat_size,
                                                  LARGE, SMALL)
        assert got == walk
        assert sum(n for _, _, n in got) == length
        lost = [rng.randrange(10), 12]
        plan = reference_reads.read_plan(offset, length, dat_size, lost,
                                         LARGE, SMALL, BLOCK)
        assert plan["intervals"] == walk
        assert plan["recovered"] == [iv for iv in walk if iv[0] == lost[0]]
        # every byte of a lost interval lies in exactly one named block
        want = []
        for shard, at, n in plan["recovered"]:
            starts = sorted({(at + i) // BLOCK * BLOCK for i in range(n)})
            want += [(shard, s, min(BLOCK, shard_size - s)) for s in starts]
        assert plan["blocks"] == want


def test_shards_of_extent_agrees_at_the_deployment_s_block_sizes():
    sizes, extents, dat_size = _layout()
    for offset, length in extents[::37]:
        got = reference_reads.intervals_of_extent(offset, length, dat_size)
        assert {s for s, _, _ in got} == reference.shards_of_extent(
            offset, length, dat_size)


def test_a_lost_parity_shard_and_no_loss_need_no_block():
    for lost in ([], [10], [13], [11, 12]):
        plan = reference_reads.read_plan(5_000_000, 4_194_336,
                                         1_006_723_848, lost)
        assert len(plan["intervals"]) == 5
        assert plan["recovered"] == [] and plan["blocks"] == []
    # the file's last block may be short
    assert reference_reads.recovery_blocks(800, 150, 1000, 256) == [
        (768, 232)]
    assert reference_reads.recovery_blocks(250, 10, 1000, 256) == [
        (0, 256), (256, 256)]


# -- laid over the parent's checkout ------------------------------------------

PARENT_CTX = {
    # a parent's window: the driver found no /admin/ec/read_stats and kept
    # no record; everything else is there
    "records": {},
    "admin": {"/admin/ec/recover_stats": [
        {"decode_seconds": 1.0, "decode_queue_seconds": 0.1,
         "cache_misses": 5, "decode_blocks": 5, "decode_batches": 4},
        {"decode_seconds": 2.0, "decode_queue_seconds": 0.3,
         "cache_misses": 9, "decode_blocks": 9, "decode_batches": 7}]},
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
    "counts": {"programs_built_in_window": 0},
    "trace": {"modules": [(0, "jit__apply_pallas(5)", 0.1, 0.00004)]},
    "log": print,
}


@pytest.mark.parametrize("name", ALL_METRICS)
def test_reader_over_a_parent_s_window(name):
    """The parent's program has no `/admin/ec/read_stats`: the four new
    metrics read None there and none raises; the eight renamed readers
    read what the parent always gave."""
    reader = _spec(name)["reader"]
    module = importlib.import_module("readers." + reader["kind"])
    value = module.read(reader, PARENT_CTX)
    if name in READ_STATS_METRICS:
        assert value is None
        for ctx in ({}, {"records": {"sealed_read": []}},
                    {"records": {"sealed_read": [{}]}},
                    {"records": {"sealed_read": [{"read_stats": {
                        "needles": 9, "timed_needles": 0,
                        "intervals": 0}}]}}):
            assert module.read(reader, ctx) is None
    else:
        assert value is not None and value >= 0
    assert module.read(reader, {}) is None


def test_readers_over_a_change_s_record():
    delta = {"locate_seconds": 0.5, "shard_seconds": 0.25,
             "assemble_seconds": 1.0, "needles": 40000, "timed_needles": 1000,
             "intervals": 1400,
             "intervals_plain": 1274, "intervals_recovered": 126}
    ctx = {"records": {"sealed_read": [{"read_stats": delta}]}}
    from readers import harness_record

    got = {name: harness_record.read(_spec(name)["reader"], ctx)
           for name in READ_STATS_METRICS}
    assert got == {"sealed_get_locate_ms": 0.5,
                   "sealed_get_shard_read_ms": 0.25,
                   "sealed_get_assemble_ms": 1.0,
                   "sealed_get_recovered_share": pytest.approx(9.0)}


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
        assert set(result["metrics"]) == TREE.ends_of(CELL) \
            == {"op_p50_ms", "op_p95_ms", "setup_s"}
        assert 0 < result["metrics"]["op_p50_ms"]["value"] \
            <= result["metrics"]["op_p95_ms"]["value"]


@pytest.mark.parametrize("name", COMPARED)
def test_rehearsal_prints_every_number_compared_beside_its_limit(name):
    code, out, err, result = _rehearsal(0)
    assert result["compared"][name] == {"value": 0, "limit": 0}
    assert f'compared: {{"name": "{name}", "value": 0, "limit": 0' in out
    assert f"compared {name}: 0 (limit 0)" in err
    assert list(result["compared"]) == list(COMPARED)


def test_rehearsal_reads_with_and_without_a_lost_block_by_the_fixed_map():
    code, out, err, result = _rehearsal(0)
    # the rehearsal's 133 objects, shard 0 deleted through the admin route
    m = re.search(r"shards \[0\] deleted; 133 objects by zipf 0\.99: (\d+) "
                  r"hold a lost block \((\d+) large\), (\d+) lost blocks",
                  out)
    assert m, out[-3000:]
    holding, large, blocks = map(int, m.groups())
    assert 0 < holding < 133 and 0 < blocks <= 12
    assert "stack warm-up: 4 programs matching" in out
    assert f"LRU warmed: one pass over the {holding} objects" in out
    m = re.search(r"(\d+) reads of large objects \(\S+%\), (\d+) of objects "
                  r"that hold a lost block", out)
    assert 0 < int(m.group(2)) < result["attempted"]
    m = re.search(r"block lookups: the program made (\d+), the reference "
                  r"needs (\d+) for the (\d+) reads completed", out)
    assert m.group(1) == m.group(2) and int(m.group(1)) > 0
    assert int(m.group(3)) == result["attempted"]
    m = re.search(r"recover: (\d+) block lookups, (\d+) hits, (\d+) "
                  r"recovered", out)
    assert int(m.group(2)) > 0 and int(m.group(3)) > 0


@pytest.mark.parametrize("name", ALL_METRICS)
def test_traced_rehearsal_reports_the_metric_or_says_nothing_to_read(name):
    code, out, err, result = _rehearsal(1)
    assert code == 0, err[-2000:]
    if name.startswith("recover_kernel_us"):     # no TPU plane in a CPU trace
        assert name not in result["metrics"]
        assert f"per-layer {name}: nothing to read" in out
        return
    if name.startswith("recover_decode_queue_ms") \
            and name not in result["metrics"]:   # nobody stood in a queue
        assert f"per-layer {name}: nothing to read" in out
        return
    assert result["metrics"][name]["unit"] == LAYER[name]["unit"]
    assert result["metrics"][name]["value"] >= 0.0
    if name == "sealed_get_recovered_share":
        assert 0 < result["metrics"][name]["value"] < 100
    if name.startswith("recover_cache_hit_share"):
        assert 0 < result["metrics"][name]["value"] < 100


def test_flipped_get_body_byte_makes_correct_false():
    """The control: an answer altered where the client receives it; the
    server served the read whole, so the lookups still add up."""
    code, out, err, result = _rehearsal(0, "get_body")
    assert code == 0, err[-2000:]
    assert "CONTROL: one byte of one GET body flipped" in out
    assert result["correct"] is False and result["failed"] == 1
    assert result["compared"]["reads_not_equal_to_their_put"] == {
        "value": 1, "limit": 0}
    assert result["compared"]["operations_failed"]["value"] == 0
    assert result["compared"]["block_lookups_off_the_reference"][
        "value"] == 0
