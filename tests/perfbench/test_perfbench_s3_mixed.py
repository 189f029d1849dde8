"""What PR 42 added to the benchmark as new files: the configuration
`s3-gateway-1chip`, the cell `s3-warp-mixed` with its driver `s3_mixed`
(which starts the gateway itself, through the harness's own `Daemons`), the
plain reference `reference_s3`, the reader `prometheus_delta` and 24
per-layer metrics.  The manifest's additions against every structural
check; the driver's own arithmetic; the reader over a parent's scrapes
(none of the program's new families: nothing to read, no error) and over
the change's; and REHEARSALS on the CPU backend (12 objects of 10 MiB, no
chip, no timing assertion) with the control."""

import functools
import importlib
import os
import random
import re
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402

import reference_s3  # noqa: E402
from drivers import s3_mixed as driver  # noqa: E402
from readers import prometheus_delta  # noqa: E402

TREE = checks.Tree(ROOT)
LAYER = TREE.layer
CELL = "s3-warp-mixed"
CONFIG = "s3-gateway-1chip"
KEPT_FROM = "warm-ec-rs10.4-1chip"

CLIENT_METRICS = ("s3_get_p50_ms", "s3_put_p50_ms", "s3_stat_p50_ms",
                  "s3_delete_p50_ms")
# operations acknowledged and right, a second: counted by the driver, and a
# per-layer number here (PERF.md, section 2)
OPS_PER_S = "s3_ops_per_s"
# from families this PR added to the program: absent on a parent
S3_STAGE_METRICS = {"s3_auth_ms": "auth", "s3_lookup_ms": "lookup",
                    "s3_get_ms": "get", "s3_put_ms": "put",
                    "s3_delete_ms": "delete"}
FILER_STAGE_METRICS = {"filer_assign_ms": "assign",
                       "filer_chunk_upload_ms": "chunk_upload",
                       "filer_meta_save_ms": "meta_save",
                       "filer_chunk_fetch_ms": "chunk_fetch"}
SENDFILE_METRICS = ("sendfile_share", "sendfile_waits_per_get")
NEW_FAMILY_METRICS = (tuple(S3_STAGE_METRICS) + tuple(FILER_STAGE_METRICS)
                      + SENDFILE_METRICS)
# from families the gateway had: a parent's gateway would give them
OLD_FAMILY_METRICS = ("s3_http_reply_ms", "filer_chunk_cache_hit_share")
# accepted readers under this cell's names
RENAMED = ("volume_get_ms", "volume_put_ms", "http_reply_ms", "gil_wait_ms",
           "device_init_s", "compiles_in_window")
ALL_METRICS = (
    CLIENT_METRICS + (OPS_PER_S,) + tuple(S3_STAGE_METRICS)
    + ("s3_http_reply_ms",)
    + tuple(FILER_STAGE_METRICS) + ("filer_chunk_cache_hit_share",)
    + tuple(f"{stem}.{CELL}" for stem in RENAMED[:4]) + SENDFILE_METRICS
    + tuple(f"{stem}.{CELL}" for stem in RENAMED[4:]))

COMPARED = (
    "operations_failed", "get_bodies_not_equal_to_their_put",
    "heads_and_etags_not_equal_to_the_reference",
    "listing_keys_missing_or_different", "listing_keys_extra",
    "listing_out_of_order", "live_keys_with_a_wrong_head",
    "deleted_keys_still_answered", "live_objects_not_read_back",
    "deleted_objects_still_read",
    "deleted_payload_bytes_the_volume_server_does_not_count",
    "wrongly_signed_requests_not_refused",
    "device_touch_seals_missing_or_off_device",
)


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def _traffic(name=CELL):
    return TREE.load("perfbench", "traffic", name + ".json")


def _config(name=CONFIG):
    return TREE.load("perfbench", "configs", name + ".json")


# -- the manifest's new entries -----------------------------------------------

def test_the_cell_the_configuration_and_the_metrics_are_in_the_manifest():
    cell = TREE.cells[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": CELL, "chips": 1}
    assert CELL in TREE.cells and CONFIG in TREE.configs
    # `goodput` is not among them: twelve windows of one tree spread wider
    # than half its bound on the driver's machines (PERF.md, section 2)
    assert TREE.ends_of(CELL) == {"op_p50_ms", "op_p95_ms", "setup_s"}
    assert TREE.end["goodput"]["workloads"] == ["put-get-open"]
    assert set(_traffic()["reports"]) == TREE.ends_of(CELL) - {"setup_s"}
    for end in TREE.ends_of(CELL) - {"setup_s"}:
        assert CELL in TREE.end[end]["workloads"]
    # the cell's metrics follow the 91 accepted before this PR, in order
    names = list(LAYER)
    assert len(ALL_METRICS) == 24 and len(set(ALL_METRICS)) == 24
    assert TREE.layers_of(CELL) == set(ALL_METRICS)
    assert tuple(names[91:91 + len(ALL_METRICS)]) == ALL_METRICS
    assert names[90] == "put_master_lookups_per_put"
    assert sum(1 for w in TREE.cells.values() if w["chips"] == 4) == 1
    for word in ("warp mixed", "20 closed-loop", "GET 45 / STAT 30 / PUT 15"
                 " / DELETE 10", "10 MiB", "S3 gateway", "host's cell",
                 "device idle"):
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


def test_configuration_keeps_the_one_chip_tier_and_states_what_it_adds():
    new, old = _config(), _config(KEPT_FROM)
    for key in ("env", "rehearse_env", "expect", "rehearse_expect",
                "chips", "volume_size_limit_mb"):
        assert new[key] == old[key], key
    assert new["flush_policy"].startswith(old["flush_policy"].split(";")[0])
    assert "-fsync" in new["flush_policy"]
    # the master as it is; the volume server with room for the window
    by_name = {d["name"]: d["args"] for d in new["daemons"]}
    was = {d["name"]: d["args"] for d in old["daemons"]}
    assert list(by_name) == ["master", "volume"]    # what Cluster starts
    assert by_name["master"] == was["master"]
    i = was["volume"].index("-max")
    assert by_name["volume"][:i + 1] == was["volume"][:i + 1]
    assert by_name["volume"][i + 2:] == was["volume"][i + 2:]
    assert (was["volume"][i + 1], by_name["volume"][i + 1]) == ("16", "32")
    assert "-ecBackend" in by_name["volume"]
    assert not any("-tcp" in d["args"] for d in new["daemons"])
    # the gateway: read by the driver, not by Cluster
    (gw,) = new["gateways"]
    assert gw["name"] == "s3" and gw["args"][0] == "s3"
    assert {a for a in gw["args"] if a.startswith("{")} == {
        "{master}", "{s3_port}", "{s3_db}", "{s3_identities}"}
    assert "-db" in gw["args"] and "-config" in gw["args"]
    assert gw["identity"]["actions"] == ["Admin"]
    assert set(new["guarantees"]) == {
        "read_your_acknowledged_write", "delete", "authentication",
        "listing", "replication"}
    for key, words in {
            "read_your_acknowledged_write": ("200", "byte-identical", "GET",
                                             "HEAD", "ETag"),
            "delete": ("204", "404", "listing", "counts"),
            "authentication": ("SigV4", "payload hash", "403"),
            "replication": ("000",)}.items():
        for word in words:
            assert word in new["guarantees"][key], (key, word)
    assert set(new["reduced"]) == {"objects", "duration"}
    assert "2,500 -> 250" in new["reduced"]["objects"]
    for key in ("warp_defaults", "signed_payload", "no_multipart", "-maxMB",
                "filer_chunk_cache", "-tcp", "-max", "-pulseSeconds",
                "page_cache", "WEED_MAINT"):
        assert key in new["assumed"], key
    assert new["assumed"]["WEED_MAINT"].startswith("0:")
    assert new["env"] == {"WEED_EC_DEVICE_SHARD": "1", "WEED_MAINT": "0"}
    assert len(new["source"]) <= 200 and new["source"] != old["source"]
    for words in ("MinIO warp `mixed`", "2,500 x 10 MiB", "20 concurrent",
                  "GET 45 / STAT 30 / PUT 15 / DELETE 10", "`weed s3`",
                  "-maxMB 4"):
        assert words in new["source"], words
    # no width is cut: object size, chunk size, the mix, the callers
    t = _traffic()
    assert new["objects"]["bytes"] == t["object_bytes"] == 10 << 20
    assert new["objects"]["callers"] == t["clients"] == 20
    assert new["objects"]["mix_percent"] == t["mix"]
    assert new["filer"]["chunk_bytes"] == 4 << 20
    assert (new["objects"]["count"], t["objects"]) == (2500, 250)


def test_traffic_is_warp_mixed_with_put_get_open_s_device_touch():
    t, pgo = _traffic(), _traffic("put-get-open")
    assert t["driver"] == "s3_mixed" and t["bucket"] == "warp"
    assert t["mix"] == {"get": 45, "stat": 30, "put": 15, "delete": 10}
    assert sum(t["mix"].values()) == 100
    assert t["device_touch"]["at_s"] == pgo["device_touch"]["at_s"] == 3.0
    assert t["device_touch"]["volume"] == pgo["device_touch"]["volume"]
    assert "host's" in t["device_touch"]["why"]
    assert t["admin_snapshots"] == []
    r = t["rehearse"]
    assert (r["clients"], r["objects"]) == (4, 12)
    assert "object_bytes" not in r      # 10 MiB in the rehearsal too: above
    # one filer chunk and above the socket's buffer


@pytest.mark.parametrize("name", ALL_METRICS)
def test_new_metric_lists_the_cell_alone_and_reads_the_named_source(name):
    entry, spec = LAYER[name], _spec(name)
    assert entry["workloads"] == spec["workloads"] == [CELL]
    reader = spec["reader"]
    if name == OPS_PER_S:
        assert reader == {"kind": "count", "count": OPS_PER_S}
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ops/s", "higher", "host_clock")
    elif name in CLIENT_METRICS:
        kind = name[len("s3_"):-len("_p50_ms")]
        assert reader == {"kind": "harness_span", "span": "s3_" + kind,
                          "stat": "median", "scale": 1000}
        assert kind in driver.KINDS and entry["source"] == "host_clock"
    elif name in S3_STAGE_METRICS or name in FILER_STAGE_METRICS:
        who = "s3" if name in S3_STAGE_METRICS else "filer"
        stage = {**S3_STAGE_METRICS, **FILER_STAGE_METRICS}[name]
        assert reader["kind"] == "prometheus_delta"
        assert reader["scrapes"] == "records.s3_prom"
        (num,), (den,) = reader["num"], reader["den"]
        assert num == {"family": f"SeaweedFS_{who}_stage_seconds_total",
                       "labels": {"stage": stage}}
        assert den == {"family": f"SeaweedFS_{who}_stage_blocks_total",
                       "labels": {"stage": stage}}
        assert entry["source"] == "program_span"
        assert entry["layer"] == ("S3 gateway" if who == "s3" else "Filer")
    elif name in SENDFILE_METRICS:
        assert reader["kind"] == "prometheus_delta"
        assert reader["scrapes"] == "prom"      # the volume server's
        assert entry["layer"] == "Volume server (Python)"
    elif name in OLD_FAMILY_METRICS:
        assert reader["scrapes"] == "records.s3_prom"
    else:
        accepted = name[:-len(CELL) - 1]
        assert accepted in RENAMED
        assert reader == _spec(accepted)["reader"]
        for key in ("unit", "better", "source", "layer"):
            assert entry[key] == LAYER[accepted][key], key
    # what a PUT costs moves the tail, what a GET or a HEAD costs the median
    if re.search(r"put|assign|upload|meta_save", name):
        assert entry["moves"] == "op_p95_ms"
    assert entry["moves"] in TREE.ends_of(CELL)


# -- the driver's own arithmetic ----------------------------------------------------

def _state(clients=4, object_bytes=1000, pool=5000):
    run = SimpleNamespace(traffic={"clients": clients, "bucket": "warp",
                                   "object_bytes": object_bytes},
                          seed=7, log=lambda m: None)
    state = driver.State(run)
    state.pool = random.Random(1).randbytes(pool)
    return state


def test_pool_of_live_keys_against_a_set():
    state, rng, want = _state(), random.Random(42), set()
    for i in range(2000):
        if want and rng.random() < 0.45:
            key = rng.choice(sorted(want))
            state.take_out(key)
            want.discard(key)
        else:
            key = f"k{i}"
            state.add(key)
            want.add(key)
        assert set(state.live) == want == set(state.where)
        assert all(state.live[i] == k for k, i in state.where.items())


def test_every_key_is_new_and_its_window_lies_inside_the_pool():
    state = _state(clients=3)
    seen = {}
    for n in range(50):
        for c in range(3):
            key = state.new_key(c)
            off = state.offsets[key]
            assert key not in seen and 0 <= off <= 4000
            seen[key] = off
            assert bytes(state.body_of(key)) == state.pool[off:off + 1000]
    assert len(set(seen.values())) > 100     # windows differ key to key
    assert sorted(seen) == sorted(seen, key=str.encode)
    assert state.path("c00/000001.rnd") == "/warp/c00/000001.rnd"
    assert state.path() == "/warp"


def test_a_body_is_compared_whole_whatever_holds_it():
    want = memoryview(random.Random(3).randbytes(4096))
    buf = bytearray(want)
    assert driver._same_bytes(memoryview(buf), want)
    assert driver._same_bytes(bytes(want), want)
    assert driver._same_bytes(memoryview(bytearray(want) + b"xx")[:4096],
                              want)
    buf[4095] ^= 1
    assert not driver._same_bytes(memoryview(buf), want)
    assert not driver._same_bytes(bytes(want)[:-1], want)


def test_a_head_is_held_to_status_size_and_etag():
    b = reference_s3.Bucket()
    b.put("k", reference_s3.describe(b"x" * 10))
    live, dead = b.head("k"), b.head("gone")
    good = {"Content-Length": "10", "ETag": live.etag}
    assert driver._head_equal(200, good, live)
    assert not driver._head_equal(200, {**good, "Content-Length": "11"},
                                  live)
    assert not driver._head_equal(200, {**good, "ETag": '"0"'}, live)
    assert not driver._head_equal(404, {}, live)
    assert driver._head_equal(404, {}, dead)
    assert not driver._head_equal(200, good, dead)
    assert not driver._head_equal(None, {}, dead)   # lost in transport


def test_driver_imports_nothing_of_the_program():
    for module in (driver, reference_s3, prometheus_delta):
        with open(module.__file__) as f:
            src = f.read()
        assert "import seaweedfs_tpu" not in src
        assert "from seaweedfs" not in src


# -- the reader over a parent's scrapes and over the change's ----------------------

def _samples(rows):
    return [(family, labels, float(value)) for family, labels, value in rows]


VOLUME = {"service": "volume"}
# what a parent's daemons export: the families that were there
PARENT_GATEWAY = [
    ("SeaweedFS_filer_chunk_cache_total", {"result": "hit"}, 40),
    ("SeaweedFS_filer_chunk_cache_total", {"result": "miss"}, 60),
    ("SeaweedFS_rpc_server_stage_seconds",
     {"service": "s3", "route": "*", "method": "GET", "stage": "reply"},
     0.5),
    ("SeaweedFS_rpc_server_requests_total",
     {"service": "s3", "route": "*", "method": "GET", "requests": "timed"},
     10),
    ("SeaweedFS_s3_request_total", {"action": "get_object", "code": "200"},
     100),
]
PARENT_VOLUME = [
    ("SeaweedFS_gateway_sendfile_bytes_total", VOLUME, 4e9),
    ("SeaweedFS_volumeServer_request_seconds_sum", {"type": "read"}, 3.0),
    ("SeaweedFS_volumeServer_request_seconds_count", {"type": "read"}, 1000),
    ("SeaweedFS_volumeServer_request_seconds_sum", {"type": "write"}, 9.0),
    ("SeaweedFS_volumeServer_request_seconds_count", {"type": "write"}, 300),
    ("SeaweedFS_rpc_server_stage_seconds",
     {"service": "volume", "route": "*", "method": "GET", "stage": "reply"},
     0.2),
    ("SeaweedFS_rpc_server_requests_total",
     {"service": "volume", "route": "*", "method": "GET",
      "requests": "timed"}, 100),
    ("SeaweedFS_profiler_gil_wait_seconds_sum", {}, 0.5),
    ("SeaweedFS_profiler_gil_wait_seconds_count", {}, 1000),
    ("SeaweedFS_volumeServer_startup_seconds", {"phase": "device_init"},
     10.5),
]
# what this PR's program adds to them
CHANGE_GATEWAY = [
    *[("SeaweedFS_s3_stage_seconds_total", {"action": a, "stage": s}, v)
      for a, s, v in (("get_object", "auth", 0.2), ("put_object", "auth", 1),
                      ("head_object", "auth", 0.1),
                      ("delete_object", "auth", 0.1),
                      ("get_object", "lookup", 0.3),
                      ("head_object", "lookup", 0.3),
                      ("get_object", "get", 30.0), ("put_object", "put", 60),
                      ("head_object", "head", 0.6),
                      ("delete_object", "delete", 20.0))],
    *[("SeaweedFS_s3_stage_blocks_total", {"action": a, "stage": s}, n)
      for a, s, n in (("get_object", "auth", 450), ("put_object", "auth", 150),
                      ("head_object", "auth", 300),
                      ("delete_object", "auth", 100),
                      ("get_object", "lookup", 450),
                      ("head_object", "lookup", 300),
                      ("get_object", "get", 450), ("put_object", "put", 150),
                      ("head_object", "head", 300),
                      ("delete_object", "delete", 100))],
    *[("SeaweedFS_filer_stage_seconds_total", {"stage": s}, v)
      for s, v in (("assign", 0.45), ("chunk_upload", 45.0),
                   ("meta_save", 1.5), ("chunk_fetch", 13.5))],
    *[("SeaweedFS_filer_stage_blocks_total", {"stage": s}, n)
      for s, n in (("assign", 450), ("chunk_upload", 450),
                   ("meta_save", 150), ("chunk_fetch", 1350))],
]
CHANGE_VOLUME = [
    ("SeaweedFS_gateway_pread_bytes_total", VOLUME, 1e9),
    ("SeaweedFS_gateway_sendfile_waits_total", VOLUME, 250),
]
WANT_ON_THE_CHANGE = {
    "s3_auth_ms": 1.4, "s3_lookup_ms": 0.8, "s3_get_ms": 30e3 / 450,
    "s3_put_ms": 400.0, "s3_delete_ms": 200.0,
    "filer_assign_ms": 1.0, "filer_chunk_upload_ms": 100.0,
    "filer_meta_save_ms": 10.0, "filer_chunk_fetch_ms": 10.0,
    "sendfile_share": 80.0, "sendfile_waits_per_get": 0.25,
    "s3_http_reply_ms": 50.0, "filer_chunk_cache_hit_share": 40.0,
    OPS_PER_S: 170.5,
}


def _ctx(gateway, volume):
    """A window that began with every counter at 0."""
    zeros = lambda rows: _samples((f, l, 0) for f, l, _ in rows)  # noqa: E731
    return {
        "records": {"s3_prom": [{"samples": zeros(gateway)},
                                {"samples": _samples(gateway)}]},
        "prom": [zeros(volume), _samples(volume)],
        "spans": {"s3_" + k: [(1.0, 1.0 + 0.01 * (i + 1))] * 3
                  for i, k in enumerate(driver.KINDS)},
        "counts": {"programs_built_in_window": 0, OPS_PER_S: 170.5},
        "log": print,
    }


def _read(name, ctx):
    reader = _spec(name)["reader"]
    return importlib.import_module("readers." + reader["kind"]).read(
        reader, ctx)


@pytest.mark.parametrize("name", ALL_METRICS)
def test_reader_over_a_parent_s_window(name):
    """A parent's program has none of this PR's families: every metric
    over them reads None and none raises; the others read what a parent
    always exported; with nothing kept at all, all read None."""
    value = _read(name, _ctx(PARENT_GATEWAY, PARENT_VOLUME))
    if name in NEW_FAMILY_METRICS:
        assert value is None
    else:
        assert value is not None and value >= 0
    assert _read(name, {}) is None
    assert _read(name, {"records": {"s3_prom": [{"samples": []}] * 2},
                        "prom": [[], []]}) is None


@pytest.mark.parametrize("name", sorted(WANT_ON_THE_CHANGE))
def test_reader_over_the_change_s_window(name):
    ctx = _ctx(PARENT_GATEWAY + CHANGE_GATEWAY,
               PARENT_VOLUME + CHANGE_VOLUME)
    assert _read(name, ctx) == pytest.approx(WANT_ON_THE_CHANGE[name])


def test_prometheus_delta_takes_deltas_and_says_nothing_when_it_cannot():
    spec = {"kind": "prometheus_delta", "scrapes": "records.r",
            "num": [{"family": "a", "labels": {"x": "1"}}],
            "den": [{"family": "a", "labels": {"x": "1"}},
                    {"family": "b"}], "scale": 100}
    before = _samples([("a", {"x": "1"}, 10), ("a", {"x": "2"}, 99),
                       ("b", {}, 5)])
    after = _samples([("a", {"x": "1"}, 40), ("a", {"x": "2"}, 999),
                      ("b", {}, 15)])
    ctx = {"records": {"r": [{"samples": before}, {"samples": after}]}}
    assert prometheus_delta.read(spec, ctx) == pytest.approx(75.0)
    # a family that first shows inside the window counts from 0
    ctx["records"]["r"][0] = {"samples": before[:2]}
    assert prometheus_delta.read(spec, ctx) == pytest.approx(
        100 * 30 / 45)
    # a term that never shows: nothing, unless it may count as 0
    ctx["records"]["r"][1] = {"samples": after[:2]}
    assert prometheus_delta.read(spec, ctx) is None
    spec["den"][1]["or_zero"] = True
    assert prometheus_delta.read(spec, ctx) == pytest.approx(100.0)
    # one scrape, a scrape the driver could not make, a denominator of 0
    assert prometheus_delta.read(spec, {"records": {"r": [{}]}}) is None
    assert prometheus_delta.read(
        spec, {"records": {"r": [{"samples": []}, {"samples": []}]}}) is None
    same = {"records": {"r": [{"samples": after}, {"samples": after}]}}
    assert prometheus_delta.read(spec, same) is None
    # without `den` the plain delta
    assert prometheus_delta.read(
        {"scrapes": "prom", "num": spec["num"]},
        {"prom": [before, after]}) == 30.0


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
    assert result["attempted"] >= 20
    assert result["device"]["platform"] == "cpu"
    assert "programs built inside the window: 0 " in out
    if trace:
        assert set(result["metrics"]) <= TREE.layers_of(CELL)
    else:
        assert set(result["metrics"]) == TREE.ends_of(CELL)
        assert 0 < result["metrics"]["op_p50_ms"]["value"] \
            <= result["metrics"]["op_p95_ms"]["value"]
        assert "goodput" not in result["metrics"]
        m = re.search(r"  (\S+) operations a second\n", out)
        assert float(m.group(1)) == pytest.approx(
            result["attempted"] / 2, rel=0.2)  # a window of two seconds


@pytest.mark.parametrize("name", COMPARED)
def test_rehearsal_prints_every_number_compared_beside_its_limit(name):
    code, out, err, result = _rehearsal(0)
    assert result["compared"][name] == {"value": 0, "limit": 0}
    assert f"compared {name}: 0 (limit 0)" in err
    assert list(result["compared"]) == list(COMPARED)


def test_rehearsal_runs_the_gateway_and_the_mix_over_ten_mib_objects():
    code, out, err, result = _rehearsal(0)
    assert re.search(r"s3 gateway on 127\.0\.0\.1:\d+", out)
    assert re.search(r"12 objects of 10485760 bytes PUT by 4 callers", out)
    assert "16 GETs and HEADs of the set-up answered as the reference " \
           "does" in out
    m = re.search(r"window: 4 closed-loop callers for \S+ s: (\d+) "
                  r"operations, 0 failed or wrong", out)
    assert m and int(m.group(1)) == result["attempted"]
    done = {k: int(n) for k, n in re.findall(
        r"  (get|stat|put|delete): (\d+) ok of \2 ", out)}
    assert set(done) == set(driver.KINDS) and min(done.values()) > 0
    assert sum(done.values()) == result["attempted"]
    m = re.search(r"ListObjectsV2: (\d+) rows, the reference holds (\d+); "
                  r"0 missing or different, 0 extra; order kept", out)
    assert m and m.group(1) == m.group(2)
    assert int(m.group(1)) == 12 + done["put"] - done["delete"]
    m = re.search(r"HEAD of (\d+) live keys: 0 wrong; of (\d+) deleted "
                  r"keys: 0 not 404", out)
    assert (int(m.group(1)), int(m.group(2))) == (
        12 + done["put"] - done["delete"], done["delete"])
    m = re.search(r"the volume server counts (\d+) more bytes deleted; the "
                  r"deleted objects' payload is (\d+)", out)
    counted, owed = map(int, m.groups())
    assert counted >= owed == done["delete"] * (10 << 20)
    # the window by slices, what PERF.md section 2 reads
    assert re.search(r"operations a second by 4 s slices: \d+", out)
    assert re.search(r"PUTs and DELETEs a second by 4 s slices: [\d.]+", out)
    assert re.search(r"stalls \(no answer for over 0\.1 s\): \d+, ", out)
    m = re.search(r"the device touch \(one ec\.encode of a small volume\) "
                  r"ran from (\S+) to (\S+) s of the window", out)
    assert 0.5 <= float(m.group(1)) < float(m.group(2))
    assert "a request signed with another secret -> 403; an unsigned one " \
           "-> 403" in out


@pytest.mark.parametrize("name", ALL_METRICS)
def test_traced_rehearsal_reports_every_metric_of_the_list(name):
    code, out, err, result = _rehearsal(1)
    assert code == 0, err[-2000:]
    # 1 request in 100 of the gateway is timed by RpcServer: a window of
    # two seconds may hold none
    if name not in result["metrics"]:
        assert name == "s3_http_reply_ms", name
        assert f"per-layer {name}: nothing to read" in out
        return
    assert result["metrics"][name]["unit"] == LAYER[name]["unit"]
    assert result["metrics"][name]["value"] >= 0.0
    if name in ("sendfile_share", "filer_chunk_cache_hit_share"):
        assert result["metrics"][name]["value"] <= 100
    if name == "sendfile_share":
        assert result["metrics"][name]["value"] > 90
    if name in NEW_FAMILY_METRICS and name != "sendfile_waits_per_get":
        assert result["metrics"][name]["value"] > 0


def test_flipped_get_body_byte_makes_correct_false():
    """The control: an answer altered where the client receives it."""
    code, out, err, result = _rehearsal(0, "get_body")
    assert code == 0, err[-2000:]
    assert "CONTROL: one byte of one GET body flipped" in out
    assert result["correct"] is False and result["failed"] == 1
    bad = {k for k, c in result["compared"].items()
           if c["value"] > c["limit"]}
    assert bad == {"get_bodies_not_equal_to_their_put"}
    assert result["compared"]["get_bodies_not_equal_to_their_put"][
        "value"] == 1
