"""What PR 45 added to the benchmark as new files: the configuration
`filer-ycsb-1chip`, the cells `ycsb-a` and `ycsb-b` with their driver
`ycsb_filer` (which starts a stand-alone `weed.py filer` itself, through
the harness's own `Daemons`, and stages the loaded state with the
program's own writers), the plain reference `reference_ycsb` and ten
per-layer metrics.  The manifest's additions against every structural
check; the reference alone (FNV and zipfian against numbers reckoned by
hand, the register against a brute-force search over every interleaving of
two writers and a reader); the driver's draws against the reference's; the
readers over a parent's scrapes (none of the program's new families:
nothing to read, no error) and over the change's; the staged state
against the same records loaded through `POST`; and REHEARSALS on the CPU
backend (400 records, 4 callers, no chip, no timing assertion) with the
control."""

import http.client
import itertools
import json
import os
import random
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402

import reference_ycsb as ref  # noqa: E402
from drivers import ycsb_filer as driver  # noqa: E402
from readers import count, harness_span, prometheus_delta  # noqa: E402

TREE = checks.Tree(ROOT)
LAYER = TREE.layer
CONFIG = "filer-ycsb-1chip"
CELLS = {"ycsb-a": "op_p95_ms", "ycsb-b": "op_p50_ms"}
MIXES = {"ycsb-a": {"read": 50, "update": 50},
         "ycsb-b": {"read": 95, "update": 5}}
KEPT_FROM = "s3-gateway-1chip"

# name -> (cell, stage of filer_stage_seconds_total)
STAGE_METRICS = {"filer_http_write_ms": ("ycsb-a", "http_write"),
                 "filer_lock_wait_ms": ("ycsb-a", "lock_wait"),
                 "filer_lock_held_ms": ("ycsb-a", "lock_held"),
                 "filer_store_write_ms": ("ycsb-a", "store_write"),
                 "filer_reclaim_ms": ("ycsb-a", "reclaim"),
                 "filer_http_read_ms": ("ycsb-b", "http_read")}
CLIENT_METRICS = {"ycsb_update_p50_ms": ("ycsb-a", "update"),
                  "ycsb_read_p50_ms": ("ycsb-b", "read")}
OPS_METRICS = {"ycsb_ops_per_s.ycsb-a": "ycsb-a",
               "ycsb_ops_per_s.ycsb-b": "ycsb-b"}
ALL_METRICS = (
    "ycsb_update_p50_ms", "ycsb_ops_per_s.ycsb-a", "filer_http_write_ms",
    "filer_lock_wait_ms", "filer_lock_held_ms", "filer_store_write_ms",
    "filer_reclaim_ms", "ycsb_read_p50_ms", "ycsb_ops_per_s.ycsb-b",
    "filer_http_read_ms")
OF_CELL = {cell: tuple(m for m in ALL_METRICS
                       if LAYER[m]["workloads"] == [cell]) for cell in CELLS}

COMPARED = (
    "operations_failed", "get_bodies_torn_or_never_written", "stale_reads",
    "listing_names_missing", "listing_names_extra",
    "standing_versions_wrong",
    "superseded_chunks_not_deleted_or_standing_ones_deleted",
    "deleted_bytes_off_the_superseded_chunks",
    "device_touch_seals_missing_or_off_device",
)


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def _traffic(name):
    return TREE.load("perfbench", "traffic", name + ".json")


def _config(name=CONFIG):
    return TREE.load("perfbench", "configs", name + ".json")


# -- the manifest's new entries -----------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_and_its_metrics_are_in_the_manifest(cell):
    entry = TREE.cells[cell]
    assert entry == {**entry, "config": CONFIG, "traffic": cell, "chips": 1}
    # one latency metric a cell, the one that lies inside one mode of its
    # mix; `goodput` stays put-get-open's (an accepted test holds its list)
    assert TREE.ends_of(cell) == {CELLS[cell], "setup_s"}
    assert TREE.end[CELLS[cell]]["workloads"][-2:].count(cell) == 1
    assert TREE.end["goodput"]["workloads"] == ["put-get-open"]
    assert _traffic(cell)["reports"] == [CELLS[cell]]
    assert TREE.layers_of(cell) == set(OF_CELL[cell])
    for m in OF_CELL[cell]:
        assert LAYER[m]["moves"] == CELLS[cell]
    for word in ("16 closed-loop", "zipfian 0.99", "1 KB", "filer",
                 "host's cell", "device idle",
                 "read {read} / update {update}".format(**MIXES[cell])):
        assert word in entry["why"], word


def test_the_new_entries_follow_the_accepted_ones_and_fill_the_list():
    names = list(LAYER)
    assert tuple(names[-len(ALL_METRICS):]) == ALL_METRICS
    assert names[-len(ALL_METRICS) - 1] == "volume_lock_held_ms"
    # the per-layer list may hold 128 and an accepted test grows a copy by
    # one more: ten were free, so fourteen of ISSUE 45's twenty-four stay
    # in the driver's log (PERF.md, section 7)
    assert len(names) == 127
    assert list(TREE.cells)[-2:] == list(CELLS)
    assert list(TREE.configs)[-1] == CONFIG
    assert sum(1 for w in TREE.cells.values() if w["chips"] == 4) == 1
    for old in ("s3-warp-mixed", "rebuild-4lost"):
        assert not any(old in LAYER[m]["workloads"] for m in ALL_METRICS)


@pytest.mark.parametrize("check,name", [
    *[("cells", c) for c in CELLS], ("configs", CONFIG),
    *[("per_layer_entries", m) for m in ALL_METRICS],
    *[("layer_metric_files", m) for m in ALL_METRICS]],
    ids=lambda v: v)
def test_structural_check_on_each_new_name(check, name):
    one = {"cells": checks.check_cell, "configs": checks.check_config,
           "per_layer_entries": checks.check_metric_entry,
           "layer_metric_files": checks.check_layer_metric_file}[check]
    one(TREE, name)


@pytest.mark.parametrize("check", sorted(checks.CHECKS))
def test_whole_tree_passes_with_the_cells_in_it(check):
    checks.CHECKS[check](TREE)


def test_configuration_keeps_the_gateway_s_tier_and_states_what_it_adds():
    new, old = _config(), _config(KEPT_FROM)
    for key in ("env", "rehearse_env", "expect", "rehearse_expect",
                "chips", "volume_size_limit_mb", "flush_policy"):
        if key == "flush_policy":
            assert new[key].split(";")[0] == old[key].split(";")[0]
        else:
            assert new[key] == old[key], key
    assert "-fsync" in new["flush_policy"]
    by_name = {d["name"]: d["args"] for d in new["daemons"]}
    was = {d["name"]: d["args"] for d in old["daemons"]}
    assert list(by_name) == ["master", "volume"]    # what Cluster starts
    assert by_name["master"] == was["master"]
    i = was["volume"].index("-max")
    assert by_name["volume"][:i + 1] == was["volume"][:i + 1]
    assert by_name["volume"][i + 2:] == was["volume"][i + 2:]
    assert by_name["volume"][i + 1] == "16"
    # the filer: read by the driver, not by Cluster
    (gw,) = new["gateways"]
    assert gw["name"] == "filer" and gw["args"][0] == "filer"
    assert {a for a in gw["args"] if a.startswith("{")} == {
        "{master}", "{filer_port}", "{filer_db}"}
    flags = dict(zip(gw["args"][1::2], gw["args"][2::2]))
    assert flags["-saveToFilerLimit"] == "0" and flags["-maxMB"] == "4"
    assert set(new["guarantees"]) == {
        "read_your_acknowledged_write", "overwrite", "durability",
        "replication"}
    for key, words in {
            "read_your_acknowledged_write": ("linearizable register",
                                             "byte for byte", "torn",
                                             "acknowledged before"),
            "overwrite": ("exactly recordcount", "superseded", "leaked"),
            "durability": ("one committed", "-fsync"),
            "replication": ("000",)}.items():
        for word in words:
            assert word in new["guarantees"][key], (key, word)
    assert set(new["reduced"]) == {"recordcount", "operationcount"}
    for key in ("ycsb_properties", "http_for_grpc", "threadcount",
                "-saveToFilerLimit", "-maxMB", "load_phase", "field_bytes",
                "filer_defaults", "-max", "-pulseSeconds", "page_cache",
                "WEED_MAINT", "WEED_EC_DEVICE_SHARD", "compile_cache"):
        assert key in new["assumed"], key
    assert "as recalled" in new["assumed"]["ycsb_properties"]
    assert new["assumed"]["WEED_MAINT"].startswith("0:")
    assert len(new["source"]) <= 200 and new["source"] != old["source"]
    for words in ("YCSB core workloads A and B", "workloada", "workloadb",
                  "seaweedfs binding", "`weed filer`", "-saveToFilerLimit 0",
                  "-maxMB 4"):
        assert words in new["source"], words


@pytest.mark.parametrize("cell", CELLS)
def test_no_shape_of_the_source_is_cut(cell):
    r, t = _config()["records"], _traffic(cell)
    assert (r["fieldcount"], r["fieldlength"]) == (10, 100) \
        == (t["fieldcount"], t["fieldlength"])
    assert r["recordcount"] == t["records"] == 100_000 >= 65_536
    assert r["threadcount"] == t["clients"] == 16
    assert (r["requestdistribution"], r["zipfian_constant"],
            r["insertorder"]) == ("zipfian", 0.99, "hashed")
    assert r["readallfields"] is True and r["writeallfields"] is False
    assert "one GET and one POST" in r["update"]
    workload = {"ycsb-a": "workloada", "ycsb-b": "workloadb"}[cell]
    assert r["mixes_percent"][workload] == t["mix"] == MIXES[cell]
    assert t["folder"] == r["folder"] == "/ycsb/usertable"
    assert t["driver"] == "ycsb_filer" and t["admin_snapshots"] == []
    assert ref.ZIPFIAN_CONSTANT == r["zipfian_constant"]
    # the master's own growth count under replication 000
    assert t["volumes"] == 7
    pgo = _traffic("put-get-open")
    assert t["device_touch"]["at_s"] == pgo["device_touch"]["at_s"] == 3.0
    assert t["device_touch"]["volume"] == pgo["device_touch"]["volume"]
    assert "host's" in t["device_touch"]["why"]
    small = t["rehearse"]
    assert (small["records"], small["clients"]) == (400, 4)
    assert small["stage_workers"] == 0      # no worker processes in a test
    assert "fieldlength" not in small and "mix" not in small
    both = [{k: v for k, v in _traffic(c).items()
             if k not in ("what", "source", "mix", "reports")} for c in CELLS]
    assert both[0] == both[1]       # one deployment, two mixes


@pytest.mark.parametrize("name", ALL_METRICS)
def test_new_metric_lists_one_cell_and_reads_the_named_source(name):
    entry, spec = LAYER[name], _spec(name)
    reader = spec["reader"]
    if name in STAGE_METRICS:
        cell, stage = STAGE_METRICS[name]
        assert reader == {
            "kind": "prometheus_delta", "scrapes": "records.filer_prom",
            "num": [{"family": "SeaweedFS_filer_stage_seconds_total",
                     "labels": {"stage": stage}}],
            "den": [{"family": "SeaweedFS_filer_stage_blocks_total",
                     "labels": {"stage": stage}}], "scale": 1000}
        assert (entry["unit"], entry["source"]) == ("ms", "program_span")
        assert entry["layer"] == ("Filer's HTTP front" if "http" in stage
                                  else "Filer mutation")
    elif name in CLIENT_METRICS:
        cell, kind = CLIENT_METRICS[name]
        assert reader == {"kind": "harness_span", "span": "ycsb_" + kind,
                          "stat": "median", "scale": 1000}
        assert kind in driver.KINDS and entry["source"] == "host_clock"
    elif name in OPS_METRICS:
        cell = OPS_METRICS[name]
        assert reader == {"kind": "count", "count": "ycsb_ops_per_s"}
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ops/s", "higher", "host_clock")
    else:
        raise AssertionError(name)
    assert entry["workloads"] == spec["workloads"] == [cell]
    assert entry["moves"] == CELLS[cell]


# -- the reference alone ---------------------------------------------------------

_M = 1 << 64


def _abs_long(h):
    return _M - h if h >= 1 << 63 else h


def test_fnv_against_its_closed_forms():
    # eight zero octets: the basis times the prime eight times over
    assert ref.fnvhash64(0) == _abs_long(
        ref.FNV_OFFSET_BASIS_64 * pow(ref.FNV_PRIME_64, 8, _M) % _M)
    # one low octet set, seven zero ones after it
    for v in (1, 7, 255):
        first = ((ref.FNV_OFFSET_BASIS_64 ^ v) * ref.FNV_PRIME_64) % _M
        assert ref.fnvhash64(v) == _abs_long(
            first * pow(ref.FNV_PRIME_64, 7, _M) % _M)
    # the octets go in low one first, the sign is taken off at the end
    assert ref.fnvhash64(0x0100) != ref.fnvhash64(0x01)
    assert all(0 <= ref.fnvhash64(v) < 1 << 63 for v in range(2000))
    assert ref.key_of(0) == f"user{ref.fnvhash64(0)}"
    assert ref.key_of(0) == "user6284781860667377211"
    assert len({ref.key_of(r) for r in range(20_000)}) == 20_000


def test_zipfian_ranks_against_hand_computed_boundaries():
    z = ref.Zipfian(10)
    zetan = sum(1 / i ** 0.99 for i in range(1, 11))
    assert z.zetan == pytest.approx(zetan, rel=1e-12)
    assert z.alpha == pytest.approx(100.0)
    eps = 1e-9
    assert z.rank(0.0) == 0 and z.rank(1 / zetan - eps) == 0
    assert z.rank(1 / zetan + eps) == 1
    assert z.rank((1 + 0.5 ** 0.99) / zetan - eps) == 1
    # beyond the two head ranks: items * (eta * u - eta + 1) ** alpha
    eta = (1 - (2 / 10) ** 0.01) / (1 - (1 + 0.5 ** 0.99) / zetan)
    for u in (0.6, 0.75, 0.9, 0.999):
        assert z.rank(u) == int(10 * (eta * u - eta + 1) ** 100)
    ranks = [z.rank(u / 1000) for u in range(1000)]
    assert ranks == sorted(ranks) and set(ranks) <= set(range(10))
    assert ranks.count(0) == pytest.approx(1000 / zetan, abs=1)


def test_scrambled_zipfian_uses_the_fixed_item_space_and_its_zeta():
    s = ref.ScrambledZipfian(100_000)
    assert s.zipfian.items == 10_000_000_001
    assert s.zipfian.zetan == 26.46902820178302
    hot, share = s.hottest()
    assert hot == ref.fnvhash64(0) % 100_000 == 77211
    assert share == pytest.approx(0.03778, abs=1e-5)
    rng = random.Random(5)
    drawn = [s.record(rng.random()) for _ in range(40_000)]
    assert all(0 <= r < 100_000 for r in drawn)
    top = max(set(drawn), key=drawn.count)
    assert top == hot
    assert drawn.count(hot) / len(drawn) == pytest.approx(share, abs=0.004)
    second = ref.fnvhash64(1) % 100_000
    assert drawn.count(second) / len(drawn) == pytest.approx(
        0.5 ** 0.99 / s.zipfian.zetan, abs=0.003)
    # the same ranks fall on other records of a smaller key space
    assert ref.ScrambledZipfian(400).hottest()[0] == ref.fnvhash64(0) % 400


def test_records_come_from_the_seed_and_have_the_source_s_shape():
    a, b = ref.Records(123), ref.Records(124)
    body = a.loaded_body(42)
    assert body == ref.Records(123).loaded_body(42) != b.loaded_body(42)
    fields = json.loads(body)
    assert list(fields) == [f"field{j}" for j in range(10)]
    assert all(len(v) == 100 for v in fields.values())
    assert len(body) == 1121 and b" " not in body
    assert set(body) <= set(ref.ALPHABET) | set(b'{}":,fields0123456789')
    assert len({a.loaded_body(r) for r in range(3000)}) == 3000
    values = {a.updated(c, s) for c in range(16) for s in range(200)}
    assert len(values) == 3200 and {len(v) for v in values} == {100}
    assert a.updated(3, 17).startswith("c03-0000017-")


# the register against every interleaving of two writers and a reader

def _interleavings():
    """Every order of the six events w1 began / acked, w2 began / acked,
    read began / ended in which an operation begins before it ends."""
    events = ("b1", "a1", "b2", "a2", "rb", "re")
    for order in itertools.permutations(events):
        at = {e: float(i) for i, e in enumerate(order)}
        if at["b1"] < at["a1"] and at["b2"] < at["a2"] \
                and at["rb"] < at["re"]:
            yield "-".join(order), at


def _legal_bodies(at) -> set:
    """Brute force: the bodies some linearization lets the read return.
    An operation that ended before another began stands before it."""
    spans = {"w1": (at["b1"], at["a1"]), "w2": (at["b2"], at["a2"]),
             "r": (at["rb"], at["re"])}
    legal = set()
    for order in itertools.permutations(spans):
        if any(spans[y][1] < spans[x][0]
               for i, x in enumerate(order) for y in order[i + 1:]):
            continue        # y wholly before x, yet placed after it
        seen = "v0"
        for op in order:
            if op == "r":
                legal.add(seen)
                break
            seen = op
    return legal


@pytest.mark.parametrize("name,at", list(_interleavings()),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_register_accepts_what_a_linearization_allows_and_nothing_else(
        name, at):
    records = ref.Records(9)
    reg = ref.Register(records)
    bodies = {"v0": records.loaded_body(3), "w1": b"first", "w2": b"second"}
    # the writes are reported as a caller would: began before it is sent,
    # acknowledged when the reply is read, both possibly after the read
    versions = {}
    for event in sorted(at, key=at.get):
        if event in ("b1", "b2"):
            w = "w" + event[1]
            versions[w] = reg.write_began(3, bodies[w], at[event])
        elif event in ("a1", "a2"):
            reg.write_acked(versions["w" + event[1]], at[event])
    legal = _legal_bodies(at)
    assert legal
    for which, body in bodies.items():
        verdict = reg.check_read(3, at["rb"], at["re"], body)
        assert (verdict == ref.OK) == (which in legal), (which, verdict)
        if which not in legal:
            # a version written wholly after the read, or one superseded
            # before it began: never "unknown", that is for foreign bytes
            assert verdict in (ref.STALE, ref.UNKNOWN)
    assert reg.check_read(3, at["rb"], at["re"], b"fir") == ref.UNKNOWN
    assert reg.check_read(3, at["rb"], at["re"],
                          bodies["v0"][:-1] + b"]") == ref.UNKNOWN


def test_register_names_a_stale_and_a_torn_read_and_an_unacknowledged_one():
    records = ref.Records(9)
    reg = ref.Register(records)
    v0 = records.loaded_body(5)
    w = reg.write_began(5, b"newer", 1.0)
    # in flight: both may be read
    assert reg.check_read(5, 2.0, 3.0, v0) == ref.OK
    assert reg.check_read(5, 2.0, 3.0, b"newer") == ref.OK
    reg.write_acked(w, 4.0)
    assert reg.check_read(5, 3.5, 5.0, v0) == ref.OK      # began before
    assert reg.check_read(5, 4.5, 5.0, v0) == ref.STALE   # after the ack
    assert reg.check_read(5, 4.5, 5.0, b"newer") == ref.OK
    assert reg.check_read(5, 4.5, 5.0, b"newe") == ref.UNKNOWN
    assert reg.check_read(5, 0.1, 0.5, b"newer") == ref.UNKNOWN  # not yet
    # a write never acknowledged keeps what it replaced readable
    reg.write_began(5, b"lost?", 6.0)
    assert reg.check_read(5, 9.0, 9.5, b"newer") == ref.OK
    assert reg.check_read(5, 9.0, 9.5, b"lost?") == ref.OK
    assert reg.written() == [5] and reg.acknowledged_writes() == 1
    # three versions were written, one stands
    assert reg.superseded_bytes({5: b"lost?"}) == (2, len(v0) + 5)
    assert reg.superseded_bytes({5: b"newer"}) == (2, len(v0) + 5)
    assert reg.check_read(6, 0.0, 1.0, records.loaded_body(6)) == ref.OK


def test_reference_imports_nothing_of_the_program_or_the_driver():
    with open(ref.__file__) as f:
        src = f.read()
    for word in ("seaweedfs", "import drivers", "from drivers", "loadgen",
                 "import cluster", "http"):
        assert word not in src.replace("seaweedfs_tpu/", "").replace(
            "`seaweedfs` binding", ""), word


# -- the driver's own arithmetic ----------------------------------------------------

def test_the_driver_s_draws_are_the_reference_s_generator_on_its_streams():
    t = {**_traffic("ycsb-a"), **_traffic("ycsb-a")["rehearse"]}
    run = SimpleNamespace(traffic=t, seed=2147483777, workdir="/nowhere",
                          log=lambda m: None, span=lambda *a: None,
                          control=lambda name, body: body)
    state = driver.State(run)
    state.keys = [ref.key_of(r) for r in range(t["records"])]
    asked = []

    class Client:
        def ask(self, method, path, body=None, headers=None):
            asked.append((method, path))
            record = state.keys.index(path.rsplit("/", 1)[1])
            return 200, state.records.loaded_body(record)

    keyspace = ref.ScrambledZipfian(t["records"])
    for caller in (0, 3):
        rng, mirror = driver.draws(run.seed, caller), random.Random(
            run.seed * 7919 + caller + 10007)
        for _ in range(50):
            took, ok = driver.op_read(state, Client(), caller, rng)
            assert ok and took >= 0
            want = keyspace.record(mirror.random())
            assert asked[-1] == ("GET", f"/ycsb/usertable/{ref.key_of(want)}")
    # the set-up's callers draw from other streams than the window's
    assert driver.draws(5, 1, warm=True).random() \
        != driver.draws(5, 1).random()
    assert state.wrong_bodies == state.stale_reads == 0


def test_an_update_is_a_get_and_a_post_of_the_same_path_one_field_changed():
    t = {**_traffic("ycsb-a"), **_traffic("ycsb-a")["rehearse"]}
    run = SimpleNamespace(traffic=t, seed=11, workdir="/nowhere",
                          log=lambda m: None, span=lambda *a: None,
                          control=lambda name, body: body)
    state = driver.State(run)
    state.keys = [ref.key_of(r) for r in range(t["records"])]
    store, asked = {}, []

    class Client:
        def ask(self, method, path, body=None, headers=None):
            asked.append((method, path, headers))
            record = state.keys.index(path.rsplit("/", 1)[1])
            if method == "POST":
                store[record] = body
                return 201, b"{}"
            return 200, store.get(record, state.records.loaded_body(record))

    rng = driver.draws(run.seed, 2)
    for n in range(30):
        took, ok = driver.op_update(state, Client(), 2, rng)
        assert ok
        (get, path, _), (post, same, headers) = asked[-2:]
        assert (get, post) == ("GET", "POST") and path == same
        assert headers == {"Content-Type": "application/json"}
    assert state.sequence[2] == 30
    for record, body in store.items():
        was = json.loads(state.records.loaded_body(record))
        now = json.loads(body)
        changed = [k for k in was if was[k] != now[k]]
        assert 1 <= len(changed) <= 10 and list(now) == list(was)
        assert all(now[k].startswith("c02-") for k in changed)
        assert state.register.check_read(record, 1e18, 2e18, body) == ref.OK
    assert state.register.acknowledged_writes() == 30
    chunks, nbytes = state.register.superseded_bytes(store)
    assert chunks == 30 and nbytes == 30 * 1121


# -- the readers over a parent's scrapes and over the change's -------------------

# what a parent's filer exports: the families that were there
PARENT_FILER = [
    *[("SeaweedFS_filer_stage_seconds_total", {"stage": s}, v)
      for s, v in (("meta_save", 9.0), ("lookup", 2.0), ("chunk_fetch", 4))],
    *[("SeaweedFS_filer_stage_blocks_total", {"stage": s}, n)
      for s, n in (("meta_save", 1000), ("lookup", 3000),
                   ("chunk_fetch", 3000))],
    ("SeaweedFS_filer_request_total", {"type": "write"}, 1000),
]
# what this PR's program adds to them
CHANGE_FILER = [
    *[("SeaweedFS_filer_stage_seconds_total", {"stage": s}, v)
      for s, v in (("http_write", 40.0), ("lock_wait", 25.0),
                   ("lock_held", 4.0), ("store_write", 3.0),
                   ("reclaim", 5.0), ("http_read", 18.0), ("notify", 0.1))],
    *[("SeaweedFS_filer_stage_blocks_total", {"stage": s}, n)
      for s, n in (("http_write", 1000), ("lock_wait", 1000),
                   ("lock_held", 1000), ("store_write", 1000),
                   ("reclaim", 1000), ("http_read", 3000),
                   ("notify", 1000))],
    ("SeaweedFS_filer_overwrites_total", {}, 1000),
]
WANT_ON_THE_CHANGE = {
    "filer_http_write_ms": 40.0, "filer_lock_wait_ms": 25.0,
    "filer_lock_held_ms": 4.0, "filer_store_write_ms": 3.0,
    "filer_reclaim_ms": 5.0, "filer_http_read_ms": 6.0,
    "ycsb_update_p50_ms": 30.0, "ycsb_read_p50_ms": 10.0,
    "ycsb_ops_per_s.ycsb-a": 512.5, "ycsb_ops_per_s.ycsb-b": 512.5,
}


def _ctx(filer):
    """A window that began with every counter at 0."""
    rows = [(f, l, float(v)) for f, l, v in filer]
    return {"records": {"filer_prom": [
                {"samples": [(f, l, 0.0) for f, l, _ in rows]},
                {"samples": rows}]},
            "prom": [[], []],
            "spans": {"ycsb_read": [(1.0, 1.01)] * 3,
                      "ycsb_update": [(1.0, 1.03)] * 3},
            "counts": {"ycsb_ops_per_s": 512.5}, "log": print}


def _read(name, ctx):
    reader = _spec(name)["reader"]
    kind = {"prometheus_delta": prometheus_delta, "count": count,
            "harness_span": harness_span}[reader["kind"]]
    return kind.read(reader, ctx)


@pytest.mark.parametrize("name", ALL_METRICS)
def test_metric_reads_nothing_on_a_parent_and_its_number_on_the_change(name):
    from_the_program = name in STAGE_METRICS
    on_parent = _read(name, _ctx(PARENT_FILER))
    if from_the_program:
        assert on_parent is None
        assert _read(name, {"records": {}, "counts": {}, "spans": {}}) is None
        assert _read(name, {"records": {"filer_prom": [
            {"samples": []}, {"samples": []}]}}) is None
    else:
        assert on_parent == pytest.approx(WANT_ON_THE_CHANGE[name])
    got = _read(name, _ctx(PARENT_FILER + CHANGE_FILER))
    assert got == pytest.approx(WANT_ON_THE_CHANGE[name])


# -- the staged state against the same records loaded through POST ---------------

def _get(addr, path):
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _post(addr, path, body):
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("POST", path, body=body,
                     headers={"Content-Type": driver.RECORD_MIME})
        resp = conn.getresponse()
        resp.read()
        return resp.status
    finally:
        conn.close()


def test_the_staged_state_is_served_as_the_same_records_loaded_by_post(
        tmp_path):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from seaweedfs_tpu.filer.filer_store import SqliteStore
    from seaweedfs_tpu.filer.server import FilerServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    records, nvolumes, seed, folder = 60, 2, 31, "/ycsb/usertable"
    t = {**_traffic("ycsb-a"), "records": records, "volumes": nvolumes,
         "stage_workers": 0, "clients": 2}
    run = SimpleNamespace(traffic=t, seed=seed, workdir=str(tmp_path),
                          log=lambda m: None)
    state = driver.State(run)
    driver.stage(run, state)
    assert state.needle_overhead == 35
    assert state.keys == [ref.key_of(r) for r in range(records)]

    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    (tmp_path / "v").mkdir()
    vs = VolumeServer([str(tmp_path / "v")], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    cluster = SimpleNamespace(volume=vs.address, master=master.address,
                              vol_dir=str(tmp_path / "v"),
                              daemons=SimpleNamespace(check_alive=lambda: 0))
    driver.mount_staged(SimpleNamespace(cluster=cluster), state)
    staged = FilerServer(master.address, port=0, store=SqliteStore(state.db),
                         save_to_filer_limit=0)
    posted = FilerServer(master.address, port=0,
                         store=SqliteStore(str(tmp_path / "posted.db")),
                         save_to_filer_limit=0)
    staged.start()
    posted.start()
    try:
        for r in range(records):
            assert _post(posted.address, state.path(r),
                         state.records.loaded_body(r)) == 200
        # the master raised its needle ids over the staged ones: no POST
        # landed on a staged needle
        for r in range(records):
            a = _get(staged.address, state.path(r))
            b = _get(posted.address, state.path(r))
            assert a[0] == b[0] == 200
            assert a[2] == b[2] == state.records.loaded_body(r)
            for header in ("Content-Type", "Content-Length", "Etag",
                           "Accept-Ranges"):
                assert a[1][header] == b[1][header], header
            ea = staged.filer.find_entry(state.path(r)).to_dict()
            eb = posted.filer.find_entry(state.path(r)).to_dict()
            (ca,), (cb,) = ea.pop("chunks"), eb.pop("chunks")
            for d in (ea["attr"], eb["attr"]):
                d.pop("mtime"), d.pop("crtime")
            assert ea == eb
            assert {k: ca[k] for k in ca if k not in ("fid", "file_id",
                                                      "modified_ts_ns")} \
                == {k: cb[k] for k in cb if k not in ("fid", "file_id",
                                                      "modified_ts_ns")}
        la = json.loads(_get(staged.address, folder + "/?limit=1000")[2])
        lb = json.loads(_get(posted.address, folder + "/?limit=1000")[2])
        names = [e["FullPath"] for e in la["Entries"]]
        assert names == [e["FullPath"] for e in lb["Entries"]]
        assert sorted(names) == sorted(f"{folder}/{k}" for k in state.keys)
        # an overwrite costs the volume server the same bytes whichever
        # way the record came: a staged needle is a served one
        def deleted():
            vols = vs.store.collect_heartbeat()["volumes"]
            return sum(v["deleted_byte_count"] for v in vols)

        d0 = deleted()
        assert _post(staged.address, state.path(7), b"x" * 1121) == 200
        d1 = deleted()
        assert _post(posted.address, state.path(7), b"x" * 1121) == 200
        assert d1 - d0 == deleted() - d1 == 1121 + state.needle_overhead
    finally:
        staged.stop()
        posted.stop()
        vs.stop()
        master.stop()


# -- REHEARSALS on the CPU backend ---------------------------------------------------

@pytest.fixture(scope="module")
def rehearsals():
    """One traced and one untraced run of each mix, shared by the tests
    below: (cell, trace) -> (process, result)."""
    runs = {}

    def get(cell, trace):
        if (cell, trace) not in runs:
            runs[cell, trace] = run_cell(cell, "--trace", str(trace),
                                         seconds=3)
        return runs[cell, trace]

    return get


@pytest.mark.parametrize("cell,trace", [("ycsb-a", 1), ("ycsb-b", 0),
                                        ("ycsb-b", 1), ("ycsb-a", 0)])
def test_rehearsal_runs_the_mix_and_is_correct(rehearsals, cell, trace):
    proc, result = rehearsals(cell, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    check_result_line(result, trace=bool(trace))
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert tuple(result["compared"]) == COMPARED
    assert all(c["limit"] == 0 for c in result["compared"].values())
    if trace:
        # the per-layer line holds every metric that lists the cell
        assert set(result["metrics"]) == set(OF_CELL[cell])
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        assert set(result["metrics"]) == {CELLS[cell], "setup_s"}
    log = proc.stdout
    assert "REHEARSAL on the CPU backend" in log
    assert "the filer's stages, ms a block x blocks:" in log
    for stage in ("lock_wait", "lock_held", "store_write", "reclaim",
                  "http_read", "http_write", "lookup", "chunk_fetch",
                  "meta_save"):
        assert f"{stage} " in log.split("the filer's stages")[1], stage
    mix = MIXES[cell]
    shares = {kind: float(log.split(f"  {kind}: ")[1].split("(")[1]
                          .split("%")[0]) for kind in mix}
    assert shares["read"] == pytest.approx(mix["read"], abs=12)
    assert "programs built inside the window: 0 " in log


def test_control_get_body_reads_incorrect():
    proc, result = run_cell("ycsb-b", "--control", "get_body", seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert result["correct"] is False
    assert result["compared"]["get_bodies_torn_or_never_written"][
        "value"] == 1
    wrong = [n for n, c in result["compared"].items()
             if c["value"] > c["limit"]]
    assert wrong == ["get_bodies_torn_or_never_written"]


def test_a_tree_whose_filer_does_not_know_its_flags_fails_at_once(tmp_path):
    """What a parent does under this PR's benchmark: `weed.py filer` exits
    on `-saveToFilerLimit`, and the run fails before anything is staged."""
    import shutil
    import subprocess
    import time

    root = tmp_path / "parent"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "seaweedfs_tpu"), root / "seaweedfs_tpu")
    os.symlink(os.path.join(ROOT, "native"), root / "native")
    with open(os.path.join(ROOT, "weed.py")) as f:
        src = f.read()
    flag = '    p.add_argument("-saveToFilerLimit"'
    assert flag in src
    head, _, tail = src.partition(flag)
    tail = tail.split("    p.add_argument(", 1)[1]
    with open(root / "weed.py", "w") as f:      # the flag, taken out again
        f.write(head + "    p.add_argument(" + tail.replace(
            "save_to_filer_limit=args.saveToFilerLimit", ""))
    t0 = time.monotonic()
    proc, result = run_cell("ycsb-a", root=str(root), seconds=2)
    took = time.monotonic() - t0
    assert proc.returncode == 1 and result is None
    assert "filer exited early with code 2" in proc.stderr
    assert "unrecognized arguments: -saveToFilerLimit" in proc.stderr
    assert "staged" not in proc.stdout
    assert took < 60
    left = subprocess.run(["pgrep", "-f", str(root)], capture_output=True,
                          text=True).stdout.split()
    assert not left
