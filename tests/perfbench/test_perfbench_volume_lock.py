"""What PR 44 added to the benchmark: two per-layer metrics of the layer
"Volume server (Python)", `volume_lock_wait_ms` and `volume_lock_held_ms`,
each a data file for the `prometheus` reader that is there and one entry
appended to `per_layer`.  They read what an acquisition of `Volume.lock`
by the served needle methods cost in the window, asking until had and had
until released
(`SeaweedFS_volumeServer_volume_lock_seconds_total{phase}` summed over
`op`, over `SeaweedFS_volumeServer_volume_lock_total`, in ms).  Here: the
structural checks on the names, the reader over scrape pairs made by hand
and over two scrapes of a live volume server, and over a program without
the families (a parent's): nothing to read, and no error.  No cell is
rehearsed again for this."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT  # noqa: E402

import cluster  # noqa: E402
from readers import prometheus  # noqa: E402

TREE = checks.Tree(ROOT)
NAMES = {"volume_lock_wait_ms": "wait", "volume_lock_held_ms": "held"}
SECONDS = "SeaweedFS_volumeServer_volume_lock_seconds_total"
COUNT = "SeaweedFS_volumeServer_volume_lock_total"


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def test_the_entries_are_behind_the_accepted_prefix_and_pr_42_s():
    names = list(TREE.layer)
    checks.check_accepted_prefix(TREE)
    for name in NAMES:
        assert names.index(name) >= len(checks.ACCEPTED_PER_LAYER)
        assert names.index(name) > names.index(
            "compiles_in_window.s3-warp-mixed")
        # the one cell whose list a test holds with `==` is left alone
        assert name not in TREE.layers_of("rebuild-4lost")
    assert names[-2:] == list(NAMES)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("check", ["per_layer_entries",
                                   "layer_metric_files"])
def test_structural_check_on_the_new_names(check, name):
    one = {"per_layer_entries": checks.check_metric_entry,
           "layer_metric_files": checks.check_layer_metric_file}[check]
    one(TREE, name)


@pytest.mark.parametrize("name", NAMES)
def test_each_names_its_layer_its_cell_and_the_reader_that_is_there(name):
    entry, spec = TREE.layer[name], _spec(name)
    assert entry["workloads"] == spec["workloads"] == ["put-get-open"]
    assert entry["layer"] == TREE.layer["volume_put_ms"]["layer"] \
        == "Volume server (Python)"
    assert (entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == ("ms", "lower", "goodput",
                                 "program_counter")
    assert spec["reader"] == {
        "kind": "prometheus", "family": SECONDS,
        "labels": {"phase": NAMES[name]}, "stat": "delta_ratio",
        "scale": 1000.0, "over": {"family": COUNT}}


def _scrape(acquisitions, wait_s, held_s):
    """A volume server's `/metrics` as the reader sees it: so many
    acquisitions by op, and the seconds they waited and held."""
    out = []
    for op, n in acquisitions.items():
        out.append((COUNT, {"op": op}, float(n)))
        out.append((SECONDS, {"op": op, "phase": "wait"},
                    wait_s.get(op, 0.0)))
        out.append((SECONDS, {"op": op, "phase": "held"},
                    held_s.get(op, 0.0)))
    return out


ZERO = _scrape({"write": 0, "read": 0, "delete": 0}, {}, {})


@pytest.mark.parametrize("before,after,reads", [
    # 1,000 PUTs that waited 4 ms and held 2, 1,000 GETs 1 and 0.5
    (ZERO, _scrape({"write": 1000, "read": 1000, "delete": 0},
                   {"write": 4.0, "read": 1.0},
                   {"write": 2.0, "read": 0.5}), (2.5, 1.25)),
    # only what the window added counts
    (_scrape({"write": 2000, "read": 0, "delete": 0},
             {"write": 9.0}, {"write": 3.0}),
     _scrape({"write": 2500, "read": 500, "delete": 0},
             {"write": 9.5, "read": 0.25}, {"write": 3.25, "read": 0.05}),
     (0.75, 0.3)),
    # a window in which nobody took the lock has no denominator
    (ZERO, ZERO, (None, None)),
], ids=["puts_and_gets", "a_window_s_delta", "no_acquisition"])
def test_reader_over_a_scrape_pair(before, after, reads):
    got = tuple(prometheus.read(_spec(name)["reader"],
                                {"prom": [before, after], "counts": {}})
                for name in NAMES)
    assert got == pytest.approx(reads)


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_s_scrapes_read_as_nothing(name):
    """A parent's `/metrics` has neither family: the metric is left out
    of its line."""
    reader = _spec(name)["reader"]
    old = [("SeaweedFS_volumeServer_request_seconds_count",
            {"type": "write"}, 100.0)]
    new = [("SeaweedFS_volumeServer_request_seconds_count",
            {"type": "write"}, 300.0)]
    assert prometheus.read(reader, {"prom": [old, new],
                                    "counts": {}}) is None
    assert prometheus.read(reader, {"prom": None, "counts": {}}) is None


def test_reader_over_a_live_volume_server_s_two_scrapes(tmp_path):
    """Above 0 and under the handlers' own mean over a few PUTs and
    GETs of one caller; the samples stand in a scrape from the start."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    vs = VolumeServer([str(tmp_path)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    try:
        vs.heartbeat_once()
        a = call(master.address, "/dir/assign")
        url, vid = a["url"], int(a["fid"].split(",")[0])
        before = cluster.scrape(url)
        for op in ("write", "read", "delete"):
            assert any(n == COUNT and lab == {"op": op}
                       for n, lab, _ in before)
        for i in range(6):
            call(url, f"/{vid},{i + 1:x}0a0b0c0d", raw=b"x" * 1024,
                 method="POST")
            call(url, f"/{vid},{i + 1:x}0a0b0c0d")
        ctx = {"prom": [before, cluster.scrape(url)], "counts": {}}
        handler_ms = prometheus.read(
            _spec("volume_put_ms")["reader"], ctx)
        for name in NAMES:
            ms = prometheus.read(_spec(name)["reader"], ctx)
            assert 0 < ms < handler_ms
    finally:
        vs.stop()
        master.stop()
