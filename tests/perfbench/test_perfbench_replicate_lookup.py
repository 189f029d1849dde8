"""What PR 41 added to the benchmark: one per-layer metric of the layer
"Volume server (Python)", `put_master_lookups_per_put`, as a data file for
the `prometheus` reader that is there and one entry appended to
`per_layer`.  It reads how often a served write of the window asked the
master for its volume's other holders
(`SeaweedFS_volumeServer_replicate_total{decision="asked"}` over the
object route's POSTs): 0 under replication 000 since PR 41, 1 where a
served write calls the master again.  Here: the structural checks on the
name, the reader over scrape pairs made by hand and over two scrapes of
a live volume server, and over a program without the family (a
parent's): nothing to read, and no error.  No cell is rehearsed again for
this."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT  # noqa: E402

import cluster  # noqa: E402
from readers import prometheus  # noqa: E402

TREE = checks.Tree(ROOT)
NAME = "put_master_lookups_per_put"
FAMILY = "SeaweedFS_volumeServer_replicate_total"
POSTS = "SeaweedFS_rpc_server_requests_total"
POST_LABELS = {"service": "volume", "route": "*", "method": "POST",
               "requests": "all"}


def _spec():
    return TREE.load("perfbench", "layer_metrics", NAME + ".json")


def test_the_entry_is_behind_the_accepted_prefix_and_pr_40_s_seven():
    names = list(TREE.layer)
    checks.check_accepted_prefix(TREE)
    assert names.index(NAME) >= len(checks.ACCEPTED_PER_LAYER)
    assert names.index(NAME) > names.index("gil_wait_ms")
    # the one cell whose list a test holds with `==` is left alone
    assert NAME not in TREE.layers_of("rebuild-4lost")


@pytest.mark.parametrize("check", ["per_layer_entries",
                                   "layer_metric_files"])
def test_structural_check_on_the_new_name(check):
    one = {"per_layer_entries": checks.check_metric_entry,
           "layer_metric_files": checks.check_layer_metric_file}[check]
    one(TREE, NAME)


def test_it_names_its_layer_its_cell_and_the_reader_that_is_there():
    entry, spec = TREE.layer[NAME], _spec()
    assert entry["workloads"] == spec["workloads"] == ["put-get-open"]
    assert entry["layer"] == TREE.layer["volume_put_ms"]["layer"] \
        == "Volume server (Python)"
    assert (entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == ("count", "lower", "goodput",
                                 "program_counter")
    assert spec["reader"] == {
        "kind": "prometheus", "family": FAMILY,
        "labels": {"decision": "asked"}, "stat": "delta_ratio",
        "over": {"family": POSTS, "labels": POST_LABELS}}


def _scrape(asked, single_copy, posts):
    """A volume server's `/metrics` as the reader sees it, after so many
    decisions and object POSTs (GETs and the timed POSTs beside them)."""
    return [(FAMILY, {"decision": "asked"}, float(asked)),
            (FAMILY, {"decision": "single_copy"}, float(single_copy)),
            (POSTS, POST_LABELS, float(posts)),
            (POSTS, dict(POST_LABELS, requests="timed"), posts // 100.0),
            (POSTS, dict(POST_LABELS, method="GET"), 7.0 * posts)]


@pytest.mark.parametrize("before,after,reads", [
    # replication 000: every POST of the window returned without asking
    (_scrape(0, 2000, 2000), _scrape(0, 23000, 23000), 0.0),
    # a served write that calls the master again: one lookup a POST
    (_scrape(2000, 0, 2000), _scrape(23000, 0, 23000), 1.0),
    # one write in four on a replicated volume
    (_scrape(0, 0, 0), _scrape(50, 150, 200), 0.25),
    # a window without a POST has no denominator: nothing to read
    (_scrape(0, 10, 10), _scrape(0, 10, 10), None),
], ids=["single_copy", "asks_once_a_post", "a_quarter", "no_posts"])
def test_reader_over_a_scrape_pair(before, after, reads):
    assert prometheus.read(_spec()["reader"], {
        "prom": [before, after], "counts": {}}) == reads


def test_a_parent_s_scrapes_read_as_nothing():
    """A parent's `/metrics` has the POSTs (since PR 40) and not the
    family: the metric is left out of its line."""
    reader = _spec()["reader"]
    old = [(POSTS, POST_LABELS, 100.0)]
    new = [(POSTS, POST_LABELS, 300.0),
           ("SeaweedFS_volumeServer_request_seconds_count",
            {"type": "write"}, 200.0)]
    assert prometheus.read(reader, {"prom": [old, new],
                                    "counts": {}}) is None
    assert prometheus.read(reader, {"prom": None, "counts": {}}) is None


def test_reader_over_a_live_volume_server_s_two_scrapes(tmp_path):
    """0.0 over PUTs to a volume of replication 000; 1.0 once its
    placement says two copies (the master still knows one holder, so
    nothing is fanned out, but it was asked)."""
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

        def window(posts):
            before = cluster.scrape(url)
            for i in range(posts):
                call(url, f"/{vid},{i + 1:x}0a0b0c0d", raw=b"x" * 1024,
                     method="POST")
                call(url, f"/{vid},{i + 1:x}0a0b0c0d")
            return prometheus.read(_spec()["reader"], {
                "prom": [before, cluster.scrape(url)], "counts": {}})

        # a server that has just started has the sample, at some count
        assert any(n == FAMILY and lab == {"decision": "asked"}
                   for n, lab, _ in cluster.scrape(url))
        assert window(5) == 0.0
        call(url, "/admin/volume/configure_replication",
             {"volume": vid, "replication": "001"})
        assert window(4) == 1.0
    finally:
        vs.stop()
        master.stop()
