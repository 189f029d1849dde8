"""What PR 40 added to the benchmark: seven per-layer metrics of the layer
"Volume server (Python)", each a data file for the `prometheus` reader that
is there and one entry appended to `per_layer`.  They read what the program
now measures of a request's life around its handler (`http.read` /
`http.handle` / `http.reply` in `RpcServer`), of the sampler's wake
lateness (the GIL's gauge) and, from a family the parent has too, of a
PUT's handler.  Here: the structural checks on the new names, and the
reader over two scrapes of a live `RpcServer` under the volume server's
names, and over two scrapes of a program without the families (a
parent's): nothing to read, and no error.  No cell is rehearsed again for
this."""

import http.client
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT  # noqa: E402

import cluster  # noqa: E402
from readers import prometheus  # noqa: E402

TREE = checks.Tree(ROOT)
FOUR = ["degraded-get", "put-get-open", "degraded-get-cached",
        "degraded-get-under-rebuild"]
# name -> (cells, source)
METRICS = {
    "http_read_ms": (FOUR, "program_span"),
    "http_handle_ms": (FOUR, "program_span"),
    "http_reply_ms": (FOUR, "program_span"),
    "http_request_ms": (FOUR, "program_span"),
    "http_put_request_ms": (["put-get-open"], "program_span"),
    "volume_put_ms": (["put-get-open"], "program_counter"),
    "gil_wait_ms": (FOUR, "program_counter"),
}
NEW_FAMILIES = ("SeaweedFS_rpc_server_stage_seconds",
                "SeaweedFS_rpc_server_requests_total",
                "SeaweedFS_profiler_gil_wait_seconds")


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


def test_the_seven_entries_are_behind_the_accepted_prefix_in_order():
    """Found by name: a later PR appends its entries behind these, or
    between them and whatever else came since, and this test holds."""
    names = list(TREE.layer)
    at = [names.index(name) for name in METRICS]
    assert at == sorted(at)
    checks.check_accepted_prefix(TREE)
    assert at[0] >= len(checks.ACCEPTED_PER_LAYER)
    # the one cell whose list a test holds with `==` is left alone
    assert not TREE.layers_of("rebuild-4lost") & set(METRICS)


@pytest.mark.parametrize("check,name", [
    *[("per_layer_entries", m) for m in METRICS],
    *[("layer_metric_files", m) for m in METRICS]], ids=lambda v: v)
def test_structural_check_on_each_new_name(check, name):
    one = {"per_layer_entries": checks.check_metric_entry,
           "layer_metric_files": checks.check_layer_metric_file}[check]
    one(TREE, name)


@pytest.mark.parametrize("name", METRICS)
def test_new_metric_names_its_layer_its_cells_and_the_reader_that_is_there(
        name):
    entry, spec = TREE.layer[name], _spec(name)
    cells, source = METRICS[name]
    assert entry["workloads"] == spec["workloads"] == cells
    assert entry["layer"] == "Volume server (Python)"
    assert entry["layer"] == TREE.layer["volume_get_ms"]["layer"]
    assert (entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == ("ms", "lower", "op_p50_ms", source)
    reader = spec["reader"]
    assert reader["kind"] == "prometheus" and reader["scale"] == 1000
    if name.startswith("http_"):
        method = "POST" if "put" in name else "GET"
        stage = name.split("_")[-2]
        assert reader["stat"] == "delta_ratio"
        assert reader["labels"] == {"service": "volume", "route": "*",
                                    "method": method, "stage": stage}
        assert reader["over"] == {
            "family": "SeaweedFS_rpc_server_requests_total",
            "labels": {"service": "volume", "route": "*",
                       "method": method, "requests": "timed"}}
    else:
        assert reader["stat"] == "histogram_mean"


# -- the reader over a live server --------------------------------------------

@pytest.fixture
def volume_like(monkeypatch):
    """An `RpcServer` under the volume server's service name whose
    default route is the object route (label `*`), as
    `VolumeServer._handle_object` is: a GET answers 32 KiB, a POST is
    counted into the write histogram as `_write_object` is."""
    from seaweedfs_tpu import profiling
    from seaweedfs_tpu.rpc.http_rpc import Response, RpcServer
    from seaweedfs_tpu.stats import metrics as stats

    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    monkeypatch.setenv("WEED_PROF_HZ", "100")
    srv = RpcServer(service_name="volume")

    def obj(method, req):
        if method == "GET":
            return Response(bytes(32 << 10))
        with stats.VolumeServerRequestHistogram.labels("write").time():
            return {"size": len(req.body)}

    srv.default_route = obj
    srv.add("GET", "/metrics", stats.metrics_handler)
    profiling.mount(srv)    # starts the always-on sampler, as a daemon does
    srv.start()
    yield srv
    srv.stop()


def _send(addr, method, path, body=None, sampled=False):
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=10)
    headers = {"X-Trace-Id": f"{time.monotonic_ns():016x}",
               "X-Trace-Sampled": "1"} if sampled else {}
    try:
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        assert resp.status == 200
        return resp.read()
    finally:
        conn.close()


def test_each_reader_reads_a_live_server_s_two_scrapes(volume_like):
    from seaweedfs_tpu.rpc.http_rpc import REQUEST_STAGES

    addr = volume_like.address
    # the counters are the process's: other tests' servers of this name
    # counted into the same rows
    timed0 = {m: REQUEST_STAGES.snapshot().get(
        ("volume", "*", m), {"timed_requests": 0})["timed_requests"]
        for m in ("GET", "POST")}
    before = cluster.scrape(addr)
    for i in range(6):
        _send(addr, "GET", f"/3,{i:x}0a0b0c0d", sampled=True)
        _send(addr, "POST", f"/3,{i:x}0a0b0c0d", body=b"p" * 1024,
              sampled=True)
        _send(addr, "GET", f"/3,{i:x}0a0b0c0d")      # counted, not timed
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:     # the last reply's flush, 30 ticks
        snap = REQUEST_STAGES.snapshot()
        if all(snap[("volume", "*", m)]["timed_requests"]
               - timed0[m] == 6 for m in ("GET", "POST")):
            break
        time.sleep(0.01)
    time.sleep(0.3)
    after = cluster.scrape(addr)
    ctx = {"prom": [before, after], "counts": {}}
    values = {name: prometheus.read(_spec(name)["reader"], ctx)
              for name in METRICS}
    for name, value in values.items():
        assert value is not None and value > 0, name
    # the three stages lie inside the request, and nothing else of weight
    parts = values["http_read_ms"] + values["http_handle_ms"] + \
        values["http_reply_ms"]
    assert parts <= values["http_request_ms"] <= parts + 5.0
    # the requests that were only counted are in no mean
    gets = {lab["requests"]: v for n, lab, v in after
            if n == "SeaweedFS_rpc_server_requests_total"
            and lab.get("service") == "volume" and lab.get("route") == "*"
            and lab.get("method") == "GET"}
    assert set(gets) == {"all", "timed"} and gets["all"] > gets["timed"]


@pytest.mark.parametrize("name", METRICS)
def test_a_parent_s_scrapes_read_as_nothing(name):
    """A parent's `/metrics` has none of the new families: the metric is
    left out of its line (and `volume_put_ms`, over a family the parent
    has, is the one of the seven it reports)."""
    reader = _spec(name)["reader"]
    old = [("SeaweedFS_volumeServer_request_seconds_sum",
            {"type": "read"}, 2.0),
           ("SeaweedFS_volumeServer_request_seconds_count",
            {"type": "read"}, 4.0),
           ("SeaweedFS_rpc_hop_seconds_sum", {"dst": "volume"}, 1.0)]
    assert prometheus.read(reader, {"prom": [old, old], "counts": {}}) is None
    assert prometheus.read(reader, {"prom": None, "counts": {}}) is None
    assert not set(NEW_FAMILIES) & {n.rsplit("_", 1)[0] for n, _, _ in old}
    if name == "volume_put_ms":
        write = [(f"SeaweedFS_volumeServer_request_seconds_{k}",
                  {"type": "write"}, v) for k, v in (("sum", 3.0),
                                                     ("count", 200.0))]
        assert prometheus.read(reader, {"prom": [old, old + write],
                                        "counts": {}}) == 15.0
    else:
        assert reader["family"] in NEW_FAMILIES
