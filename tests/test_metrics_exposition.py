"""Strict validation of the /metrics text exposition format.

A scrape that Prometheus silently mis-parses is worse than no scrape,
so this parses the exposition with its own strict mini-parser: HELP
before TYPE before samples for every family, label values escaped,
histogram buckets cumulative and monotone ending in le="+Inf", and
_count consistent with the +Inf bucket."""

import re

import pytest

from seaweedfs_tpu.stats import metrics as m

SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>.*)\})? '
    r'(?P<value>-?(?:\d+\.?\d*(?:e[+-]?\d+)?|\+Inf|-Inf|NaN))$')
LABEL_RE = re.compile(
    r'(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<val>(?:[^"\\]|\\.)*)"')


def parse_labels(raw):
    """Parse a label body strictly: every byte must belong to a
    key="value" pair (values may contain escaped quotes)."""
    if raw is None:
        return {}
    out = {}
    pos = 0
    while pos < len(raw):
        match = LABEL_RE.match(raw, pos)
        assert match, f"unparseable label body at {raw[pos:]!r}"
        out[match.group("key")] = match.group("val")
        pos = match.end()
        if pos < len(raw):
            assert raw[pos] == ",", f"bad label separator in {raw!r}"
            pos += 1
    return out


def strict_parse(text):
    """Returns {family: {"help":…, "type":…, "samples":[(name, labels,
    value)]}} enforcing HELP -> TYPE -> samples ordering per family."""
    assert text.endswith("\n"), "exposition must end with a newline"
    families = {}
    current = None
    for line in text.splitlines():
        assert line == line.strip(), f"stray whitespace: {line!r}"
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name not in families, f"duplicate HELP for {name}"
            current = families[name] = {
                "help": line, "type": None, "samples": []}
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert current is not None and name in families, \
                f"TYPE before HELP for {name}"
            assert families[name]["type"] is None, f"duplicate TYPE {name}"
            assert kind in ("counter", "gauge", "histogram"), kind
            families[name]["type"] = kind
        else:
            match = SAMPLE_RE.match(line)
            assert match, f"unparseable sample line: {line!r}"
            sname = match.group("name")
            base = re.sub(r"_(bucket|sum|count)$", "", sname)
            fam = families.get(sname) or families.get(base)
            assert fam is not None, f"sample {sname} with no HELP/TYPE"
            assert fam["type"] is not None, f"sample before TYPE: {sname}"
            fam["samples"].append(
                (sname, parse_labels(match.group("labels")),
                 float(match.group("value").replace("+Inf", "inf"))))
    return families


def check_histograms(families):
    checked = 0
    for name, fam in families.items():
        if fam["type"] != "histogram":
            continue
        series = {}
        for sname, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            rec = series.setdefault(key, {"buckets": [], "sum": None,
                                          "count": None})
            if sname.endswith("_bucket"):
                rec["buckets"].append((float(labels["le"]), value))
            elif sname.endswith("_sum"):
                rec["sum"] = value
            elif sname.endswith("_count"):
                rec["count"] = value
        for key, rec in series.items():
            les = [le for le, _ in rec["buckets"]]
            counts = [c for _, c in rec["buckets"]]
            assert les == sorted(les), f"{name}{key}: le out of order"
            assert les and les[-1] == float("inf"), \
                f"{name}{key}: missing le=+Inf"
            assert counts == sorted(counts), \
                f"{name}{key}: non-monotone cumulative buckets"
            assert rec["count"] == counts[-1], \
                f"{name}{key}: _count != +Inf bucket"
            assert rec["sum"] is not None and rec["sum"] >= 0
            checked += 1
    return checked


class TestExpositionFormat:
    def test_registry_exposition_is_strictly_parseable(self):
        # exercise every metric kind in a private registry
        reg = m.Registry()
        c = reg.counter("t_requests_total", "requests", ("code",))
        c.labels("200").inc()
        c.labels("404").inc(3)
        g = reg.gauge("t_temperature", "degrees")
        g.set(-3.5)
        h = reg.histogram("t_latency_seconds", "latency", ("op",))
        for v in (0.0002, 0.002, 0.02, 0.2, 2, 200):
            h.labels("read").observe(v)
        h.labels("write").observe(0.05)
        fams = strict_parse(reg.expose())
        assert fams["t_requests_total"]["type"] == "counter"
        assert fams["t_temperature"]["samples"][0][2] == -3.5
        assert check_histograms(fams) == 2
        read = [s for s in fams["t_latency_seconds"]["samples"]
                if s[0].endswith("_count") and s[1]["op"] == "read"]
        assert read[0][2] == 6

    def test_label_values_escaped(self):
        reg = m.Registry()
        c = reg.counter("t_weird_total", "weird labels", ("path",))
        c.labels('a"b\\c\nd').inc()
        text = reg.expose()
        assert '\\"' in text and "\\\\" in text and "\\n" in text
        fams = strict_parse(text)
        _, labels, value = fams["t_weird_total"]["samples"][0]
        assert labels["path"] == 'a\\"b\\\\c\\nd'  # wire form, re-escaped
        assert value == 1

    def test_labelless_counter_exposes_zero(self):
        reg = m.Registry()
        reg.counter("t_zero_total", "never incremented")
        fams = strict_parse(reg.expose())
        assert fams["t_zero_total"]["samples"] == [
            ("t_zero_total", {}, 0.0)]

    def test_global_registry_after_minicluster(self, tmp_path):
        """The real /metrics payload of a daemon that served traffic
        must survive the strict parser end to end."""
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.rpc.http_rpc import call
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "v0"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        try:
            a = call(master.address, "/dir/assign")
            call(a["url"], f"/{a['fid']}", raw=b"x" * 2048, method="POST")
            assert call(a["url"], f"/{a['fid']}") == b"x" * 2048
            payload = call(vs.store.url, "/metrics")
            if isinstance(payload, (bytes, bytearray)):
                payload = payload.decode()
        finally:
            vs.stop()
            master.stop()
        fams = strict_parse(payload)
        # the families the dashboards scrape must be present and typed
        assert fams["SeaweedFS_rpc_hop_seconds"]["type"] == "histogram"
        assert fams["SeaweedFS_volumeServer_request_seconds"][
            "type"] == "histogram"
        assert fams["SeaweedFS_rpc_inflight_requests"]["type"] == "gauge"
        # continuous-profiling families (profiling.py)
        assert fams["SeaweedFS_profiler_overhead_ratio"]["type"] == "gauge"
        assert fams["SeaweedFS_profiler_stacks"]["type"] == "gauge"
        assert fams["SeaweedFS_profiler_route_samples_total"][
            "type"] == "counter"
        assert fams["SeaweedFS_volumeServer_ec_kernel_dispatch_ready"
                    "_seconds"]["type"] == "histogram"
        # XLA's cost-analysis gauges are gone (read by nothing); the
        # heartbeat loop's own two instruments are exported instead
        assert not [f for f in fams if f.startswith(
            "SeaweedFS_volumeServer_ec_kernel_flops")
            or f.startswith("SeaweedFS_volumeServer_ec_kernel_bytes")]
        assert fams["SeaweedFS_volumeServer_heartbeat_failures_total"][
            "type"] == "counter"
        assert fams["SeaweedFS_volumeServer_heartbeat_max_gap_seconds"][
            "type"] == "gauge"
        assert fams["SeaweedFS_volumeServer_device_pool_hwm_bytes"][
            "type"] == "gauge"
        assert fams["SeaweedFS_volumeServer_device_pool_hwm_seconds"][
            "type"] == "gauge"
        # the self-measured duty cycle is a sane ratio
        overhead = fams["SeaweedFS_profiler_overhead_ratio"]["samples"]
        assert len(overhead) == 1 and 0.0 <= overhead[0][2] < 1.0
        assert check_histograms(fams) >= 2
        # the hop histogram observed this test's calls
        hops = [s for s in fams["SeaweedFS_rpc_hop_seconds"]["samples"]
                if s[0].endswith("_count")]
        assert sum(v for _, _, v in hops) >= 2


class TestMergeExpositions:
    """Edge cases of the prefork fleet-merge: the leader's scrape loop
    feeds the merged text straight into the health-plane TSDB, so a
    merge that emits duplicate family blocks or shuffles histogram
    buckets would corrupt every downstream SLO."""

    W0 = ("# HELP SeaweedFS_demo_total demo counter\n"
          "# TYPE SeaweedFS_demo_total counter\n"
          "SeaweedFS_demo_total 3\n")

    def test_conflicting_help_first_wins_single_block(self):
        w1 = self.W0.replace("demo counter", "OTHER help text")
        merged = m.merge_expositions([("0", self.W0), ("1", w1)])
        fams = strict_parse(merged)  # rejects duplicate HELP blocks
        fam = fams["SeaweedFS_demo_total"]
        assert "demo counter" in fam["help"]
        assert "OTHER" not in merged
        # both workers' samples grouped under the single header
        workers = {s[1]["worker"] for s in fam["samples"]}
        assert workers == {"0", "1"}

    def test_absent_worker_part_mid_read(self):
        """A worker that died mid-scrape contributes an empty (or
        truncated, headerless) part; the merge must not invent
        families or drop the healthy workers' samples."""
        merged = m.merge_expositions(
            [("0", self.W0), ("1", ""), ("2", self.W0)])
        fams = strict_parse(merged)
        samples = fams["SeaweedFS_demo_total"]["samples"]
        assert {s[1]["worker"] for s in samples} == {"0", "2"}
        assert sum(s[2] for s in samples) == 6.0

    def test_histogram_bucket_merge_ordering(self):
        """Per-worker le-buckets must stay contiguous per series (the
        worker label separates the series); the merged text must still
        satisfy the strict cumulative-monotone histogram checks."""
        hist = ("# HELP SeaweedFS_demo_seconds demo latency\n"
                "# TYPE SeaweedFS_demo_seconds histogram\n"
                'SeaweedFS_demo_seconds_bucket{le="0.1"} %d\n'
                'SeaweedFS_demo_seconds_bucket{le="1"} %d\n'
                'SeaweedFS_demo_seconds_bucket{le="+Inf"} %d\n'
                "SeaweedFS_demo_seconds_sum %f\n"
                "SeaweedFS_demo_seconds_count %d\n")
        merged = m.merge_expositions([
            ("0", hist % (1, 2, 3, 1.5, 3)),
            ("1", hist % (4, 4, 9, 8.0, 9)),
        ])
        fams = strict_parse(merged)
        assert check_histograms(fams) == 2  # one series per worker
        # and the health-plane parser agrees on totals
        from seaweedfs_tpu.stats import tsdb

        types, samples = tsdb.parse_exposition(merged)
        assert types["SeaweedFS_demo_seconds"] == "histogram"
        counts = [v for n, labels, v in samples
                  if n == "SeaweedFS_demo_seconds_count"]
        assert sorted(counts) == [3.0, 9.0]
