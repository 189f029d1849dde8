"""Workload analytics plane: mergeable access sketches, per-daemon
recorders, the leader's /cluster/usage fold, heat-driven placement
hints, and the read cache's sketch-backed promotion heat.

The sketch tests pin the algebra the whole plane rests on (merge
associativity/commutativity, Space-Saving's overestimate invariant,
the HLL error bound, canonical serialization across a real process
boundary); the integration tests pin the plumbing — volume servers
ride heartbeats, filer/S3 ride the health-plane scrape, tenants come
from the QoS attribution, and a cold volume becomes an advisory
tier.move under WEED_HEAT_TIER=1."""

import collections
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from seaweedfs_tpu.loadgen.generators import ZipfPopularity
from seaweedfs_tpu.rpc.http_rpc import call
from seaweedfs_tpu.stats import access
from seaweedfs_tpu.stats import events as events_mod
from seaweedfs_tpu.stats import sketch as sketch_mod
from seaweedfs_tpu.stats.sketch import (HyperLogLog, LogQuantile,
                                        SpaceSaving)


def wait_for(pred, timeout=10.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def zipf_keys(n_draws=20000, n_objects=2000, s=1.2, seed=7):
    z = ZipfPopularity(n_objects, s=s, seed=seed)
    return [f"k{z.sample(i):05d}" for i in range(n_draws)]


# ---------------------------------------------------------------------------
# Space-Saving
# ---------------------------------------------------------------------------

class TestSpaceSaving:
    def test_exact_under_capacity(self):
        sk = SpaceSaving(capacity=64)
        for i in range(10):
            for _ in range(i + 1):
                sk.offer(f"k{i}")
        assert len(sk) == 10
        assert sk.estimate("k9") == 10.0
        assert sk.error("k9") == 0.0
        assert sk.top(1) == [("k9", 10.0, 0.0)]
        assert sk.total == sum(range(1, 11))

    def test_overestimate_invariant_on_zipfian_stream(self):
        """The classic Space-Saving guarantees, on a realistic skewed
        stream (the loadgen zipf generator is the fixture): every
        tracked estimate is an upper bound, estimate-error a lower
        bound, and the true head keys are never lost."""
        keys = zipf_keys()
        true = collections.Counter(keys)
        sk = SpaceSaving(capacity=256)
        for k in keys:
            sk.offer(k)
        assert len(sk) <= 256
        for key, est, err in sk.top(0):
            assert est >= true[key] - 1e-9
            assert est - err <= true[key] + 1e-9
        head = [k for k, _ in true.most_common(10)]
        tracked = [k for k, _, _ in sk.top(30)]
        assert set(head) <= set(tracked)

    def test_merge_commutative_even_with_truncation(self):
        keys = zipf_keys(n_draws=6000)
        a = SpaceSaving(32)
        b = SpaceSaving(32)
        for i, k in enumerate(keys):
            (a if i % 2 else b).offer(k)
        ad, bd = a.to_dict(), b.to_dict()
        ab = SpaceSaving.from_dict(ad).merge(
            SpaceSaving.from_dict(bd)).to_dict()
        ba = SpaceSaving.from_dict(bd).merge(
            SpaceSaving.from_dict(ad)).to_dict()
        assert ab == ba

    def test_merge_associative_when_union_fits(self):
        keys = zipf_keys(n_draws=6000, n_objects=300)
        parts = [SpaceSaving(1024) for _ in range(3)]
        for i, k in enumerate(keys):
            parts[i % 3].offer(k)
        d = [p.to_dict() for p in parts]

        def build(i):
            return SpaceSaving.from_dict(d[i])

        left = build(0).merge(build(1)).merge(build(2)).to_dict()
        right = build(0).merge(build(1).merge(build(2))).to_dict()
        assert left == right
        # and the union equals the single-stream sketch exactly
        one = SpaceSaving(1024)
        for k in keys:
            one.offer(k)
        assert left["counts"] == one.to_dict()["counts"]

    def test_eviction_keeps_heavy_keys(self):
        sk = SpaceSaving(capacity=8)
        for _ in range(100):
            sk.offer("heavy")
        for i in range(500):
            sk.offer(f"cold{i}")
        assert "heavy" in sk.counts
        assert sk.estimate("heavy") >= 100.0

    def test_scale_decays_and_drops(self):
        sk = SpaceSaving(capacity=16)
        for _ in range(8):
            sk.offer("hot")
        sk.offer("barely", 0.001)
        sk.scale(0.5)
        assert sk.estimate("hot") == 4.0
        assert "barely" not in sk.counts     # below the drop floor
        assert sk.total == pytest.approx(8.001 * 0.5)
        # the heap survives decay: eviction still picks the minimum
        for i in range(16):
            sk.offer(f"f{i}")
        sk.offer("newcomer")
        assert sk.estimate("hot") >= 4.0


# ---------------------------------------------------------------------------
# HyperLogLog
# ---------------------------------------------------------------------------

class TestHyperLogLog:
    def test_error_bound(self):
        hll = HyperLogLog(p=10)           # ~3.2% standard error
        for i in range(5000):
            hll.add(f"key-{i}")
        assert hll.estimate() == pytest.approx(5000, rel=0.10)

    def test_small_cardinality_linear_counting(self):
        hll = HyperLogLog(p=10)
        for i in range(50):
            hll.add(f"k{i}")
        assert hll.estimate() == pytest.approx(50, rel=0.10)

    def test_merge_equals_union_and_is_idempotent(self):
        full, a, b = HyperLogLog(), HyperLogLog(), HyperLogLog()
        for i in range(4000):
            key = f"key-{i}"
            full.add(key)
            (a if i % 2 else b).add(key)
        a.merge(b)
        assert a.registers == full.registers
        before = bytes(a.registers)
        a.merge(b)                        # re-merge changes nothing
        assert bytes(a.registers) == before

    def test_precision_mismatch_rejected(self):
        with pytest.raises(ValueError):
            HyperLogLog(p=10).merge(HyperLogLog(p=12))


# ---------------------------------------------------------------------------
# LogQuantile
# ---------------------------------------------------------------------------

class TestLogQuantile:
    def test_relative_error_bound(self):
        lq = LogQuantile(alpha=0.01)
        for v in range(1, 10001):
            lq.observe(float(v))
        for q in (0.5, 0.9, 0.99):
            exact = q * 10000
            assert lq.quantile(q) == pytest.approx(exact, rel=0.03)
        assert lq.mean() == pytest.approx(5000.5)

    def test_merge_is_exact(self):
        full, a, b = LogQuantile(), LogQuantile(), LogQuantile()
        # dyadic values: float sums are exact in any order, so the
        # merged wire form must match the single-stream one bit for bit
        vals = [0.25 * (i + 1) for i in range(500)] + [0.0, 0.0]
        for i, v in enumerate(vals):
            full.observe(v)
            (a if i % 2 else b).observe(v)
        assert a.merge(b).to_dict() == full.to_dict()

    def test_weighted_observe(self):
        lq = LogQuantile()
        lq.observe(10.0, weight=4.0)
        assert lq.count == 4.0
        assert lq.sum == 40.0


# ---------------------------------------------------------------------------
# canonical serialization
# ---------------------------------------------------------------------------

def _sample_sketches():
    ss = SpaceSaving(32)
    hll = HyperLogLog()
    lq = LogQuantile()
    for i, k in enumerate(zipf_keys(n_draws=3000, n_objects=200)):
        ss.offer(k)
        hll.add(k)
        lq.observe(0.001 * (i + 1))
    return ss, hll, lq


class TestSerialization:
    def test_json_round_trip_all_kinds(self):
        for sk in _sample_sketches():
            d = sk.to_dict()
            wire = json.loads(json.dumps(d))
            back = sketch_mod.from_dict(wire)
            assert type(back) is type(sk)
            assert back.to_dict() == d

    def test_from_dict_polymorphic_dispatch(self):
        assert sketch_mod.from_dict(None) is None
        assert sketch_mod.from_dict({"kind": "nope"}) is None

    def test_merge_across_subprocess_boundary(self):
        """Two recorders' summaries survive a real process boundary:
        a fresh interpreter merges the JSON wire forms and must land
        on byte-identical sketch state to the in-process merge."""
        recs = []
        for node in ("vs-a", "vs-b"):
            rec = access.AccessRecorder(node=node, now=lambda: 1000.0)
            for i, k in enumerate(
                    zipf_keys(n_draws=2000, n_objects=150,
                              seed=hash(node) % 997)):
                rec.record("read", fid=k, volume=1 + i % 3, nbytes=256,
                           tenant=f"t{i % 5}", latency_s=0.001)
            recs.append(rec)
        parts = [r.summary() for r in recs]
        local = access.merge_summaries(parts)
        code = (
            "import json, sys\n"
            "from seaweedfs_tpu.stats import access\n"
            "m = access.merge_summaries(json.load(sys.stdin))\n"
            "print(json.dumps({'reads': m['totals']['reads'],\n"
            "                  'hot': m['hot'].to_dict(),\n"
            "                  'distinct': m['distinct'].to_dict()}))\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code],
                             input=json.dumps(parts), text=True,
                             capture_output=True, env=env, timeout=120)
        assert out.returncode == 0, out.stderr
        remote = json.loads(out.stdout)
        assert remote["reads"] == pytest.approx(local["totals"]["reads"])
        assert remote["hot"] == local["hot"].to_dict()
        assert remote["distinct"] == local["distinct"].to_dict()


# ---------------------------------------------------------------------------
# AccessRecorder
# ---------------------------------------------------------------------------

class TestAccessRecorder:
    def test_memory_bounded_by_max_keys(self, monkeypatch):
        monkeypatch.setenv("WEED_HEAT_MAX_KEYS", "64")
        rec = access.AccessRecorder(node="vs")
        for i in range(5000):
            rec.record("read", fid=f"7,{i:08x}", volume=7, nbytes=512)
        assert rec.tracked_keys() <= 64
        assert rec.memory_bytes() < 100_000
        s = rec.summary()
        assert len(s["hot"]["counts"]) <= 64
        # the cardinality estimate still sees every distinct key
        assert HyperLogLog.from_dict(
            s["distinct"]).estimate() == pytest.approx(5000, rel=0.10)

    def test_epoch_decay(self, monkeypatch):
        monkeypatch.setenv("WEED_HEAT_EPOCH_S", "60")
        monkeypatch.setenv("WEED_HEAT_DECAY", "0.5")
        clock = [1000.0]
        rec = access.AccessRecorder(node="vs", now=lambda: clock[0])
        for _ in range(100):
            rec.record("read", fid="1,aa", volume=1, nbytes=100)
        assert rec.summary()["reads"] == pytest.approx(100.0)
        clock[0] += 60.0
        s = rec.summary()
        assert s["reads"] == pytest.approx(50.0)
        assert s["bytes_read"] == pytest.approx(5000.0)
        assert SpaceSaving.from_dict(
            s["hot"]).estimate("1,aa") == pytest.approx(50.0)
        assert s["records"] == 100    # the raw record count never decays
        clock[0] += 120.0             # two more epochs at once
        assert rec.summary()["reads"] == pytest.approx(12.5)

    def test_disabled_by_knob(self, monkeypatch):
        monkeypatch.setenv("WEED_HEAT", "0")
        rec = access.AccessRecorder(node="vs")
        assert not rec.enabled
        rec.record("read", fid="1,aa", volume=1, nbytes=100)
        assert rec.records == 0
        assert rec.summary()["reads"] == 0.0

    def test_entity_accounting_per_op(self):
        rec = access.AccessRecorder(node="s3", now=lambda: 1000.0)
        rec.record("read", collection="photos", tenant="alice",
                   fid="b/k1", nbytes=300)
        rec.record("write", collection="photos", tenant="alice",
                   fid="b/k2", nbytes=700)
        s = rec.summary()
        alice = s["tenants"]["alice"]
        assert alice["ops"] == {"read": 1.0, "write": 1.0}
        assert alice["bytes"] == {"read": 300.0, "write": 700.0}
        assert s["collections"]["photos"]["ops"]["read"] == 1.0

    def test_quantile_sampling_preserves_total_weight(self):
        rec = access.AccessRecorder(node="vs", now=lambda: 1000.0)
        for _ in range(8):
            rec.record("read", fid="1,aa", nbytes=100, latency_s=0.002)
        # 1-in-4 systematic sample at 4x weight: the sketch's mass
        # matches the stream even though only 2 records were observed
        assert rec.sizes.count == pytest.approx(8.0)
        assert rec.latency["default"].count == pytest.approx(8.0)


# ---------------------------------------------------------------------------
# UsageAggregator
# ---------------------------------------------------------------------------

def _traffic_recorder(node, hot_fid="1,aa", hot_reads=200, spread=40):
    rec = access.AccessRecorder(node=node, now=lambda: 1000.0)
    for _ in range(hot_reads):
        rec.record("read", fid=hot_fid, volume=1, nbytes=100,
                   tenant="alice")
    for i in range(spread):
        rec.record("read", fid=f"2,{i:04x}", volume=2, nbytes=100,
                    tenant="bob")
    return rec


class TestUsageAggregator:
    def test_replace_not_accumulate(self):
        agg = access.UsageAggregator(now=lambda: 1000.0)
        s = _traffic_recorder("vs-a").summary()
        agg.ingest("vs-a", s)
        agg.ingest("vs-a", s)    # a re-delivered summary is idempotent
        u = agg.usage()
        assert u["nodes"] == ["vs-a"]
        assert u["totals"]["reads"] == pytest.approx(240.0)

    def test_merge_across_nodes(self):
        agg = access.UsageAggregator(now=lambda: 1000.0)
        agg.ingest("vs-a", _traffic_recorder("vs-a").summary())
        agg.ingest("vs-b", _traffic_recorder("vs-b").summary())
        u = agg.usage(topk=5)
        assert u["nodes"] == ["vs-a", "vs-b"]
        assert u["totals"]["reads"] == pytest.approx(480.0)
        assert u["top_keys"][0]["fid"] == "1,aa"
        assert u["top_keys"][0]["reads"] == pytest.approx(400.0)
        assert u["top_keys"][0]["share"] == pytest.approx(400 / 480,
                                                          abs=0.01)
        assert u["volumes"]["1"] == pytest.approx(400.0)
        alice = u["tenants"]["alice"]
        assert alice["ops"]["read"] == pytest.approx(400.0)
        assert alice["bytes"]["read"] == pytest.approx(40000.0)

    def test_stale_parts_age_out(self, monkeypatch):
        monkeypatch.setenv("WEED_USAGE_MAX_AGE_S", "10")
        clock = [1000.0]
        agg = access.UsageAggregator(now=lambda: clock[0])
        agg.ingest("vs-a", _traffic_recorder("vs-a").summary())  # ts=1000
        assert agg.usage()["nodes"] == ["vs-a"]
        clock[0] = 1011.0
        u = agg.usage()
        assert u["nodes"] == []
        assert u["totals"]["reads"] == 0.0

    def test_hot_key_event_fires_once_per_epoch(self, monkeypatch):
        monkeypatch.setenv("WEED_HEAT_HOT_SHARE", "0.25")
        monkeypatch.setenv("WEED_HEAT_MIN_READS", "100")
        agg = access.UsageAggregator(now=lambda: 1000.0)
        agg.ingest("vs-a", _traffic_recorder("vs-a").summary())
        ev = agg.maybe_emit_hot_key(node="master-1")
        assert ev is not None
        assert ev["kind"] == events_mod.HOT_KEY
        assert ev["detail"]["fid"] == "1,aa"
        assert ev["detail"]["share"] >= 0.25
        # deduped: the same hot fid does not spam the journal
        assert agg.maybe_emit_hot_key(node="master-1") is None

    def test_no_event_below_share_or_volume_gates(self, monkeypatch):
        monkeypatch.setenv("WEED_HEAT_HOT_SHARE", "0.95")
        agg = access.UsageAggregator(now=lambda: 1000.0)
        agg.ingest("vs-a", _traffic_recorder("vs-a").summary())
        assert agg.maybe_emit_hot_key(node="m") is None   # share 0.83
        monkeypatch.setenv("WEED_HEAT_HOT_SHARE", "0.25")
        monkeypatch.setenv("WEED_HEAT_MIN_READS", "100000")
        assert agg.maybe_emit_hot_key(node="m") is None   # too few reads


# ---------------------------------------------------------------------------
# read cache: sketch-backed promotion heat (regression)
# ---------------------------------------------------------------------------

class TestReadCacheHeat:
    def test_hot_fid_promotion_survives_cold_scan(self, monkeypatch):
        """Regression for the clear-all heat wipe: a fid with
        accumulated (decayed) heat must keep it through a scan of
        more distinct cold fids than the heat table can hold — the
        sketch evicts minimum counters, never the whole table."""
        from seaweedfs_tpu.cache import read_cache as rc_mod

        monkeypatch.setenv("WEED_HEAT_MAX_KEYS", "64")
        clock = [1000.0]
        monkeypatch.setattr(rc_mod.time, "monotonic", lambda: clock[0])
        c = rc_mod.TieredReadCache(mem_bytes=1 << 20, hbm_bytes=1 << 20)
        if c.hbm is None:
            pytest.skip("no HBM-capable backend")
        try:
            hot = "5,deadbeef"
            c.put(hot, b"h" * 64)
            assert c.get(hot) is not None          # heat 1
            clock[0] += 70.0                       # one epoch: decay 0.5
            assert c.get(hot) is not None          # heat 0.5 + 1 = 1.5
            assert c._heat.estimate(hot) == pytest.approx(1.5)
            # cold scan: 3x the table capacity in distinct fids, each
            # read once — the old dict-based heat cleared wholesale
            # under this pressure, losing the hot fid's 1.5
            for i in range(200):
                fid = f"9,{i:08x}"
                c.put(fid, b"c" * 64)
                c.get(fid)
            assert c._heat.estimate(hot) == pytest.approx(1.5)
            assert hot not in c.hbm._keys          # not promoted yet
            assert c.get(hot) is not None          # 2.5 >= promote gate
            assert hot in c.hbm._keys
            # promoted fids retire their counter (no re-put churn)
            assert c._heat.estimate(hot) == 0.0
        finally:
            c.close()

    def test_clear_resets_heat_but_keeps_capacity(self, monkeypatch):
        from seaweedfs_tpu.cache import read_cache as rc_mod

        monkeypatch.setenv("WEED_HEAT_MAX_KEYS", "64")
        c = rc_mod.TieredReadCache(mem_bytes=1 << 20)
        try:
            c.put("1,aa", b"x")
            c.get("1,aa")
            c.clear()
            assert c._heat.estimate("1,aa") == 0.0
            assert c._heat.capacity == 64
        finally:
            c.close()


# ---------------------------------------------------------------------------
# tenant attribution (QoS key -> access records)
# ---------------------------------------------------------------------------

class TestTenantAttribution:
    @pytest.fixture
    def auth_stack(self, tmp_path):
        from seaweedfs_tpu.filer.server import FilerServer
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.s3api.auth import Identity
        from seaweedfs_tpu.s3api.server import S3ApiServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "vs0"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        filer = FilerServer(master.address, port=0, chunk_size=1024)
        filer.start()
        s3 = S3ApiServer(filer, port=0, identities=[
            Identity(name="admin", access_key="AKID", secret_key="SK")])
        s3.start()
        yield s3, filer
        s3.stop()
        filer.stop()
        vs.stop()
        master.stop()

    def test_sigv4_identity_is_the_tenant_at_s3_and_filer(
            self, auth_stack):
        """The same sigv4 access key must show up as the tenant in the
        S3 gateway's records AND in the filer's chunk records for the
        same request — one attribution across both doors."""
        from test_s3 import sigv4_request

        s3, filer = auth_stack
        assert sigv4_request(s3.address, "PUT", "/b",
                             access_key="AKID", secret_key="SK")[0] == 200
        payload = b"p" * 6000          # above INLINE_LIMIT: 6 chunks
        assert sigv4_request(s3.address, "PUT", "/b/k",
                             body=payload, access_key="AKID",
                             secret_key="SK")[0] == 200
        status, _, body = sigv4_request(s3.address, "GET", "/b/k",
                                        access_key="AKID",
                                        secret_key="SK")
        assert status == 200 and body == payload
        s3_tenants = s3.access_recorder.summary()["tenants"]
        assert "AKID" in s3_tenants
        assert s3_tenants["AKID"]["ops"].get("read", 0) >= 1
        assert s3_tenants["AKID"]["ops"].get("write", 0) >= 1
        filer_tenants = filer.access_recorder.summary()["tenants"]
        assert "AKID" in filer_tenants
        assert filer_tenants["AKID"]["ops"].get("chunk", 0) >= 1

    def test_filer_honors_qos_tenant_header(self, auth_stack):
        _, filer = auth_stack
        payload = b"d" * 3000
        call(filer.address, "/tenants/x.bin", raw=payload, method="POST")
        req = urllib.request.Request(
            f"http://{filer.address}/tenants/x.bin",
            headers={"X-QoS-Tenant": "team-red"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.read() == payload
        tenants = filer.access_recorder.summary()["tenants"]
        assert "team-red" in tenants
        assert tenants["team-red"]["ops"].get("chunk", 0) >= 1


# ---------------------------------------------------------------------------
# /cluster/usage end to end
# ---------------------------------------------------------------------------

class TestClusterUsage:
    def test_usage_assembled_from_all_daemon_kinds(self, tmp_path,
                                                   monkeypatch):
        """>=2 volume servers (heartbeat path) + filer + s3 gateway
        (scrape path) all land in the leader's merged view; the
        assembled sketch stays bounded by WEED_HEAT_MAX_KEYS even
        though the workload touches more distinct keys than that."""
        from seaweedfs_tpu.filer.server import FilerServer
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.s3api.server import S3ApiServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        monkeypatch.setenv("WEED_HEAT_MAX_KEYS", "128")
        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        vols = []
        for i in range(2):
            d = tmp_path / f"vs{i}"
            d.mkdir()
            vs = VolumeServer([str(d)], master.address, port=0,
                              pulse_seconds=0.2)
            vs.start()
            vs.heartbeat_once()
            vols.append(vs)
        filer = FilerServer(master.address, port=0, chunk_size=1024)
        filer.start()
        s3 = S3ApiServer(filer, port=0)
        s3.start()
        try:
            from test_s3 import sigv4_request

            assert sigv4_request(s3.address, "PUT", "/b")[0] == 200
            for i in range(40):
                assert sigv4_request(
                    s3.address, "PUT", f"/b/obj{i:03d}",
                    body=bytes([i % 251]) * 3000)[0] == 200
            for _ in range(3):          # a skewed read pass
                assert sigv4_request(s3.address, "GET", "/b/obj000")[0] \
                    == 200
            for i in range(40):
                assert sigv4_request(s3.address, "GET",
                                     f"/b/obj{i:03d}")[0] == 200
            for vs in vols:
                vs.heartbeat_once()
            assert wait_for(lambda: len(master._members) >= 2), \
                "filer/s3 never registered with the master"
            master.health.scrape_round()

            u = call(master.address, "/cluster/usage")
            nodes = u["nodes"]
            assert filer.address in nodes
            assert s3.address in nodes
            vs_nodes = [n for n in nodes
                        if n not in (filer.address, s3.address)
                        and not n.startswith("master")]
            assert len(vs_nodes) >= 2, nodes
            assert u["totals"]["reads"] > 0
            assert u["totals"]["writes"] > 0
            assert u["totals"]["distinct_keys"] > 0
            assert u["top_keys"], "merged view lost the hot keys"
            assert u["tenants"], "merged view lost the tenants"
            # bounded state: no daemon ships more keys than the knob,
            # and the wire form carries sketches, never raw key streams
            for rec in (filer.access_recorder, s3.access_recorder,
                        *(vs.access_recorder for vs in vols)):
                assert rec.tracked_keys() <= 128
            for part in master.health.usage.parts.values():
                assert len(part["hot"]["counts"]) <= 128
        finally:
            s3.stop()
            filer.stop()
            for vs in vols:
                vs.stop()
            master.stop()


# ---------------------------------------------------------------------------
# temperature detector -> advisory tier.move
# ---------------------------------------------------------------------------

class TestTemperature:
    SNAP = {"volumes": [
        {"id": 1, "collection": "", "size": 4096},
        {"id": 2, "collection": "photos", "size": 8192},
        {"id": 3, "collection": "", "size": 0},       # empty: skip
    ]}

    def _usage(self, vol_reads):
        total = sum(vol_reads.values())
        return {"volumes": {str(k): v for k, v in vol_reads.items()},
                "totals": {"reads": total}}

    def test_cold_volume_flagged_hot_volume_not(self):
        from seaweedfs_tpu.maintenance import detectors

        specs = detectors.scan_temperature(
            self.SNAP, self._usage({1: 50.0, 2: 0.2}), enabled=True)
        assert [s["volume"] for s in specs] == [2]
        (spec,) = specs
        assert spec["type"] == "tier.move"
        assert spec["collection"] == "photos"
        assert spec["params"]["advisory"] is True
        assert spec["params"]["reads"] == pytest.approx(0.2)

    def test_disabled_by_default_and_gated_on_traffic(self, monkeypatch):
        from seaweedfs_tpu.maintenance import detectors

        monkeypatch.delenv("WEED_HEAT_TIER", raising=False)
        assert detectors.scan_temperature(
            self.SNAP, self._usage({1: 50.0})) == []
        # no reads anywhere -> no temperature signal, no hints
        assert detectors.scan_temperature(
            self.SNAP, self._usage({}), enabled=True) == []
        assert detectors.scan_temperature(self.SNAP, None,
                                          enabled=True) == []

    def test_hint_budget(self):
        from seaweedfs_tpu.maintenance import detectors

        snap = {"volumes": [{"id": i, "collection": "", "size": 100}
                            for i in range(1, 12)]}
        specs = detectors.scan_temperature(
            snap, {"volumes": {"1": 9.0}, "totals": {"reads": 9.0}},
            enabled=True, cold_reads=1.0, max_hints=4)
        assert len(specs) == 4
        # coldest first, deterministic
        assert [s["volume"] for s in specs] == [2, 3, 4, 5]

    def test_cold_volume_enqueues_tier_move_via_curator(
            self, tmp_path, monkeypatch):
        """Live loop: WEED_HEAT_TIER=1, a volume holding data with no
        reads in the merged usage view -> the curator's next tick
        enqueues an advisory tier.move and journals it."""
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        monkeypatch.setenv("WEED_MAINT_WORKER", "0")
        monkeypatch.setenv("WEED_MAINT_INTERVAL", "3600")
        monkeypatch.setenv("WEED_HEAT_TIER", "1")
        # the budget is coldest-first: raise it so the written volume
        # cannot fall off the end behind its empty pre-grown siblings
        monkeypatch.setenv("WEED_HEAT_TIER_MAX_HINTS", "16")
        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "vs0"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        try:
            a = call(master.address, "/dir/assign")
            call(a["url"], f"/{a['fid']}", raw=b"x" * 2048, method="POST")
            vs.heartbeat_once()
            vid = int(a["fid"].split(",")[0])
            # fleet traffic exists, but none of it touches `vid`
            rec = access.AccessRecorder(node="vs-x")
            for _ in range(50):
                rec.record("read", fid=f"{vid + 1000},aa",
                           volume=vid + 1000, nbytes=100)
            master.health.usage.ingest(vs.address, rec.summary())
            seq0 = events_mod.JOURNAL.seq
            master.curator.tick()
            jobs = [j for j in master.curator.queue.jobs()
                    if j["type"] == "tier.move"]
            assert jobs, "cold volume produced no tier.move hint"
            # every pre-grown volume is cold here; the written one must
            # be among the flagged (the hint budget is id-ordered)
            by_vol = {j["volume"]: j for j in jobs}
            assert vid in by_vol, jobs
            assert by_vol[vid]["params"]["advisory"] is True
            kinds = [e["kind"] for e in events_mod.JOURNAL.since(seq0)]
            assert events_mod.TIER_MOVE in kinds
        finally:
            vs.stop()
            master.stop()


# ---------------------------------------------------------------------------
# perf smoke: the recorder on the read path counts, and stays bounded
# ---------------------------------------------------------------------------

@pytest.mark.perf_smoke
class TestRecorderOnReadPath:
    def test_every_smallfile_read_recorded_once_and_sketch_bounded(
            self, tmp_path):
        """A live small-file storm is counted request for request by
        the volume server's own recorder, and a recorder fed a 15,000-
        record zipfian stream over 200 keys holds every record in its
        totals while its tables stay inside WEED_HEAT_MAX_KEYS.  What a
        record() costs beside a read is not measured here."""
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "vs0"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        try:
            fids = []
            for i in range(30):
                a = call(master.address, "/dir/assign")
                call(a["url"], f"/{a['fid']}", raw=os.urandom(2048),
                     method="POST")
                fids.append((a["url"], a["fid"]))
            n_reads = 330
            for i in range(n_reads):
                url, fid = fids[i % len(fids)]
                assert len(call(url, f"/{fid}")) == 2048
            s = vs.access_recorder.summary()
            assert s["records"] == len(fids) + n_reads
            assert (s["writes"], s["reads"]) == (len(fids), n_reads)
            assert s["bytes_read"] == n_reads * 2048
            hot = SpaceSaving.from_dict(s["hot"])
            assert {hot.estimate(fid) for _, fid in fids} == {
                n_reads / len(fids)}       # heat is reads: eleven each
        finally:
            vs.stop()
            master.stop()

        rec = access.AccessRecorder(node="vs")
        pool = [f"7,{i:08x}" for i in range(200)]
        z = ZipfPopularity(len(pool), s=1.1, seed=3)
        for i in range(15000):
            rec.record("read", fid=pool[z.sample(i)], volume=7,
                       nbytes=2048, tenant=f"t{i % 16}", latency_s=5e-4,
                       qos_class="standard")
        s = rec.summary()
        assert s["records"] == s["reads"] == 15000
        assert s["bytes_read"] == 15000 * 2048
        assert rec.tracked_keys() <= 200 + 16 + 2   # fids, tenants, volume
        assert len(s["tenants"]) == 16
        assert sum(t["ops"]["read"] for t in s["tenants"].values()) == 15000


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
