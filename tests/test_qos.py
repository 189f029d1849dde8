"""Cluster QoS: classification, weighted-fair admission, tenant
buckets, collection quotas, and priority device lanes.

The scheduler and bucket tests run on injected fake clocks (the
rpc/policy.py convention) so tier-1 stays deterministic with zero
sleeps; the chaos-style isolation test at the bottom drives a live
mini-cluster through a degraded-read storm while a device-batched deep
scrub grinds concurrently."""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import qos
from seaweedfs_tpu.qos import quota as qos_quota
from seaweedfs_tpu.qos.admission import (AdmissionGate, DrrQueue,
                                         TenantBuckets, TokenBucket,
                                         _Waiter)
from seaweedfs_tpu.qos.lanes import DeviceLanes, LANES
from seaweedfs_tpu.rpc.http_rpc import RpcError, RpcServer, call

BG = qos.BACKGROUND
INT = qos.INTERACTIVE
STD = qos.STANDARD


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


@pytest.fixture(autouse=True)
def _fresh_qos_counters():
    LANES.reset()
    yield
    LANES.reset()


class TestTokenBucket:
    def test_burst_then_refill_on_fake_clock(self):
        clk = FakeClock()
        b = TokenBucket(rate=2.0, burst=2.0, now=clk)
        assert b.try_take() and b.try_take()
        assert not b.try_take()
        assert b.denied == 1
        clk.advance(0.5)  # 1 token back at 2/s
        assert b.try_take()
        assert not b.try_take()
        clk.advance(10.0)  # refill clamps at burst
        assert b.try_take() and b.try_take() and not b.try_take()

    def test_rate_zero_is_unlimited(self):
        b = TokenBucket(rate=0.0, burst=1.0, now=FakeClock())
        assert all(b.try_take() for _ in range(100))
        assert b.denied == 0 and b.taken == 100


class TestDrrQueue:
    def test_weighted_round_shares(self):
        q = DrrQueue(weights={INT: 4, STD: 2, BG: 1})
        for i in range(8):
            for cls in (BG, STD, INT):  # arrival order must not matter
                q.push(cls, f"{cls}{i}")
        # one full DRR round under backlog: 4 interactive, 2 standard,
        # 1 background — and the next round repeats the same shape
        for _ in range(2):
            got = [q.pop() for _ in range(7)]
            assert [g[:3] for g in got] == ["int"] * 4 + ["sta"] * 2 \
                + ["bac"]

    def test_idle_class_does_not_bank_deficit(self):
        q = DrrQueue(weights={INT: 4, STD: 2, BG: 1})
        q.push(BG, "b0")
        assert q.pop() == "b0"
        assert q.deficit[BG] == 0.0  # drained queue resets its deficit
        assert q.pop() is None and len(q) == 0

    def test_depths(self):
        q = DrrQueue()
        q.push(INT, "a")
        q.push(INT, "b")
        q.push(BG, "c")
        assert q.depth(INT) == 2 and q.depth(BG) == 1 and len(q) == 3


class TestTenantBuckets:
    def test_per_tenant_isolation(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_TENANT_RPS", "2")
        monkeypatch.setenv("WEED_QOS_TENANT_BURST", "2")
        clk = FakeClock()
        tb = TenantBuckets(now=clk)
        assert tb.try_take("alice") and tb.try_take("alice")
        assert not tb.try_take("alice")
        assert tb.try_take("bob")  # separate bucket
        assert tb.try_take("")     # unattributed traffic never throttles
        clk.advance(1.0)
        assert tb.try_take("alice")
        snap = tb.snapshot()
        assert snap["tenants"] == 2 and snap["denied"] == 1

    def test_unset_rate_admits_everything(self, monkeypatch):
        monkeypatch.delenv("WEED_QOS_TENANT_RPS", raising=False)
        tb = TenantBuckets(now=FakeClock())
        assert all(tb.try_take("t") for _ in range(50))
        assert tb.snapshot()["tenants"] == 0  # no bucket even built

    def test_cap_evicts_oldest(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_TENANT_RPS", "1000")
        tb = TenantBuckets(cap=3, now=FakeClock())
        for t in ("a", "b", "c", "d"):
            tb.try_take(t)
        assert tb.snapshot()["tenants"] == 3


class TestAdmissionGate:
    def _gate(self, **kw):
        kw.setdefault("limit_env", "T_QOS_GATE_LIMIT")
        kw.setdefault("now", FakeClock())
        return AdmissionGate("test", **kw)

    def test_no_limit_classifies_and_counts_only(self, monkeypatch):
        monkeypatch.delenv("T_QOS_GATE_LIMIT", raising=False)
        g = self._gate()
        for _ in range(5):
            release = g.admit(INT)
            release()
        assert g.admitted[INT] == 5
        assert g.total_inflight() == 0 and g.occupancy() == 0.0

    def test_deprecated_fallback_env(self, monkeypatch):
        g = self._gate(limit_env="T_QOS_NEW", fallback_env="T_QOS_OLD",
                       default_limit=9)
        assert g.effective_limit() == 9
        monkeypatch.setenv("T_QOS_OLD", "7")
        assert g.effective_limit() == 7
        monkeypatch.setenv("T_QOS_NEW", "3")  # new knob wins
        assert g.effective_limit() == 3

    def test_admit_release_and_nowait_shed(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "2")
        g = self._gate()
        r1, r2 = g.admit(STD), g.admit(STD)
        with pytest.raises(RpcError) as ei:
            g.admit(STD, wait=False)
        assert ei.value.status == 503
        assert 1 <= int(ei.value.headers["Retry-After"]) <= 4
        r1()
        r1()  # idempotent: double release must not free two slots
        g.admit(STD)()
        r2()
        assert g.total_inflight() == 0
        assert g.shed[STD] == 1 and g.admitted[STD] == 3

    def test_queue_timeout_sheds_503(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "1")
        monkeypatch.setenv("WEED_QOS_QUEUE_TIMEOUT", "0")
        g = self._gate()
        hold = g.admit(INT)
        with pytest.raises(RpcError) as ei:
            g.admit(BG)  # parks, times out instantly, sheds
        assert ei.value.status == 503
        assert "Retry-After" in ei.value.headers
        assert g.shed[BG] == 1 and g.total_queued() == 0
        hold()

    def test_release_dispatches_interactive_first(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "1")
        g = self._gate()
        release = g.admit(STD)
        waiters = {cls: _Waiter(cls) for cls in (BG, STD, INT)}
        with g._lock:
            for w in waiters.values():  # bg pushed first, int last
                g._drr.push(w.cls, w)
                g.queued[w.cls] += 1
        release()  # one slot frees: DRR must hand it to interactive
        assert waiters[INT].event.is_set()
        assert not waiters[STD].event.is_set()
        assert not waiters[BG].event.is_set()
        g._release(INT)
        assert waiters[STD].event.is_set()
        assert not waiters[BG].event.is_set()
        g._release(STD)
        assert waiters[BG].event.is_set()
        g._release(BG)
        assert g.total_inflight() == 0 and g.total_queued() == 0

    def test_cancelled_waiter_skipped_on_dispatch(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "1")
        g = self._gate()
        release = g.admit(STD)
        dead, live = _Waiter(INT), _Waiter(INT)
        dead.cancelled = True
        with g._lock:
            for w in (dead, live):
                g._drr.push(w.cls, w)
            g.queued[INT] += 1  # only `live` still counts as queued
        release()
        assert live.event.is_set() and not dead.event.is_set()
        g._release(INT)

    def test_threaded_queue_admission(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "1")
        monkeypatch.setenv("WEED_QOS_QUEUE_TIMEOUT", "30")
        g = AdmissionGate("test", limit_env="T_QOS_GATE_LIMIT")
        release = g.admit(INT)
        admitted = threading.Event()

        def second():
            r = g.admit(INT)  # parks until the holder releases
            admitted.set()
            r()

        th = threading.Thread(target=second, daemon=True)
        th.start()
        deadline = time.monotonic() + 10
        while g.total_queued() < 1:
            assert time.monotonic() < deadline, "waiter never queued"
            time.sleep(0.001)
        assert not admitted.is_set()
        release()
        th.join(timeout=10)
        assert admitted.is_set()
        assert g.admitted[INT] == 2 and g.total_inflight() == 0

    def test_background_sheds_at_watermark(self, monkeypatch):
        """Class-aware shedding: at 50% total queue occupancy
        background stops queuing while standard and interactive still
        park; interactive gives up only at its own cap."""
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "1")
        for cls_env in ("INTERACTIVE", "STANDARD", "BACKGROUND"):
            monkeypatch.setenv(f"WEED_QOS_QUEUE_{cls_env}", "4")
        g = self._gate()
        hold = g.admit(STD)
        with g._lock:  # park 6 of 12 total slots: bg watermark (50%)
            for cls in (INT, INT, INT, STD, STD, STD):
                g._drr.push(cls, _Waiter(cls))
                g.queued[cls] += 1
            with pytest.raises(RpcError) as ei:
                g._try_enqueue(BG, wait=True)
            assert ei.value.status == 503
            # standard (85% watermark) and interactive still queue
            assert g._try_enqueue(STD, wait=True).cls == STD
            assert g._try_enqueue(INT, wait=True).cls == INT
            # interactive sheds only once its own queue cap (4) fills
            with pytest.raises(RpcError):
                g._try_enqueue(INT, wait=True)
        assert g.shed[BG] == 1
        hold()

    def test_tenant_bucket_sheds_429(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_TENANT_RPS", "1")
        monkeypatch.setenv("WEED_QOS_TENANT_BURST", "1")
        clk = FakeClock()
        g = self._gate(now=clk)
        g.admit(STD, tenant="hog")()
        with pytest.raises(RpcError) as ei:
            g.admit(STD, tenant="hog")
        assert ei.value.status == 429
        assert "Retry-After" in ei.value.headers
        g.admit(STD, tenant="polite")()  # other tenants unaffected
        clk.advance(1.0)
        g.admit(STD, tenant="hog")()

    def test_occupancy_is_the_pacer_signal(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "4")
        g = self._gate()
        assert g.occupancy() == 0.0
        r1, r2 = g.admit(INT), g.admit(BG)
        assert g.occupancy() == 0.5
        r1()
        r2()
        monkeypatch.delenv("T_QOS_GATE_LIMIT")
        assert g.occupancy() == 0.0  # no limit -> no backpressure signal

    def test_snapshot_shape(self, monkeypatch):
        monkeypatch.setenv("T_QOS_GATE_LIMIT", "8")
        g = self._gate()
        r = g.admit(INT, tenant="t")
        snap = g.snapshot()
        r()
        assert snap["service"] == "test" and snap["limit"] == 8
        assert snap["inflight"][INT] == 1
        assert set(snap["weights"]) == set(qos.CLASSES)
        assert snap["queue_caps"][BG] >= 1


class TestClassify:
    def test_scope_nesting_restores(self):
        assert qos.current_class() == STD and qos.current_tenant() == ""
        with qos.qos_scope(BG, tenant="curator"):
            assert (qos.current_class(), qos.current_tenant()) == \
                (BG, "curator")
            with qos.qos_scope(INT):  # tenant=None keeps enclosing
                assert (qos.current_class(), qos.current_tenant()) == \
                    (INT, "curator")
            assert qos.current_class() == BG
        assert qos.current_class() == STD and qos.current_tenant() == ""

    def test_inject_and_from_headers_roundtrip(self):
        assert qos.inject({}) == {}  # unclassified traffic adds nothing
        with qos.qos_scope(BG, tenant="t1"):
            h = qos.inject({})
        assert h == {qos.QOS_HEADER: BG, qos.TENANT_HEADER: "t1"}
        assert qos.from_headers(h) == (BG, "t1")
        assert qos.from_headers({}) == (STD, "")
        assert qos.from_headers({qos.QOS_HEADER: "bogus"}) == (STD, "")

    def test_class_map_overrides_tenant(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_CLASS_MAP",
                           "analytics=background, mobile=interactive")
        assert qos.class_for_tenant("analytics", STD) == BG
        assert qos.class_for_tenant("mobile", STD) == INT
        assert qos.class_for_tenant("other", STD) == STD

    def test_retry_after_jitter_bounds(self):
        assert qos.retry_after(1, 3, rand=lambda: 0.0) == "1"
        assert qos.retry_after(1, 3, rand=lambda: 0.999) == "4"
        assert qos.retry_after(2, 0) == "2"
        import random
        rng = random.Random(7)
        vals = {qos.retry_after(1, 3, rand=rng.random)
                for _ in range(64)}
        assert vals == {"1", "2", "3", "4"}  # full jitter, both ends


class TestHeaderPropagation:
    def test_class_and_tenant_ride_rpc_headers(self):
        seen = []
        s = RpcServer()
        s.add("GET", "/who", lambda req: {
            "cls": qos.current_class(), "tenant": qos.current_tenant()})
        s.add("GET", "/probe",
              lambda req: seen.append((qos.current_class(),
                                       qos.current_tenant())) or {})
        s.start()
        try:
            assert call(s.address, "/who") == \
                {"cls": STD, "tenant": ""}
            with qos.qos_scope(BG, tenant="scrubber"):
                assert call(s.address, "/who") == \
                    {"cls": BG, "tenant": "scrubber"}
            call(s.address, "/probe")  # context reset between requests
            assert seen == [(STD, "")]
        finally:
            s.stop()


class TestDeviceLanes:
    def test_checkpoint_without_foreground_is_free(self):
        lanes = DeviceLanes()
        assert lanes.background_checkpoint() == 0.0
        snap = lanes.snapshot()
        assert snap["background_batches"] == 1
        assert snap["preemptions"] == 0

    def test_foreground_blocks_background_until_exit(self):
        lanes = DeviceLanes()
        entered = threading.Event()
        waited = []

        def bg():
            entered.set()
            waited.append(lanes.background_checkpoint())

        with lanes.foreground():
            th = threading.Thread(target=bg, daemon=True)
            th.start()
            entered.wait(5)
            deadline = time.monotonic() + 5
            while lanes.snapshot()["preemptions"] < 1:
                assert time.monotonic() < deadline, "bg never preempted"
                time.sleep(0.001)
            assert not waited  # still parked behind the fg decode
        th.join(timeout=5)
        assert waited and waited[0] >= 0.0
        snap = lanes.snapshot()
        assert snap["preemptions"] == 1
        assert snap["foreground_batches"] == 1
        assert snap["background_batches"] == 1

    def test_stall_floor_prevents_starvation(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_BG_MAX_STALL_MS", "0")
        lanes = DeviceLanes(now=lambda: 100.0)
        with lanes.foreground():
            # floor 0: the checkpoint counts the preemption but never
            # parks — background cannot be starved forever
            assert lanes.background_checkpoint() == 0.0
        assert lanes.snapshot()["preemptions"] == 1

    def test_disabled_lanes_never_pace(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_LANES", "0")
        lanes = DeviceLanes()
        with lanes.foreground():
            assert lanes.background_checkpoint() == 0.0
        assert lanes.snapshot()["preemptions"] == 0


class TestCollectionQuotas:
    def test_spec_parser(self):
        spec = qos_quota._parse_spec(
            "photos=200ops+64mb, logs=50ops,*=1000ops, junk, =2ops")
        assert spec["photos"] == (200.0, 64 * (1 << 20))
        assert spec["logs"] == (50.0, 0.0)
        assert spec["*"] == (1000.0, 0.0)

    def test_ops_and_byte_buckets(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_QUOTA", "photos=2ops+1mb,*=1000ops")
        clk = FakeClock()
        q = qos_quota.CollectionQuotas(now=clk)
        assert q.allow("photos") and q.allow("photos")
        assert not q.allow("photos")  # ops quota drained
        clk.advance(1.0)
        assert q.allow("photos", nbytes=1 << 20)
        assert not q.allow("photos", nbytes=1)  # byte quota drained
        assert q.allow("unlisted")  # falls to the * entry
        assert q.rejects["ops"] == 1 and q.rejects["bytes"] == 1

    def test_no_spec_is_unlimited(self, monkeypatch):
        monkeypatch.delenv("WEED_QOS_QUOTA", raising=False)
        q = qos_quota.CollectionQuotas(now=FakeClock())
        assert all(q.allow("c", nbytes=1 << 30) for _ in range(100))

    def test_live_spec_change_resets_buckets(self, monkeypatch):
        monkeypatch.setenv("WEED_QOS_QUOTA", "c=1ops")
        clk = FakeClock()
        q = qos_quota.CollectionQuotas(now=clk)
        assert q.allow("c") and not q.allow("c")
        monkeypatch.setenv("WEED_QOS_QUOTA", "c=5ops")
        assert q.allow("c")  # new spec, fresh bucket


class TestDaemonIntegration:
    def test_debug_qos_and_metric_families(self, tmp_path):
        """/debug/qos answers on master and volume server, the gate
        sees classified traffic, and the qos_* Prometheus families
        survive the strict exposition parser."""
        from tests.test_metrics_exposition import (check_histograms,
                                                   strict_parse)

        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "v"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        try:
            a = call(master.address, "/dir/assign")
            call(a["url"], f"/{a['fid']}", raw=b"q" * 512, method="POST")
            with qos.qos_scope(BG, tenant="scrubber"):
                assert call(a["url"], f"/{a['fid']}") == b"q" * 512
            assert call(a["url"], f"/{a['fid']}") == b"q" * 512

            snap = call(vs.store.url, "/debug/qos")
            assert snap["enabled"] is True
            gate = snap["gate"]
            assert gate["service"] == "volume"
            # tagged background read + unclassified-GET=interactive
            assert gate["admitted"]["background"] >= 1
            assert gate["admitted"]["interactive"] >= 1
            assert "lanes" in snap and "quotas" in snap

            msnap = call(master.address, "/debug/qos")
            assert msnap["gate"] is None and "quotas" in msnap

            payload = call(vs.store.url, "/metrics")
            if isinstance(payload, (bytes, bytearray)):
                payload = payload.decode()
            fams = strict_parse(payload)
            assert fams["SeaweedFS_qos_requests_total"][
                "type"] == "counter"
            assert fams["SeaweedFS_qos_inflight"]["type"] == "gauge"
            assert fams["SeaweedFS_qos_queue_depth"]["type"] == "gauge"
            assert fams["SeaweedFS_qos_queue_wait_seconds"][
                "type"] == "histogram"
            assert fams["SeaweedFS_qos_lane_preemptions_total"][
                "type"] == "counter"
            check_histograms(fams)
            admits = [s for s in
                      fams["SeaweedFS_qos_requests_total"]["samples"]
                      if s[1].get("service") == "volume"
                      and s[1].get("outcome") == "admit"]
            assert sum(v for _, _, v in admits) >= 3
        finally:
            vs.stop()
            master.stop()

    def test_master_assign_quota_sheds_with_retry_after(
            self, tmp_path, monkeypatch):
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "v"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        try:
            monkeypatch.setenv("WEED_QOS_QUOTA", "*=1ops")
            assert "fid" in call(master.address, "/dir/assign")
            with pytest.raises(RpcError) as ei:
                call(master.address, "/dir/assign")
            assert ei.value.status == 503
            assert 1 <= int(ei.value.headers["Retry-After"]) <= 4
            monkeypatch.setenv("WEED_QOS_QUOTA", "")
            assert "fid" in call(master.address, "/dir/assign")
        finally:
            vs.stop()
            master.stop()

    def test_s3_put_quota_slowdown(self, tmp_path, monkeypatch):
        from tests.test_s3 import sigv4_request

        from seaweedfs_tpu.filer.server import FilerServer
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.s3api.server import S3ApiServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "v"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        filer = FilerServer(master.address, port=0, chunk_size=1024)
        filer.start()
        s3 = S3ApiServer(filer, port=0)
        s3.start()
        try:
            assert sigv4_request(s3.address, "PUT", "/qb")[0] == 200
            monkeypatch.setenv("WEED_QOS_QUOTA", "qb=1ops")
            status, _, _ = sigv4_request(s3.address, "PUT", "/qb/k1",
                                         body=b"x")
            assert status == 200
            status, headers, body = sigv4_request(
                s3.address, "PUT", "/qb/k2", body=b"x")
            assert status == 503 and b"SlowDown" in body
            assert 1 <= int(headers["Retry-After"]) <= 4
            monkeypatch.setenv("WEED_QOS_QUOTA", "")
            assert sigv4_request(s3.address, "PUT", "/qb/k2",
                                 body=b"x")[0] == 200
        finally:
            s3.stop()
            filer.stop()
            vs.stop()
            master.stop()


def _make_scrub_volume(directory, vid, n_bytes, seed):
    from seaweedfs_tpu.storage.erasure_coding.encoder import (
        save_volume_info, write_ec_files)

    base = os.path.join(str(directory), str(vid))
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, n_bytes, dtype=np.uint8).tobytes())
    crcs = write_ec_files(base, batched=True)
    save_volume_info(base, version=3, extra={"shard_crc32c": crcs})
    return base


@pytest.mark.qos
@pytest.mark.chaos
class TestIsolationChaos:
    def test_scrub_paced_behind_held_foreground_lane(
            self, tmp_path, monkeypatch):
        """Deterministic pacing proof: with the foreground lane held,
        every scrub batch preempts and pays the stall floor."""
        from seaweedfs_tpu.maintenance.deep_scrub import (deep_scrub,
                                                          local_target)

        monkeypatch.setenv("WEED_QOS_BG_MAX_STALL_MS", "20")
        bases = [_make_scrub_volume(tmp_path, i + 1, 1 << 20, seed=i)
                 for i in range(2)]
        targets = [local_target(b, i + 1) for i, b in enumerate(bases)]
        LANES.reset()
        stats: dict = {}
        with LANES.foreground():
            out = deep_scrub(targets, span_bytes=256 << 10,
                             batch_units=2, stage_stats=stats)
        assert out["corrupt"] == [] and out["scrubbed_bytes"] > 0
        snap = LANES.snapshot()
        assert snap["preemptions"] >= 1
        assert snap["background_wait_seconds"] > 0.0
        # the stall shows up in the scrub's own stage accounting
        assert stats.get("lane_wait", 0.0) > 0.0

    def test_degraded_reads_and_concurrent_scrub_share_the_lanes(
            self, tmp_path, monkeypatch):
        """The isolation drill: a 1 KB degraded-read storm (shards
        0-3 killed, every read reconstructs) runs against a live
        volume server while a fault-injected device-batched deep scrub
        loops in-process.  Every foreground read returns its bytes, the
        scrub makes progress, and both lanes saw batches: the scrub was
        dispatched behind the foreground lane, not beside it.  What the
        scrub costs the storm's p99 is a number for the chip."""
        import concurrent.futures as cf

        from seaweedfs_tpu.maintenance.deep_scrub import (deep_scrub,
                                                          local_target)
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.shell import commands as sh
        from seaweedfs_tpu.util import faults
        from seaweedfs_tpu.volume_server.server import VolumeServer

        monkeypatch.setenv("WEED_QOS_BG_MAX_STALL_MS", "100")
        # disable the recovered-block LRU so every storm read really
        # decodes (otherwise one pass caches the whole 150 KB volume
        # and the foreground lane never activates)
        monkeypatch.setenv("WEED_EC_RECOVER_CACHE_MB", "0")
        workdir = tmp_path / "vs"
        workdir.mkdir()
        master = MasterServer(port=0, pulse_seconds=0.5,
                              volume_size_limit_mb=256)
        master.start()
        vs = VolumeServer([str(workdir)], master.address, port=0,
                          pulse_seconds=0.5, max_volume_counts=[8])
        vs.start()
        vs.heartbeat_once()
        try:
            payload = os.urandom(1024)
            fids, vid = [], None
            for _ in range(150):
                a = call(master.address, "/dir/assign")
                if vid is None:
                    vid = int(a["fid"].split(",")[0])
                if int(a["fid"].split(",")[0]) != vid:
                    continue
                call(a["url"], f"/{a['fid']}", raw=payload,
                     method="POST")
                fids.append(a["fid"])
            sh.ec_encode(sh.CommandEnv(master.address), vid)
            vs.heartbeat_once()
            kill = [0, 1, 2, 3]
            call(vs.store.url, "/admin/ec/unmount",
                 {"volume": vid, "shard_ids": kill})
            call(vs.store.url, "/admin/ec/delete_shards",
                 {"volume": vid, "shard_ids": kill})
            vs.heartbeat_once()
            assert call(vs.store.url, f"/{fids[0]}") == payload

            def storm(n=300, workers=8) -> int:
                def one(i):
                    return call(vs.store.url,
                                f"/{fids[i % len(fids)]}") == payload

                with cf.ThreadPoolExecutor(max_workers=workers) as pool:
                    return n - sum(pool.map(one, range(n)))

            assert storm() == 0

            # background: scrub separate volumes in a loop until the
            # storm drains, under injected latency faults (the chaos
            # part: the scrub path must stay paced even while crawling)
            sdir = tmp_path / "scrub"
            sdir.mkdir()
            bases = [_make_scrub_volume(sdir, i + 1, 1 << 20, seed=40 + i)
                     for i in range(2)]
            targets = [local_target(b, i + 1)
                       for i, b in enumerate(bases)]
            deep_scrub(targets, span_bytes=128 << 10, batch_units=2)
            faults.REGISTRY.configure(
                "latency,ms=20,pct=10,side=server,route=/[0-9]*",
                seed=7)
            LANES.reset()
            stop = threading.Event()
            passes = [0]

            def scrub_loop():
                with qos.qos_scope(BG, tenant="maintenance"):
                    while not stop.is_set():
                        deep_scrub(targets, span_bytes=128 << 10,
                                   batch_units=2)
                        passes[0] += 1

            th = threading.Thread(target=scrub_loop, daemon=True)
            th.start()
            try:
                failed = storm()
            finally:
                stop.set()
                th.join(timeout=60)
                faults.REGISTRY.clear()

            assert failed == 0
            assert not th.is_alive(), "scrub loop never stopped"
            snap = LANES.snapshot()
            # the scrub made progress AND went through the lanes the
            # foreground storm was dispatching on
            assert passes[0] >= 1 or snap["background_batches"] > 0
            assert snap["foreground_batches"] > 0
        finally:
            vs.stop()
            master.stop()
