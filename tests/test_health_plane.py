"""Cluster health plane: ring TSDB, SLO burn-rate engine (fake clock),
event journal, /healthz + /readyz, and the live chaos slice — a
multi-master cluster where a volume server dies, the availability alert
must fire with the kill/election/alert sequence ordered in
/cluster/events, and clear after recovery."""

import json
import socket
import time

import pytest

from seaweedfs_tpu.rpc.http_rpc import RpcError, call
from seaweedfs_tpu.stats import events as events_mod
from seaweedfs_tpu.stats import metrics as stats
from seaweedfs_tpu.stats import slo as slo_mod
from seaweedfs_tpu.stats import tsdb as tsdb_mod


def wait_for(pred, timeout=10.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


# ---------------------------------------------------------------------------
# Ring TSDB
# ---------------------------------------------------------------------------

class TestTsdb:
    def test_ingest_latest_and_avg(self):
        clock = [1000.0]
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: clock[0])
        text = ("# TYPE SeaweedFS_demo_up gauge\n"
                'SeaweedFS_demo_up{kind="volume"} 1\n')
        db.ingest("127.0.0.1:9", text)
        latest = db.latest("SeaweedFS_demo_up")
        assert list(latest.values()) == [1.0]
        # the target label is stamped on
        (items,) = latest.keys()
        assert dict(items)["target"] == "127.0.0.1:9"
        clock[0] += 1
        db.ingest("127.0.0.1:9", text.replace(" 1\n", " 0\n"))
        assert db.avg("SeaweedFS_demo_up", 10.0) == 0.5
        assert db.avg("SeaweedFS_demo_up", 10.0,
                      match={"kind": "volume"}) == 0.5
        assert db.avg("SeaweedFS_demo_up", 10.0,
                      match={"kind": "nope"}) is None

    def test_counter_delta_survives_reset(self):
        clock = [0.0]
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: clock[0])
        for v in (100.0, 110.0, 5.0, 20.0):  # restart drops to 5
            db.put("SeaweedFS_demo_total", {}, v, tsdb_mod.COUNTER)
            clock[0] += 1
        # monotone increases only: 10 + 15, not the -105 swing
        assert db.delta("SeaweedFS_demo_total", 60.0) == 25.0

    def test_retention_laps_old_slots(self, monkeypatch):
        monkeypatch.setenv("WEED_TSDB_RETENTION", "10")
        clock = [0.0]
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: clock[0])
        db.put("SeaweedFS_demo", {}, 1.0)
        clock[0] += 100.0  # many laps later the old slot must be stale
        db.put("SeaweedFS_demo", {}, 2.0)
        (ring,) = db.series.values()
        pts = ring.window(clock[0], 1000.0)
        assert pts == [(100.0, 2.0)]

    def test_cardinality_cap_prefers_priority_families(self, monkeypatch):
        monkeypatch.setenv("WEED_TSDB_MAX_SERIES", "16")
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: 0.0)
        lines = ["# TYPE SeaweedFS_filler gauge"]
        lines += [f'SeaweedFS_filler{{i="{i}"}} 1' for i in range(40)]
        lines += ["# TYPE SeaweedFS_vip_seconds histogram",
                  'SeaweedFS_vip_seconds_bucket{le="+Inf"} 3',
                  "SeaweedFS_vip_seconds_count 3"]
        text = "\n".join(lines) + "\n"
        db.ingest("t", text, priority={"SeaweedFS_vip_seconds"})
        fams = db.families()
        # the priority family got slots even though filler alone would
        # have exhausted the cap; the overflow was counted
        assert "SeaweedFS_vip_seconds_bucket" in fams
        assert "SeaweedFS_vip_seconds_count" in fams
        assert db.dropped > 0
        assert len(db.series) <= 16

    def test_ingest_never_feeds_back_own_families(self):
        """The leader's /metrics exports the health plane's derived
        gauges; scraping them back in would let a stale
        cluster_target_up 0 hold an availability alert firing forever
        (regression: the live chaos test's clear-after-recovery)."""
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: 0.0)
        text = ("# TYPE SeaweedFS_cluster_target_up gauge\n"
                'SeaweedFS_cluster_target_up{target="dead:1"} 0\n'
                "# TYPE SeaweedFS_cluster_slo_burn_rate gauge\n"
                'SeaweedFS_cluster_slo_burn_rate{rule="a"} 300\n'
                "# TYPE SeaweedFS_demo_up gauge\n"
                "SeaweedFS_demo_up 1\n")
        db.ingest("127.0.0.1:9333", text)
        assert db.families() == {"SeaweedFS_demo_up"}

    def test_histogram_window_and_quantile(self):
        clock = [0.0]
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: clock[0])
        fam = "SeaweedFS_demo_seconds"
        for t, (b1, b2, binf) in ((0, (0, 0, 0)), (1, (90, 99, 100))):
            clock[0] = float(t)
            db.put(fam + "_bucket", {"le": "0.1"}, float(b1),
                   tsdb_mod.COUNTER)
            db.put(fam + "_bucket", {"le": "0.5"}, float(b2),
                   tsdb_mod.COUNTER)
            db.put(fam + "_bucket", {"le": "+Inf"}, float(binf),
                   tsdb_mod.COUNTER)
            db.put(fam + "_count", {}, float(binf), tsdb_mod.COUNTER)
        buckets, count = db.histogram_window(fam, 60.0)
        assert count == 100.0
        assert [le for le, _ in buckets] == [0.1, 0.5, float("inf")]
        p99 = tsdb_mod.quantile(buckets, count, 0.99)
        assert p99 == pytest.approx(0.5, rel=0.01)
        # the p50 lands inside the first bucket by interpolation
        assert tsdb_mod.quantile(buckets, count, 0.5) < 0.1


# ---------------------------------------------------------------------------
# Event journal
# ---------------------------------------------------------------------------

class TestEventJournal:
    def test_emit_since_and_cap(self, monkeypatch):
        monkeypatch.setenv("WEED_EVENTS_MAX", "16")
        j = events_mod.EventJournal(now=lambda: 42.0)
        for i in range(40):
            j.emit("demo.kind", service="test", node=str(i))
        evs = j.since(0)
        assert len(evs) == 16  # ring capped
        assert evs[-1]["node"] == "39" and evs[-1]["seq"] == 40
        assert j.since(38) == evs[-2:]
        assert j.since(0, limit=3) == evs[-3:]
        assert all(e["ts"] == 42.0 for e in evs)

    def test_merge_dedups_by_origin_cursor(self):
        a = events_mod.EventJournal()
        b = events_mod.EventJournal()
        a.emit("k1", node="n1")
        a.emit("k2", node="n2")
        assert b.merge(a.since(0)) == 2
        # replaying the same batch lands nothing new
        assert b.merge(a.since(0)) == 0
        a.emit("k3", node="n3")
        assert b.merge(a.since(0)) == 1
        kinds = [e["kind"] for e in b.since(0)]
        assert kinds == ["k1", "k2", "k3"]
        # a journal never re-ingests its own events (shared-process echo)
        assert a.merge(b.since(0)) == 0

    def test_wait_unblocks_on_emit(self):
        j = events_mod.EventJournal()
        assert j.wait(j.seq, timeout=0.05) == []
        j.emit("late.kind")
        got = j.wait(0, timeout=1.0)
        assert got and got[-1]["kind"] == "late.kind"


# ---------------------------------------------------------------------------
# SLO engine under a fake clock — fully deterministic fire/clear
# ---------------------------------------------------------------------------

class TestSloEngineFakeClock:
    def _mk(self, monkeypatch):
        monkeypatch.setenv("WEED_SLO_FAST_S", "10")
        monkeypatch.setenv("WEED_SLO_SLOW_S", "60")
        clock = [10000.0]
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: clock[0])
        transitions = []
        rules = [slo_mod.Rule("availability", "availability",
                              slo_mod.LIVENESS_FAMILY, objective=0.999)]
        eng = slo_mod.SloEngine(
            db, rules=rules, now=lambda: clock[0],
            on_transition=lambda r, a, f: transitions.append((r.name, f)),
            journal=events_mod.EventJournal(now=lambda: clock[0]))
        return clock, db, eng, transitions

    def _feed(self, db, clock, ups, seconds):
        for _ in range(int(seconds)):
            for target, up in ups.items():
                db.put(slo_mod.LIVENESS_FAMILY,
                       {"target": target, "kind": "volume"}, float(up))
            clock[0] += 1.0

    def test_fire_needs_both_windows_then_clears(self, monkeypatch):
        clock, db, eng, transitions = self._mk(monkeypatch)
        # 60 s healthy: burn 0, nothing fires
        self._feed(db, clock, {"a": 1, "b": 1}, 60)
        out = eng.evaluate()
        assert out["availability"]["firing"] is False
        assert out["availability"]["burn_fast"] == 0.0
        # target b dies; one bad sample in both windows blows the
        # 0.1% budget instantly (multi-window: both must burn)
        self._feed(db, clock, {"a": 1, "b": 0}, 3)
        out = eng.evaluate()
        alert = out["availability"]
        assert alert["firing"] is True
        assert alert["burn_fast"] >= 14.4
        assert alert["burn_slow"] >= 6.0
        assert alert["detail"]["down"] == ["b"]
        assert transitions == [("availability", True)]
        assert eng.firing() == ["availability"]
        # recovery: the alert clears only once the fast window is clean
        self._feed(db, clock, {"a": 1, "b": 1}, 3)
        assert eng.evaluate()["availability"]["firing"] is True
        self._feed(db, clock, {"a": 1, "b": 1}, 12)
        out = eng.evaluate()
        assert out["availability"]["firing"] is False
        assert transitions == [("availability", True),
                               ("availability", False)]
        kinds = [e["kind"] for e in eng.journal.since(0)]
        assert kinds == [events_mod.ALERT_FIRE, events_mod.ALERT_CLEAR]

    def test_slow_window_suppresses_blips(self, monkeypatch):
        clock, db, eng, transitions = self._mk(monkeypatch)
        # long healthy history, then a single bad sample: the fast
        # window burns hot but the 60 s window stays under threshold
        rule = eng.rules()[0]
        rule.burn_fast, rule.burn_slow = 2.0, 50.0
        self._feed(db, clock, {"a": 1, "b": 1}, 60)
        self._feed(db, clock, {"a": 1, "b": 0}, 1)
        out = eng.evaluate()
        assert out["availability"]["burn_fast"] >= 2.0
        assert out["availability"]["burn_slow"] < 50.0
        assert out["availability"]["firing"] is False
        assert transitions == []

    def test_latency_rule_p99_from_bucket_deltas(self, monkeypatch):
        monkeypatch.setenv("WEED_SLO_FAST_S", "10")
        monkeypatch.setenv("WEED_SLO_SLOW_S", "60")
        clock = [5000.0]
        db = tsdb_mod.Tsdb(interval=1.0, now=lambda: clock[0])
        fam = "SeaweedFS_qos_queue_wait_seconds"
        rule = slo_mod.Rule("p99-int", "latency", fam,
                            match={"class": "interactive"},
                            objective=0.99, le=0.1,
                            burn_fast=1.5, burn_slow=1.0)
        eng = slo_mod.SloEngine(
            db, rules=[rule], now=lambda: clock[0],
            journal=events_mod.EventJournal(now=lambda: clock[0]))

        def feed(total, fast):
            db.put(fam + "_bucket", {"class": "interactive", "le": "0.1"},
                   float(fast), tsdb_mod.COUNTER)
            db.put(fam + "_bucket", {"class": "interactive", "le": "+Inf"},
                   float(total), tsdb_mod.COUNTER)
            db.put(fam + "_count", {"class": "interactive"},
                   float(total), tsdb_mod.COUNTER)
            clock[0] += 1.0

        feed(0, 0)
        for _ in range(5):  # 100% fast traffic
            feed(1000, 1000)
        out = eng.evaluate()["p99-int"]
        assert out["firing"] is False and out["burn_fast"] == 0.0
        for _ in range(5):  # 10% of new requests slower than 100 ms
            feed(6000, 5900)
        out = eng.evaluate()["p99-int"]
        # bad fraction ~5%/window vs 1% budget in both windows -> fires
        assert out["firing"] is True
        assert out["detail"]["requests"] > 0
        assert out["detail"]["p99_ms"] is not None

    def test_no_traffic_is_not_an_alert(self, monkeypatch):
        clock, db, eng, _ = self._mk(monkeypatch)
        out = eng.evaluate()
        assert out["availability"]["firing"] is False


class TestSloRuleParsing:
    def test_compact_spec_round_trip(self):
        rules = slo_mod.parse_rules(
            "p99-get,kind=latency,family=SeaweedFS_demo_seconds,"
            "match.type=get,le=0.1,objective=0.99,burn_fast=2,burn_slow=1"
            "; avail,kind=availability,objective=0.9995"
            "; ,kind=latency"            # nameless: skipped
            "; bad,kind=latency,le=oops" # malformed float: skipped
            "; worse,kind=nonsense")     # unknown kind: skipped
        assert [r.name for r in rules] == ["p99-get", "avail"]
        assert rules[0].match == {"type": "get"}
        assert rules[0].thresholds() == (2.0, 1.0)
        assert rules[1].family == slo_mod.LIVENESS_FAMILY
        assert rules[1].budget == pytest.approx(0.0005)

    def test_env_spec_replaces_defaults(self, monkeypatch):
        assert [r.name for r in slo_mod.active_rules()] == [
            "availability", "p99-interactive", "p99-standard"]
        monkeypatch.setenv("WEED_SLO_RULES",
                           "only,kind=availability,objective=0.99")
        assert [r.name for r in slo_mod.active_rules()] == ["only"]


# ---------------------------------------------------------------------------
# /healthz + /readyz on a live daemon pair
# ---------------------------------------------------------------------------

class TestHealthzReadyz:
    def test_daemon_health_endpoints(self, tmp_path):
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
            for addr in (master.address, vs.address):
                assert call(addr, "/healthz")["ok"] is True
                ready = call(addr, "/readyz")
                assert ready["ready"] is True
                assert all(c["ok"] for c in ready["checks"])
            # draining flips the volume server not-ready with a 503
            # whose body names the failing check
            call(vs.address, "/admin/drain",
                 payload={"draining": True}, method="POST")
            with pytest.raises(RpcError) as exc:
                call(vs.address, "/readyz")
            assert exc.value.status == 503
            body = json.loads(str(exc.value))
            assert body["ready"] is False
            failing = [c["name"] for c in body["checks"] if not c["ok"]]
            assert "draining" in failing
        finally:
            vs.stop()
            master.stop()


# ---------------------------------------------------------------------------
# Live chaos slice: VS death -> alert -> ordered events -> clear after
# recovery
# ---------------------------------------------------------------------------

class TestClusterChaos:
    def test_vs_death_fires_availability_alert_then_clears(
            self, tmp_path, monkeypatch):
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.volume_server.server import VolumeServer

        # compress every window so fire AND clear happen in seconds:
        # scrape at 150 ms, alert windows of 2 s / 6 s.  The election
        # timeout stays generous — a spurious re-election mid-test
        # would hand the plane to a fresh leader with no liveness
        # history, which is a different scenario than the one pinned
        # here (down-transition ordering needs a stable observer).
        monkeypatch.setenv("WEED_HEALTH_SCRAPE_MS", "150")
        monkeypatch.setenv("WEED_HEALTH_DEADLINE_MS", "500")
        monkeypatch.setenv("WEED_SLO_FAST_S", "2")
        monkeypatch.setenv("WEED_SLO_SLOW_S", "6")
        # All five daemons export this process's one metrics registry,
        # which also holds whatever families the tests before this one
        # touched; five copies of it can fill the TSDB's default 4096
        # series before the last target's liveness series is made, and
        # a target without one is never seen down.
        monkeypatch.setenv("WEED_TSDB_MAX_SERIES", "65536")
        seq0 = events_mod.JOURNAL.seq
        ports = free_ports(3)
        addrs = [f"127.0.0.1:{p}" for p in ports]
        masters = []
        for i, p in enumerate(ports):
            d = tmp_path / f"m{i}"
            d.mkdir()
            masters.append(MasterServer(
                port=p, peers=list(addrs), raft_dir=str(d),
                raft_election_timeout=1.5, pulse_seconds=0.3))
        vss = []
        try:
            for m in masters:
                m.start()
            assert wait_for(lambda: any(m.raft.is_leader
                                        for m in masters), 10)
            leader = next(m for m in masters if m.raft.is_leader)
            for i in range(2):
                d = tmp_path / f"vs{i}"
                d.mkdir()
                vs = VolumeServer([str(d)], leader.address, port=0,
                                  pulse_seconds=0.2)
                vs.start()
                vs.heartbeat_once()
                vss.append(vs)
            victim_addr = vss[1].address
            victim_dir = str(tmp_path / "vs1")
            # the scrape loop must have SAMPLED every target healthy
            # first (the rollup defaults unknown targets to up, so the
            # later down-transition event needs real prior samples)
            assert wait_for(lambda: len(leader.health._up) >= 5
                            and all(leader.health._up.values()), 10)
            assert call(leader.address, "/cluster/health")["status"] == "ok"
            assert call(leader.address, "/cluster/alerts")["alerts"] == []

            # -- kill one volume server ---------------------------------
            vss[1].stop()
            assert wait_for(
                lambda: "availability" in call(
                    leader.address, "/cluster/alerts")["firing"], 30)
            # `firing` above is the evaluator's state, set before the
            # alert is pushed to the curator (raft commits); the rollup
            # /cluster/health serves is stored after that push returns
            rollups = []

            def rolled_up():
                rollups.append(call(leader.address, "/cluster/health"))
                return rollups[-1]["slo"]["availability"]["firing"]

            assert wait_for(rolled_up, 30)
            assert rollups[-1]["status"] in ("degraded", "critical")

            # events: the victim's death precedes the alert firing
            evs = [e for e in call(
                leader.address, f"/cluster/events?since={seq0}")["events"]]
            downs = [e for e in evs
                     if e["kind"] == events_mod.NODE_DOWN
                     and e["node"] == victim_addr]
            fires = [e for e in evs
                     if e["kind"] == events_mod.ALERT_FIRE
                     and e["node"] == "availability"]
            assert downs and fires
            assert min(e["seq"] for e in downs) < min(
                e["seq"] for e in fires)

            # -- recovery -----------------------------------------------
            vs2 = VolumeServer([victim_dir], leader.address, port=0,
                               pulse_seconds=0.2)
            vs2.start()
            vs2.heartbeat_once()
            vss[1] = vs2

            # a re-election mid-test would strand the old leader's
            # stale firing state; always poll the CURRENT leader
            def leader_addr():
                return next((m.address for m in masters
                             if m.raft.is_leader), leader.address)

            assert wait_for(
                lambda: "availability" not in call(
                    leader_addr(), "/cluster/alerts")["firing"], 30)
            evs = [e for e in call(
                leader.address, f"/cluster/events?since={seq0}")["events"]]
            clears = [e["seq"] for e in evs
                      if e["kind"] == events_mod.ALERT_CLEAR
                      and e["node"] == "availability"]
            assert clears and min(
                e["seq"] for e in fires) < min(clears)
            assert wait_for(lambda: call(
                leader_addr(), "/cluster/health")["status"] == "ok", 15)
        finally:
            for vs in vss:
                try:
                    vs.stop()
                except Exception:
                    pass
            for m in masters:
                try:
                    m.stop()
                except Exception:
                    pass
