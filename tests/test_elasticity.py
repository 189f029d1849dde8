"""Closed-loop elasticity: autoscale detector units over fabricated
telemetry snapshots, the cluster.scale shell surface against a live
mini-cluster, and a chaos-marked graceful-drain drill — scale.drain
under a foreground read storm must finish with zero failed reads and
interactive p99 inside the QoS isolation bound."""

import threading
import time

import pytest

from seaweedfs_tpu.maintenance import detectors
from seaweedfs_tpu.maintenance.jobs import TYPE_SCALE_DRAIN, TYPE_SCALE_UP
from seaweedfs_tpu.rpc.http_rpc import RpcError, call


def node(url, volumes=0, ec_shards=0, occupancy=0.0, rps=0.0,
         draining=False):
    return {"url": url, "volumes": volumes, "ec_shards": ec_shards,
            "occupancy": occupancy, "rps": rps, "mbps": 0.0,
            "draining": draining, "free": 10}


class TestScanScale:
    def test_disabled_by_default(self, monkeypatch):
        """Capacity changes are strictly opt-in: without WEED_SCALE the
        detector stays silent no matter how loaded the fleet looks."""
        monkeypatch.delenv("WEED_SCALE", raising=False)
        snap = {"nodes": [node("a", occupancy=1.0, rps=1e6)]}
        assert detectors.scan_scale(snap) == []

    def test_occupancy_pressure_scales_up(self):
        snap = {"nodes": [node("a", occupancy=0.9),
                          node("b", occupancy=0.8)]}
        (spec,) = detectors.scan_scale(snap, scale_enabled=True,
                                       scale_up_occ=0.75)
        assert spec["type"] == TYPE_SCALE_UP
        assert spec["params"]["nodes"] == 2
        assert spec["params"]["occupancy"] == pytest.approx(0.85)

    def test_rps_pressure_scales_up(self):
        """The GIL flattens instantaneous gate occupancy on small
        hosts, so mean rps is an OR'd second trigger (0 disables)."""
        snap = {"nodes": [node("a", occupancy=0.1, rps=900.0)]}
        (spec,) = detectors.scan_scale(snap, scale_enabled=True,
                                       scale_up_occ=0.75,
                                       scale_up_rps=500.0)
        assert spec["type"] == TYPE_SCALE_UP
        # rps trigger off -> same snapshot is quiet
        assert detectors.scan_scale(snap, scale_enabled=True,
                                    scale_up_occ=0.75,
                                    scale_up_rps=0.0) == []

    def test_idle_fleet_drains_emptiest_node(self):
        snap = {"nodes": [node("a", volumes=5, ec_shards=4),
                          node("b", volumes=1, ec_shards=0),
                          node("c", volumes=2, ec_shards=9)]}
        (spec,) = detectors.scan_scale(snap, scale_enabled=True,
                                       scale_drain_occ=0.15,
                                       scale_min_nodes=1,
                                       scale_drain_rps=1.0)
        assert spec["type"] == TYPE_SCALE_DRAIN
        # fewest volumes+shards evacuates the least data
        assert spec["params"]["server"] == "b"

    def test_rps_guard_blocks_drain_of_busy_fleet(self):
        """Serialized handlers can report near-zero occupancy during a
        real storm; the rps idle-guard must veto the drain."""
        snap = {"nodes": [node("a", occupancy=0.05, rps=800.0),
                          node("b", occupancy=0.05, rps=700.0)]}
        assert detectors.scan_scale(snap, scale_enabled=True,
                                    scale_drain_occ=0.15,
                                    scale_min_nodes=1,
                                    scale_drain_rps=1.0) == []

    def test_min_nodes_floor_blocks_drain(self):
        snap = {"nodes": [node("a"), node("b")]}
        assert detectors.scan_scale(snap, scale_enabled=True,
                                    scale_min_nodes=2) == []
        assert detectors.scan_scale({"nodes": [node("a")]},
                                    scale_enabled=True,
                                    scale_min_nodes=1) == []

    def test_draining_nodes_invisible_to_detectors(self):
        """A node mid-drain must not retrigger scale decisions: not as
        drain victim, not in the scale-up mean."""
        snap = {"nodes": [node("a", occupancy=0.1),
                          node("b", occupancy=0.9, draining=True)]}
        assert detectors.scan_scale(snap, scale_enabled=True,
                                    scale_up_occ=0.75,
                                    scale_min_nodes=1,
                                    scale_drain_occ=0.05) == []
        only_draining = {"nodes": [node("a", draining=True)]}
        assert detectors.scan_scale(only_draining,
                                    scale_enabled=True) == []


# -- live mini-cluster fixtures ----------------------------------------------


@pytest.fixture
def scale_cluster(tmp_path, monkeypatch):
    """Master + 2 volume servers, worker threads parked so tests drive
    poll_once() deterministically; autoscale detector stays opt-out."""
    monkeypatch.setenv("WEED_MAINT_WORKER", "0")
    monkeypatch.setenv("WEED_MAINT_INTERVAL", "3600")
    monkeypatch.delenv("WEED_SCALE", raising=False)
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    (tmp_path / "m").mkdir()
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=0.2,
                          raft_dir=str(tmp_path / "m"))
    master.start()
    servers = []
    for i in range(2):
        d = tmp_path / f"vs{i}"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          rack=f"rack{i}", pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        servers.append(vs)
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


def _preload(master, n=40, size=2048):
    import os as _os

    stored = {}
    for i in range(n):
        a = call(master.address, "/dir/assign")
        payload = _os.urandom(size)
        call(a["url"], f"/{a['fid']}", raw=payload, method="POST")
        stored[a["fid"]] = payload
    return stored


def _read(master, fid, retries=3):
    """Foreground read with fresh-lookup retry: mid-evacuation a volume
    may vanish from its old holder between lookup and GET."""
    vid = int(fid.split(",")[0])
    last = None
    for attempt in range(retries + 1):
        try:
            found = call(master.address, f"/dir/lookup?volumeId={vid}")
            for loc in found["locations"]:
                try:
                    return call(loc["url"], f"/{fid}")
                except RpcError as e:
                    last = e
        except RpcError as e:
            last = e
        time.sleep(0.05 * (attempt + 1))
    raise last or RpcError(f"unreachable {fid}", 404)


class TestScaleShell:
    def test_status_joins_knobs_and_telemetry(self, scale_cluster):
        from seaweedfs_tpu.shell import commands as sh
        from seaweedfs_tpu.shell import commands_scale as scale

        master, servers = scale_cluster
        env = sh.CommandEnv(master.address)
        st = scale.scale_status(env)
        assert st["autoscale"].keys() >= {"enabled", "up_occupancy",
                                          "drain_occupancy", "min_nodes"}
        assert st["autoscale"]["enabled"] is False
        assert len(st["nodes"]) == 2
        for n in st["nodes"]:
            assert n.keys() >= {"url", "volumes", "occupancy", "rps",
                                "draining"}
            assert n["draining"] is False
        assert st["scale_jobs"] == []

    def test_manual_up_and_drain_enqueue_jobs(self, scale_cluster):
        from seaweedfs_tpu.shell import commands as sh
        from seaweedfs_tpu.shell import commands_scale as scale

        master, servers = scale_cluster
        env = sh.CommandEnv(master.address)
        assert scale.scale_up(env)["enqueued"]
        target = servers[1].store.url
        assert scale.scale_drain(env, target)["enqueued"]
        with pytest.raises(ValueError):
            scale.scale_drain(env, "")
        jobs = scale.scale_status(env)["scale_jobs"]
        assert {j["type"] for j in jobs} == {TYPE_SCALE_UP,
                                             TYPE_SCALE_DRAIN}
        drain = next(j for j in jobs if j["type"] == TYPE_SCALE_DRAIN)
        assert drain["params"]["server"] == target


# -- chaos: graceful drain under live foreground traffic ---------------------


@pytest.mark.chaos
def test_scale_drain_under_storm_keeps_reads_whole(scale_cluster):
    """The ISSUE acceptance drill: trigger scale.drain of a populated
    server while a read storm runs.  The drain (read-only demotion ->
    evacuation -> deregistration) must complete with zero failed
    foreground reads, and every byte must survive the move."""
    master, servers = scale_cluster
    stored = _preload(master, n=40)
    fids = sorted(stored)
    for vs in servers:
        vs.heartbeat_once()

    victim = servers[1]
    victim_url = victim.store.url

    stop = threading.Event()

    def storm():
        i = 0
        while not stop.is_set():
            try:
                _read(master, fids[i % len(fids)], retries=0)
            except RpcError:
                pass  # storm reads are load, not the assertion
            i += 1

    storm_threads = [threading.Thread(target=storm, daemon=True)
                     for _ in range(6)]
    for th in storm_threads:
        th.start()

    call(master.address, "/maintenance/run",
         {"type": TYPE_SCALE_DRAIN, "params": {"server": victim_url}})
    drained = {"n": 0}

    def drain():
        # the surviving server's worker leases and executes the drain
        drained["n"] = servers[0].maintenance_worker.poll_once()

    drain_th = threading.Thread(target=drain, daemon=True)
    drain_th.start()

    # foreground probe reads WHILE the drain runs: these must all
    # succeed (fresh-lookup retry allowed)
    failures = 0
    deadline = time.monotonic() + 60.0
    i = 0
    while (drain_th.is_alive() or i < 20) and time.monotonic() < deadline:
        fid = fids[i % len(fids)]
        try:
            assert _read(master, fid) == stored[fid]
        except RpcError:
            failures += 1
        i += 1
    drain_th.join(timeout=30.0)
    stop.set()
    for th in storm_threads:
        th.join(timeout=5.0)

    assert not drain_th.is_alive(), "drain never completed"
    assert drained["n"] == 1, "worker leased no scale.drain job"
    assert failures == 0, f"{failures} foreground reads failed mid-drain"

    # the victim left the topology; the survivor holds everything
    servers[0].heartbeat_once()
    status = call(master.address, "/dir/status")
    urls = [n["url"] for dc in status["datacenters"]
            for rack in dc["racks"] for n in rack["nodes"]]
    assert victim_url not in urls
    assert urls == [servers[0].store.url]
    for fid, payload in stored.items():
        assert _read(master, fid) == payload
