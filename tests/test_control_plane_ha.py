"""Control-plane HA: the command-typed replicated FSM, curator-queue
failover, crash-atomic journal compaction, and the leader-kill chaos
slice (tier-1: a raft leader dies mid write-storm and the cluster must
resume writes in < 5 s without losing one acked write or curator job).
"""

import json
import os
import socket
import time

import pytest

from seaweedfs_tpu.maintenance.queue import JobQueue
from seaweedfs_tpu.master.fsm import ControlFSM
from seaweedfs_tpu.master.raft import RaftNode
from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc.http_rpc import RpcError, call
from seaweedfs_tpu.util import faults


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


def wait_for(pred, timeout=10.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def leaders(masters):
    return [m for m in masters if m.raft.is_leader]


def start_trio(tmp_path, election=0.4):
    ports = free_ports(3)
    addrs = [f"127.0.0.1:{p}" for p in ports]
    masters = []
    for i, p in enumerate(ports):
        d = tmp_path / f"ham{i}"
        d.mkdir()
        m = MasterServer(port=p, peers=list(addrs), raft_dir=str(d),
                         raft_election_timeout=election,
                         pulse_seconds=0.5)
        m.start()
        masters.append(m)
    return masters


# ---------------------------------------------------------------------------
# FSM determinism: replaying the same command sequence — or a snapshot
# plus the suffix — must yield byte-identical state on any node.
# ---------------------------------------------------------------------------

def _command_script():
    """A fixed command sequence covering every FSM command type, with
    pinned timestamps (commands carry their own `now`)."""
    cmds = [
        {"type": "volume.assign", "value": 1, "now": 100.0},
        {"type": "volume.assign", "value": 2, "now": 101.0},
        {"type": "topology.epoch", "now": 102.0},
        {"type": "curator.enqueue", "now": 103.0,
         "job_type": "deep.scrub", "volume": 7, "collection": "photos",
         "params": {"reason": "stale"}},
        {"type": "curator.enqueue", "now": 104.0,
         "job_type": "ec.rebuild", "volume": 9, "collection": "",
         "params": {"shard": 3}},
        {"type": "curator.lease", "now": 105.0, "worker": "w1",
         "limit": 1, "lease_seconds": 30.0},
        {"type": "curator.renew", "now": 110.0, "id": "j2",
         "worker": "w1", "lease_seconds": 30.0},
        {"type": "curator.fail", "now": 115.0, "id": "j2",
         "worker": "w1", "error": "disk gone", "max_attempts": 5,
         "backoff": 5.0},
        {"type": "curator.enqueue", "now": 116.0,
         "job_type": "deep.scrub", "volume": 8, "collection": ""},
        {"type": "curator.lease", "now": 117.0, "worker": "w2",
         "limit": 2, "lease_seconds": 30.0},
        {"type": "curator.done", "now": 120.0, "id": "j1",
         "worker": "w2", "outcome": "ok"},
        {"type": "curator.expire", "now": 200.0},
        {"type": "curator.pause", "now": 201.0, "paused": True},
        {"type": "curator.pause", "now": 202.0, "paused": False},
        {"type": "filer.lease", "now": 203.0,
         "holder": "127.0.0.1:7101", "ttl": 10.0},
        {"type": "filer.lease", "now": 204.0,
         "holder": "127.0.0.1:7102", "ttl": 10.0},
        {"type": "volume.assign", "value": 3, "now": 205.0},
        {"type": "filer.lease", "now": 206.0,
         "holder": "127.0.0.1:7101", "release": True},
        {"type": "topology.epoch", "now": 207.0},
    ]
    return cmds


class TestFSMDeterminism:
    def test_full_replay_identical(self):
        a, b = ControlFSM(), ControlFSM()
        for cmd in _command_script():
            a.apply(cmd)
            b.apply(cmd)
        assert json.dumps(a.snapshot(), sort_keys=True) == \
            json.dumps(b.snapshot(), sort_keys=True)

    def test_snapshot_plus_suffix_identical(self):
        """restore(snapshot at midpoint) + suffix == full replay — the
        exact path a follower takes after InstallSnapshot."""
        cmds = _command_script()
        full = ControlFSM()
        for cmd in cmds:
            full.apply(cmd)
        for cut in (1, len(cmds) // 2, len(cmds) - 1):
            head = ControlFSM()
            for cmd in cmds[:cut]:
                head.apply(cmd)
            resumed = ControlFSM()
            resumed.restore(head.snapshot())
            for cmd in cmds[cut:]:
                resumed.apply(cmd)
            assert json.dumps(resumed.snapshot(), sort_keys=True) == \
                json.dumps(full.snapshot(), sort_keys=True), \
                f"divergence when snapshotting after {cut} commands"

    def test_apply_never_raises(self):
        fsm = ControlFSM()
        for cmd in ({}, {"type": "nope"}, {"type": "volume.assign"},
                    {"type": "curator.done", "id": "j999"},
                    {"type": "curator.fail"}, {"type": "filer.lease"},
                    {"type": "volume.assign", "value": "garbage"}):
            assert fsm.apply(dict(cmd)) is None or True  # no exception

    def test_raft_restart_replays_identical_state(self, tmp_path):
        """A restarted single-node raft (snapshot + log suffix from
        disk) must reconstruct the exact FSM, including past the
        compaction threshold."""
        d = tmp_path / "solo"
        d.mkdir()
        node = RaftNode("127.0.0.1:1", [], state_dir=str(d))
        node.start()
        for i in range(80):  # crosses SNAPSHOT_THRESHOLD=64
            node.propose({"type": "curator.enqueue", "now": 50.0 + i,
                          "job_type": "deep.scrub", "volume": i,
                          "collection": ""})
        node.next_volume_id()
        node.propose({"type": "topology.epoch", "now": 900.0})
        node.stop()
        want = json.dumps(node.fsm.snapshot(), sort_keys=True)
        assert node.snapshot_index > 0, "compaction never kicked in"

        reborn = RaftNode("127.0.0.1:1", [], state_dir=str(d))
        assert json.dumps(reborn.fsm.snapshot(), sort_keys=True) == want
        assert reborn.fsm.max_volume_id == node.fsm.max_volume_id


# ---------------------------------------------------------------------------
# Curator queue through raft: every mutation commits on a quorum, so a
# failed-over leader resumes with the identical pending/leased set.
# ---------------------------------------------------------------------------

class TestQueueThroughRaft:
    def test_queue_state_survives_leader_kill(self, tmp_path):
        masters = start_trio(tmp_path)
        try:
            assert wait_for(lambda: len(leaders(masters)) == 1)
            leader = leaders(masters)[0]
            jid1 = leader.raft.propose(
                {"type": "curator.enqueue", "now": 10.0,
                 "job_type": "deep.scrub", "volume": 4,
                 "collection": "photos"})
            jid2 = leader.raft.propose(
                {"type": "curator.enqueue", "now": 11.0,
                 "job_type": "ec.rebuild", "volume": 5,
                 "collection": ""})
            leased = leader.raft.propose(
                {"type": "curator.lease", "now": 12.0, "worker": "w1",
                 "limit": 1, "lease_seconds": 120.0})
            assert jid1 and jid2 and leased
            want = json.dumps(leader.raft.fsm.snapshot()["queue"],
                              sort_keys=True)

            leader.stop()
            rest = [m for m in masters if m is not leader]
            assert wait_for(lambda: len(leaders(rest)) == 1, timeout=60)
            new_leader = leaders(rest)[0]
            got = json.dumps(new_leader.raft.fsm.snapshot()["queue"],
                             sort_keys=True)
            assert got == want, \
                "failed-over leader's queue diverged from the acked state"
            # and the new leader keeps mutating the same queue
            done = new_leader.raft.propose(
                {"type": "curator.done", "now": 20.0,
                 "id": leased[0]["id"], "worker": "w1",
                 "outcome": "ok"})
            assert done and done["id"] == leased[0]["id"]
        finally:
            for m in masters:
                m.stop()

    def test_follower_rejects_with_leader_hint(self, tmp_path):
        masters = start_trio(tmp_path)
        try:
            # A loaded box can trigger a re-election between sampling
            # the leader and proposing, leaving the follower's hint
            # momentarily unset — retry until a stable round is seen.
            hint = None
            for _ in range(10):
                assert wait_for(lambda: len(leaders(masters)) == 1)
                leader = leaders(masters)[0]
                follower = next(m for m in masters
                                if not m.raft.is_leader)
                with pytest.raises(RpcError) as ei:
                    follower.raft.propose(
                        {"type": "topology.epoch", "now": 1.0})
                assert ei.value.status == 409
                hint = (ei.value.headers or {}).get("X-Raft-Leader")
                if hint == leader.address and leader.raft.is_leader:
                    break
                time.sleep(0.3)
            assert hint == leader.address
        finally:
            for m in masters:
                m.stop()


# ---------------------------------------------------------------------------
# Journal compaction crash-atomicity (the standalone-queue durability
# path: tmp + fsync + rename).
# ---------------------------------------------------------------------------

class TestCompactCrashAtomic:
    def _fill(self, q, n=6):
        for i in range(n):
            q.enqueue("deep.scrub", volume=i, collection="c")

    def test_kill_before_rename_keeps_old_journal(self, tmp_path,
                                                  monkeypatch):
        jpath = str(tmp_path / "maint.jlog")
        q = JobQueue(journal_path=jpath)
        self._fill(q)
        before = open(jpath).read()

        real_replace = os.replace

        def crash_replace(src, dst):
            if dst == jpath:
                raise OSError("simulated kill before rename")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", crash_replace)
        with pytest.raises(OSError):
            q._compact()
        monkeypatch.undo()
        # the journal is byte-identical: the crash hit before the swap
        assert open(jpath).read() == before
        replayed = JobQueue(journal_path=jpath)
        assert sorted(j["id"] for j in replayed.jobs()) == \
            sorted(j["id"] for j in q.jobs())

    def test_compaction_then_replay_is_lossless(self, tmp_path):
        jpath = str(tmp_path / "maint.jlog")
        q = JobQueue(journal_path=jpath)
        self._fill(q, n=8)
        q.lease("w1", limit=2)
        q._compact()
        replayed = JobQueue(journal_path=jpath)
        assert json.dumps(sorted(replayed.jobs(),
                                 key=lambda j: j["id"]),
                          sort_keys=True) == \
            json.dumps(sorted(q.jobs(), key=lambda j: j["id"]),
                       sort_keys=True)


# ---------------------------------------------------------------------------
# Leader-kill chaos slice (tier-1): deterministic fault seed, bounded
# waits, < 5 s write-unavailability, zero acked writes or jobs lost.
# ---------------------------------------------------------------------------

def _run_leader_kill_storm(tmp_path, fault_spec, pre_acks=15,
                           post_acks=15):
    from seaweedfs_tpu.volume_server.server import VolumeServer

    faults.REGISTRY.configure(fault_spec, seed=42)
    masters = start_trio(tmp_path, election=0.3)
    addrs = [m.address for m in masters]
    vols = []
    for i in range(2):
        vd = tmp_path / f"vol{i}"
        vd.mkdir()
        vs = VolumeServer([str(vd)], ",".join(addrs), port=0,
                          pulse_seconds=0.3, max_volume_counts=[8])
        vs.start()
        vs.heartbeat_once()
        vols.append(vs)

    acked = {}  # fid -> (url, payload)
    alive = list(masters)

    def write_once(i):
        payload = f"needle-{i}".encode() * 16
        for m in alive:
            try:
                a = call(m.address, "/dir/assign", timeout=2)
                call(a["url"], f"/{a['fid']}", raw=payload,
                     method="POST", timeout=2)
                acked[a["fid"]] = (a["url"], payload)
                return True
            except RpcError:
                continue
        return False

    try:
        assert wait_for(lambda: len(leaders(masters)) == 1)
        leader = leaders(masters)[0]

        i = 0
        deadline = time.monotonic() + 30
        while len(acked) < pre_acks and time.monotonic() < deadline:
            write_once(i)
            i += 1
        assert len(acked) >= pre_acks, "storm never got going"

        jid = leader.raft.propose(
            {"type": "curator.enqueue", "now": 5.0,
             "job_type": "deep.scrub", "volume": 1, "collection": ""})
        assert jid
        queue_want = json.dumps(
            leader.raft.fsm.snapshot()["queue"], sort_keys=True)

        # -- kill the leader mid-storm ---------------------------------
        alive = [m for m in masters if m is not leader]
        leader.stop()
        t_kill = time.monotonic()
        resumed_at = None
        while time.monotonic() < t_kill + 30:
            if write_once(i):
                resumed_at = time.monotonic()
                break
            i += 1
            time.sleep(0.05)
        assert resumed_at is not None, "writes never resumed"

        deadline = time.monotonic() + 30
        target = len(acked) + post_acks
        while len(acked) < target and time.monotonic() < deadline:
            write_once(i)
            i += 1

        # -- no acked write lost: every fid reads back byte-identical --
        assert len(acked) >= pre_acks + post_acks
        fids = list(acked)
        assert len(set(fids)) == len(fids), "duplicate fid acked"
        for fid, (url, payload) in acked.items():
            assert call(url, f"/{fid}", timeout=5) == payload, \
                f"acked write {fid} lost or corrupted after failover"

        # -- no curator job lost: queue state is byte-identical --------
        assert wait_for(lambda: len(leaders(alive)) == 1, timeout=30)
        new_leader = leaders(alive)[0]
        queue_got = json.dumps(
            new_leader.raft.fsm.snapshot()["queue"], sort_keys=True)
        assert queue_got == queue_want, \
            "curator queue diverged across the failover"
        return resumed_at - t_kill
    finally:
        faults.REGISTRY.clear()
        for vs in vols:
            vs.stop()
        for m in alive:
            m.stop()


@pytest.mark.chaos
def test_leader_kill_mid_storm(tmp_path):
    """Tier-1 slice: raft leader killed mid write-storm under a
    deterministic fault seed — writes resume, nothing acked is lost,
    the failed-over curator queue is byte-identical."""
    _run_leader_kill_storm(
        tmp_path, "latency,ms=5,pct=10,side=client,route=/dir/assign*")


@pytest.mark.slow
@pytest.mark.chaos
def test_leader_kill_soak(tmp_path):
    """Soak variant: heavier injected faults and a longer storm."""
    window = _run_leader_kill_storm(
        tmp_path,
        "latency,ms=20,pct=20,side=client;"
        "error,status=503,pct=3,side=client,route=/dir/assign*",
        pre_acks=60, post_acks=60)
    assert window < 5.0
