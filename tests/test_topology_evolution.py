"""Online topology evolution: consensus-safe raft membership changes
(learner join -> catch-up -> promotion, clean removals, zombie
rejection) and live filer shard split/merge (two-phase dual-write
handover) — including the chaos drills: leader killed mid-split,
learner crashed mid-catch-up, granting store-server crashed mid-dump.
Nothing acked may be lost at any point.
"""

import json
import socket
import threading
import time

import pytest

from seaweedfs_tpu.filer.entry import Entry
from seaweedfs_tpu.filer.filer_store import ShardedSqliteStore
from seaweedfs_tpu.filer.store_server import FilerStoreServer
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


def wait_for(pred, timeout=20.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def leaders(masters):
    return [m for m in masters if m.raft.is_leader]


# ---------------------------------------------------------------------------
# Raft membership: learner join, catch-up, promotion, removal
# ---------------------------------------------------------------------------

class TestMembershipGrowth:
    def test_grow_one_to_three_via_learner_join(self, tmp_path):
        """A solo master grows to a 3-voter cluster online: joiners
        enter as learners, catch up past a snapshot boundary, and are
        promoted — while allocations stay strictly increasing."""
        d0 = tmp_path / "m0"
        d0.mkdir()
        m0 = MasterServer(port=0, raft_dir=str(d0), pulse_seconds=0.5,
                          raft_election_timeout=0.3)
        m0.start()
        joiners = []
        allocated = []
        try:
            # cross SNAPSHOT_THRESHOLD so catch-up exercises
            # InstallSnapshot (with its embedded config), not just
            # log replay
            for i in range(80):
                m0.raft.propose({"type": "curator.enqueue",
                                 "now": 10.0 + i,
                                 "job_type": "deep.scrub", "volume": i,
                                 "collection": ""})
            assert m0.raft.snapshot_index > 0
            allocated.append(m0.raft.next_volume_id())

            for i in (1, 2):
                d = tmp_path / f"m{i}"
                d.mkdir()
                m = MasterServer(port=0, raft_dir=str(d),
                                 peers=[m0.address], join=True,
                                 pulse_seconds=0.5,
                                 raft_election_timeout=0.3)
                m.start()
                joiners.append(m)
                # a joiner starts as a NON-voter
                assert m.raft.address not in m.raft.voters

            assert wait_for(
                lambda: all(m.address in m0.raft.voters
                            for m in joiners), timeout=30), \
                (m0.raft.voters, m0.raft.learners)
            assert m0.raft.learners == []
            # allocations kept working and never went backwards
            allocated.append(m0.raft.next_volume_id())
            assert allocated[1] > allocated[0]

            # the promoted voters hold the identical applied history
            want = json.dumps(m0.raft.fsm.snapshot(), sort_keys=True)
            for m in joiners:
                assert wait_for(
                    lambda m=m: m.raft.commit_index
                    == m0.raft.commit_index, timeout=10)
                assert json.dumps(m.raft.fsm.snapshot(),
                                  sort_keys=True) == want
            # and the grown cluster survives the founder's death
            m0.stop()
            assert wait_for(lambda: len(leaders(joiners)) == 1,
                            timeout=30)
            new_leader = leaders(joiners)[0]
            assert new_leader.raft.next_volume_id() > allocated[-1]
        finally:
            for m in joiners:
                m.stop()
            m0.stop()

    def test_learner_crash_mid_catchup_is_reaped(self, tmp_path,
                                                 monkeypatch):
        """A learner that dies before catching up must not squat in the
        config forever: the leader removes it after
        WEED_RAFT_LEARNER_TIMEOUT, and commit quorum never depended on
        it in the first place."""
        monkeypatch.setenv("WEED_RAFT_LEARNER_TIMEOUT", "1.5")
        d0 = tmp_path / "m0"
        d0.mkdir()
        m0 = MasterServer(port=0, raft_dir=str(d0), pulse_seconds=0.5,
                          raft_election_timeout=0.3)
        m0.start()
        try:
            dead = "127.0.0.1:1"  # nothing listens: crash-at-birth
            change = m0.raft.add_server(dead)
            assert change["op"] == "add_learner"
            assert dead in m0.raft.learners
            # a learner is non-voting: the solo leader still commits
            vid = m0.raft.next_volume_id()
            assert vid > 0
            assert wait_for(
                lambda: dead not in m0.raft.learners
                and dead not in m0.raft.voters, timeout=15), \
                m0.raft.status()
            # the reap went through the log like any other change
            assert m0.raft.next_volume_id() > vid
        finally:
            m0.stop()

    def test_one_config_change_in_flight(self, tmp_path):
        """Single-server changes serialize: a second add while one is
        uncommitted is refused (409), never interleaved."""
        from seaweedfs_tpu.master.raft import RaftNode

        d = tmp_path / "solo"
        d.mkdir()
        node = RaftNode("127.0.0.1:7001", [], state_dir=str(d))
        node.start()
        # no transport runs: an add to an unreachable peer stays
        # uncommitted (quorum of 1 commits it though) — so instead
        # exercise the guard directly against a fabricated in-flight
        # entry
        node.log.append({"index": node._last_index() + 1,
                         "term": node.term,
                         "cmd": {"type": "raft.config", "op": "add",
                                 "address": "x",
                                 "voters": ["127.0.0.1:7001", "x"],
                                 "learners": []}})
        node._refresh_config()
        with pytest.raises(RpcError) as ei:
            node.add_server("127.0.0.1:7002")
        assert ei.value.status == 409
        node.stop()


class TestMembershipRemoval:
    def _trio(self, tmp_path, election=0.3):
        ports = free_ports(3)
        addrs = [f"127.0.0.1:{p}" for p in ports]
        masters = []
        for i, p in enumerate(ports):
            d = tmp_path / f"rm{i}"
            d.mkdir()
            m = MasterServer(port=p, peers=list(addrs),
                             raft_dir=str(d),
                             raft_election_timeout=election,
                             pulse_seconds=0.5)
            m.start()
            masters.append(m)
        return masters

    def test_removed_ex_leader_demotes_and_is_rejected(self, tmp_path):
        """Remove the LEADER through the log: it finishes replicating
        its own removal, steps down to a single-node observer, and the
        survivors reject its stale RPCs without adopting its term."""
        masters = self._trio(tmp_path)
        try:
            assert wait_for(lambda: len(leaders(masters)) == 1)
            leader = leaders(masters)[0]
            rest = [m for m in masters if m is not leader]

            leader.raft.remove_server(leader.address, reason="drain")
            assert wait_for(lambda: leader.raft.observer, timeout=15)
            assert leader.raft.voters == [leader.address]
            assert not leader.raft.is_leader
            # survivors elect among themselves and keep committing
            assert wait_for(lambda: len(leaders(rest)) == 1,
                            timeout=30)
            assert leaders(rest)[0].raft.next_volume_id() > 0

            # a zombie heartbeat from the removed ex-leader is turned
            # away by the `removed` marker — term NOT adopted
            survivor = rest[0].raft
            # the marker is set where the removal is known COMMITTED;
            # a follower learns that from its new leader's next
            # heartbeat, which on a loaded box had not always come yet
            assert wait_for(
                lambda: leader.address in survivor._expelled, timeout=30)
            before = survivor.term
            r = survivor.handle_append_entries(
                {"term": before + 100, "leader": leader.address,
                 "prev_index": 0, "prev_term": 0, "entries": [],
                 "commit_index": 0})
            assert r.get("removed") and not r.get("ok")
            assert survivor.term == before
            v = survivor.handle_request_vote(
                {"term": before + 100, "candidate": leader.address,
                 "last_index": 10 ** 6, "last_term": before + 100})
            assert v.get("removed") and not v.get("granted")
            assert survivor.term == before
        finally:
            for m in masters:
                m.stop()

    def test_set_peers_removal_edge_regression(self, tmp_path):
        """The legacy set_peers broadcast path: reconfiguring every
        node to a list excluding the current leader demotes it to a
        single-node observer (it must NOT keep campaigning against the
        survivors with its old term)."""
        masters = self._trio(tmp_path)
        try:
            assert wait_for(lambda: len(leaders(masters)) == 1)
            leader = leaders(masters)[0]
            rest = [m for m in masters if m is not leader]
            remaining = [m.address for m in rest]
            for m in masters:
                m.raft.set_peers(list(remaining))

            assert leader.raft.observer
            assert not leader.raft.is_leader
            assert leader.raft.voters == [leader.address]
            assert wait_for(lambda: len(leaders(rest)) == 1,
                            timeout=30)
            new_leader = leaders(rest)[0]
            assert new_leader.raft.next_volume_id() > 0
            # the ex-leader stays demoted: no term explosion, no
            # leadership flap from its stale campaigns
            t = new_leader.raft.term
            time.sleep(1.5)
            assert new_leader.raft.is_leader
            assert new_leader.raft.term == t
        finally:
            for m in masters:
                m.stop()

    def test_cannot_remove_last_voter(self, tmp_path):
        d = tmp_path / "solo"
        d.mkdir()
        m = MasterServer(port=0, raft_dir=str(d), pulse_seconds=0.5)
        m.start()
        try:
            with pytest.raises(RpcError) as ei:
                call(m.address, "/raft/remove_peer",
                     payload={"address": m.address}, method="POST")
            assert ei.value.status == 400
        finally:
            m.stop()


# ---------------------------------------------------------------------------
# Filer shard split / merge (two-phase, through the replicated FSM)
# ---------------------------------------------------------------------------

@pytest.fixture
def resize_cluster(tmp_path, monkeypatch):
    """1 master + 2 store servers on a 2-slot map (ready to split)."""
    monkeypatch.setenv("WEED_FILER_SHARDS", "2")
    monkeypatch.setenv("WEED_FILER_SHARD_LEASE", "1.0")
    master = MasterServer(port=0, pulse_seconds=0.5)
    master.start()
    stores = []
    for i in range(2):
        s = FilerStoreServer(
            port=0, store=ShardedSqliteStore(str(tmp_path / f"s{i}"),
                                             shard_count=2),
            masters=[master.address])
        s.start()
        stores.append(s)
    stopped = []
    yield master, stores, stopped
    for s in stores:
        if s not in stopped:
            s.stop()
    master.stop()


def _insert(stores, path, timeout=5.0):
    for s in stores:
        try:
            call(s.address, "/store/insert",
                 payload=Entry(full_path=path).to_dict(),
                 method="POST", timeout=timeout)
            return True
        except RpcError:
            continue
    return False


def _readable(stores, path):
    for s in stores:
        try:
            call(s.address, "/store/find?path=" + path, timeout=5)
            return True
        except RpcError:
            continue
    return False


class TestShardResize:
    def test_split_under_writes_loses_nothing(self, resize_cluster):
        master, stores, _ = resize_cluster
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 2)
        seeds = [f"/pre{i}/obj" for i in range(30)]
        for p in seeds:
            assert _insert(stores, p, timeout=30.0)

        acked, failed = [], [0]
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                p = f"/live{i}/obj"
                ok = False
                # a slot in handover refuses writes until its new holder
                # has its lease: how long that takes is the master's
                # pulse and the box's load, so a write is tried until
                # it lands; the deadline makes a hang fail
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    if _insert(stores, p):
                        ok = True
                        break
                    time.sleep(0.05)
                if ok:
                    acked.append(p)
                else:
                    failed[0] += 1
                i += 1
                time.sleep(0.01)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            r = call(master.address, "/filer/shard_resize",
                     payload={"op": "start", "to": 8}, method="POST")
            assert not r.get("error"), r

            def committed():
                v = call(master.address, "/filer/shards")
                return v["slots"] == 8 and not v.get("resize")

            assert wait_for(committed, timeout=30)
            assert wait_for(
                lambda: sum(len(s._held) for s in stores) == 8,
                timeout=20), [s._held for s in stores]
        finally:
            stop.set()
            t.join(timeout=40)

        assert failed[0] == 0, f"{failed[0]} writes failed mid-split"
        for p in seeds + acked:
            assert _readable(stores, p), \
                f"acked write {p} lost across the split"
        # the stores really run the new layout (not a proxy illusion)
        assert all(s._slots == 8 for s in stores)

    def test_merge_folds_slots_without_loss(self, resize_cluster,
                                            monkeypatch):
        master, stores, _ = resize_cluster
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 2)
        call(master.address, "/filer/shard_resize",
             payload={"op": "start", "to": 8}, method="POST")
        assert wait_for(
            lambda: call(master.address,
                         "/filer/shards")["slots"] == 8, timeout=30)
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 8,
            timeout=20)
        seeds = [f"/merge{i}/obj" for i in range(30)]
        for p in seeds:
            assert _insert(stores, p, timeout=30.0)

        # fold 8 -> 2: every new slot inherits 4 old ones; unowned
        # sources become handover prevs so no entry strands
        call(master.address, "/filer/shard_resize",
             payload={"op": "start", "to": 2}, method="POST")
        assert wait_for(
            lambda: call(master.address,
                         "/filer/shards")["slots"] == 2
            and not call(master.address,
                         "/filer/shards").get("resize"), timeout=30)
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 2,
            timeout=20)
        for p in seeds:
            assert _readable(stores, p), f"{p} lost across the merge"

    def test_resize_validation(self, resize_cluster):
        master, stores, _ = resize_cluster
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 2)
        for bad in (2, 0, 3):  # same count / zero / non-divisible
            with pytest.raises(RpcError) as ei:
                call(master.address, "/filer/shard_resize",
                     payload={"op": "start", "to": bad},
                     method="POST")
            assert ei.value.status == 400, bad

    def test_resize_aborts_when_a_holder_never_acks(self, tmp_path,
                                                    monkeypatch):
        """A resize whose prepare-acks never complete rolls back after
        WEED_SHARD_RESIZE_TIMEOUT instead of wedging the map."""
        monkeypatch.setenv("WEED_FILER_SHARDS", "4")
        monkeypatch.setenv("WEED_SHARD_RESIZE_TIMEOUT", "1.0")
        master = MasterServer(port=0, pulse_seconds=0.3)
        master.start()
        try:
            # a ghost holder leases the map and will never ack
            master.raft.propose({"type": "filer.lease",
                                 "now": time.time(),
                                 "holder": "127.0.0.1:1",
                                 "ttl": 3600.0})
            r = call(master.address, "/filer/shard_resize",
                     payload={"op": "start", "to": 8}, method="POST")
            assert not r.get("error"), r
            assert call(master.address,
                        "/filer/shards")["resize"] is not None
            assert wait_for(
                lambda: call(master.address,
                             "/filer/shards")["resize"] is None,
                timeout=15)
            assert call(master.address, "/filer/shards")["slots"] == 4
        finally:
            master.stop()


# ---------------------------------------------------------------------------
# Chaos drills
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_leader_killed_mid_shard_split(tmp_path, monkeypatch):
    """Kill the raft leader while a 2->8 split is in its prepare
    window: the committed resize survives into the new leader, the
    split completes, writes resume < 5 s, nothing acked is lost."""
    monkeypatch.setenv("WEED_FILER_SHARDS", "2")
    monkeypatch.setenv("WEED_FILER_SHARD_LEASE", "1.0")
    ports = free_ports(3)
    addrs = [f"127.0.0.1:{p}" for p in ports]
    masters = []
    for i, p in enumerate(ports):
        d = tmp_path / f"cm{i}"
        d.mkdir()
        m = MasterServer(port=p, peers=list(addrs), raft_dir=str(d),
                         raft_election_timeout=0.3, pulse_seconds=0.5)
        m.start()
        masters.append(m)
    stores = []
    for i in range(2):
        s = FilerStoreServer(
            port=0, store=ShardedSqliteStore(str(tmp_path / f"cs{i}"),
                                             shard_count=2),
            masters=list(addrs))
        s.start()
        stores.append(s)

    acked, failed = [], [0]
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            p = f"/chaos{i}/obj"
            ok = False
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if _insert(stores, p):
                    ok = True
                    break
                time.sleep(0.05)
            if ok:
                acked.append((p, time.monotonic()))
            else:
                failed[0] += 1
            i += 1
            time.sleep(0.01)

    alive = list(masters)
    t = threading.Thread(target=writer, daemon=True)
    try:
        assert wait_for(lambda: len(leaders(masters)) == 1)
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 2)
        t.start()
        assert wait_for(lambda: len(acked) >= 10, timeout=30)

        leader = leaders(masters)[0]
        r = call(leader.address, "/filer/shard_resize",
                 payload={"op": "start", "to": 8}, method="POST")
        assert not r.get("error"), r
        # the start is committed (propose acks at commit): kill now,
        # inside the prepare window
        alive = [m for m in masters if m is not leader]
        leader.stop()
        t_kill = time.monotonic()

        assert wait_for(lambda: len(leaders(alive)) == 1, timeout=30)

        def committed():
            for m in alive:
                try:
                    v = call(m.address, "/filer/shards", timeout=2)
                    return v["slots"] == 8 and not v.get("resize")
                except RpcError:
                    continue
            return False

        assert wait_for(committed, timeout=40), \
            "split never completed after the leader kill"
        assert wait_for(
            lambda: sum(len(s._held) for s in stores) == 8,
            timeout=20)
        assert wait_for(lambda: any(ts > t_kill + 0.0
                                    for _, ts in acked), timeout=30)
        stop.set()
        t.join(timeout=10)

        # writes resumed across the kill
        after = [ts for _, ts in acked if ts > t_kill]
        assert after, "writes never resumed after the leader kill"
        assert failed[0] == 0, f"{failed[0]} writes failed"
        # zero acked writes lost
        for p, _ in acked:
            assert _readable(stores, p), \
                f"acked write {p} lost across the chaos split"
    finally:
        stop.set()
        if t.is_alive():
            t.join(timeout=10)
        for s in stores:
            s.stop()
        for m in alive:
            m.stop()


@pytest.mark.chaos
def test_granting_server_crash_mid_dump(tmp_path, monkeypatch):
    """Satellite drill: the GRANTING store server dies after a slot
    handover's /store/dump has started but before it finishes.  The
    retried handover converges (crash takeover: slots come up empty
    but writable) and no slot is ever owned by two servers."""
    monkeypatch.setenv("WEED_FILER_SHARD_LEASE", "1.0")
    master = MasterServer(port=0, pulse_seconds=0.5)
    master.start()
    s1 = FilerStoreServer(
        port=0, store=ShardedSqliteStore(str(tmp_path / "g1"),
                                         shard_count=8),
        masters=[master.address])
    s1.start()
    s2 = FilerStoreServer(
        port=0, store=ShardedSqliteStore(str(tmp_path / "g2"),
                                         shard_count=8),
        masters=[master.address])
    try:
        assert wait_for(lambda: len(s1._held) == 8)
        for i in range(24):
            call(s1.address, "/store/insert",
                 payload=Entry(full_path=f"/dump{i}/obj").to_dict(),
                 method="POST")
        # every dump the grantor serves now stalls long enough for the
        # kill below to land mid-transfer
        faults.REGISTRY.configure(
            "latency,ms=600,pct=100,side=server,route=/store/dump*",
            seed=7)
        s2.start()
        # the joiner is granted its fair share and starts pulling
        assert wait_for(lambda: len(s2._map) == 8, timeout=20)
        time.sleep(0.3)  # inside a stalled dump
        # crash the grantor: no release, lease must expire
        s1._lease_stop.set()
        if s1._lease_thread is not None:
            s1._lease_thread.join(timeout=5)
        s1.server.stop()
        faults.REGISTRY.clear()

        assert wait_for(lambda: len(s2._held) == 8, timeout=30), \
            s2._held
        # the master's map never double-assigns a slot (one holder per
        # slot is structural) and it is all s2 now
        shards = call(master.address, "/filer/shards")
        assert set(shards["map"].values()) == {s2.address}
        # availability: every directory is writable again through s2
        for i in range(24):
            call(s2.address, "/store/insert",
                 payload=Entry(
                     full_path=f"/dump{i}/after").to_dict(),
                 method="POST")
            call(s2.address, f"/store/find?path=/dump{i}/after")
    finally:
        faults.REGISTRY.clear()
        s1.store.close()
        s2.stop()
        master.stop()
