"""Whom a served write fans out to is decided from the volume's own
superblock before any call leaves the process (upstream's
getWritableRemoteReplications, weed/topology/store_replicate.go): a
volume on the local store whose placement has one copy is not looked up
at the master; every other volume is, as before.  On a live master and
volume servers: what the master was asked, what the server's
`SeaweedFS_volumeServer_replicate_total{decision}` counted, and what
each holder then has.  No assertion on seconds."""

import re

import pytest

from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc import policy
from seaweedfs_tpu.rpc.http_rpc import RpcError, call
from seaweedfs_tpu.storage.types import parse_file_id
from seaweedfs_tpu.volume_server.server import VolumeServer

FAMILY = "SeaweedFS_volumeServer_replicate_total"


@pytest.fixture
def cluster(tmp_path):
    """One master, three volume servers on two racks (010 finds another
    rack from any of them; 001 lands on the rack that has two)."""
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=0.2)
    master.start()
    servers = {}
    for i in range(3):
        d = tmp_path / f"vs{i}"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          rack=f"rack{i % 2}", pulse_seconds=0.2)
        vs.start()
        vs.heartbeat_once()
        servers[vs.store.url] = vs
    yield master, servers
    for vs in servers.values():
        vs.stop()
    master.stop()


def _samples(addr, family, **labels):
    """The sum of the family's samples with these labels in a scrape of
    `addr`, None where the scrape has no such sample."""
    text = call(addr, "/metrics", parse=False).decode()
    picked = []
    for line in text.splitlines():
        m = re.match(r"(\w+)\{(.*)\} (\S+)$", line)
        if m and m.group(1) == family:
            have = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2)))
            if all(have.get(k) == v for k, v in labels.items()):
                picked.append(float(m.group(3)))
    return sum(picked) if picked else None


def _decisions(addr):
    return {d: _samples(addr, FAMILY, decision=d) or 0.0
            for d in ("single_copy", "asked", "fanned_out")}


class _Watch:
    """What one operation cost: the master's `/dir/lookup` requests and
    the replicate counter's three decisions, before and after it.  The
    registry is the process's, so any daemon's scrape has every one."""

    def __init__(self, master):
        self.addr = master.address
        self.before = self._read()

    def _read(self):
        # the scrape brings rpc_server_requests_total up to the counts
        return dict(_decisions(self.addr), lookups=_samples(
            self.addr, "SeaweedFS_rpc_server_requests_total",
            service="master", route="/dir/lookup", requests="all") or 0.0)

    def delta(self):
        after = self._read()
        return {k: after[k] - self.before[k] for k in after}


def _assign(master, replication="000"):
    a = call(master.address, f"/dir/assign?replication={replication}")
    return a["fid"], a["url"], int(a["fid"].split(",")[0])


def _holders(master, vid):
    found = call(master.address, f"/dir/lookup?volumeId={vid}")
    return [loc["url"] for loc in found["locations"]]


def _has(url, fid, body):
    try:
        return call(url, f"/{fid}") == body
    except RpcError as e:
        assert e.status == 404
        return False


def _fetch_write(tmp_path, url, fid, vid):
    """`remote.cache`'s call, from a remote of the `local` kind."""
    root = tmp_path / "remote" / "bkt"
    root.mkdir(parents=True)
    (root / "obj.bin").write_bytes(b"fetched " * 64)
    _, nid, cookie = parse_file_id(fid)
    up = call(url, "/admin/remote/fetch_write", {
        "volume": vid, "needle_id": nid, "cookie": cookie,
        "remote_conf": {"name": "r", "type": "local",
                        "directory": str(tmp_path / "remote")},
        "remote_location": "r/bkt/obj.bin"})
    assert up["size"] == 512
    return b"fetched " * 64


# -- (a) one copy: nobody is asked --------------------------------------------

@pytest.mark.parametrize("op", ["PUT", "DELETE", "remote_fetch_write"])
def test_single_copy_volume_never_asks_the_master(cluster, tmp_path, op):
    master, servers = cluster
    fid, url, vid = _assign(master)
    body = b"one copy"
    if op == "DELETE":
        call(url, f"/{fid}", raw=body, method="POST")
    watch = _Watch(master)
    if op == "PUT":
        call(url, f"/{fid}", raw=body, method="POST")
    elif op == "DELETE":
        call(url, f"/{fid}", method="DELETE")
    else:
        body = _fetch_write(tmp_path, url, fid, vid)
    assert watch.delta() == {"single_copy": 1, "asked": 0,
                             "fanned_out": 0, "lookups": 0}
    assert _has(url, fid, body) == (op != "DELETE")


def test_a_scrape_before_any_write_reads_zero_not_nothing(cluster):
    master, servers = cluster
    # other tests of this process may have counted; the samples exist
    for decision in ("single_copy", "asked"):
        assert _samples(next(iter(servers)), FAMILY,
                        decision=decision) is not None


# -- (b) more than one copy: asked, and every holder written ------------------

@pytest.mark.parametrize("op", ["PUT", "DELETE"])
@pytest.mark.parametrize("replication", ["010", "001"])
def test_replicated_volume_asks_and_fans_out(cluster, replication, op):
    master, servers = cluster
    fid, url, vid = _assign(master, replication)
    holders = _holders(master, vid)
    assert len(holders) == 2 and url in holders
    body = b"two copies " + replication.encode()
    if op == "DELETE":
        call(url, f"/{fid}", raw=body, method="POST")
        assert all(_has(u, fid, body) for u in holders)
    watch = _Watch(master)
    if op == "PUT":
        call(url, f"/{fid}", raw=body, method="POST",
             headers={"Content-Type": "text/plain",
                      "X-File-Name": "two.txt"})
    else:
        call(url, f"/{fid}", method="DELETE")
    assert watch.delta() == {"single_copy": 0, "asked": 1,
                             "fanned_out": 1, "lookups": 1}
    for u in holders:
        assert _has(u, fid, body) == (op == "PUT")


# -- (c) the placement is read at every call ----------------------------------

def _configure(url, vid, replication):
    out = call(url, "/admin/volume/configure_replication",
               {"volume": vid, "replication": replication})
    assert out["replication"] == replication


def test_placement_raised_on_a_live_volume_is_followed_and_back(cluster):
    master, servers = cluster
    fid, url, vid = _assign(master)
    call(url, f"/{fid}", raw=b"v1", method="POST")
    _configure(url, vid, "001")
    watch = _Watch(master)
    call(url, f"/{fid}", raw=b"v2", method="POST")
    # asked; the master knows one holder, so there is nobody to write
    assert watch.delta() == {"single_copy": 0, "asked": 1,
                             "fanned_out": 0, "lookups": 1}
    _configure(url, vid, "000")
    watch = _Watch(master)
    call(url, f"/{fid}", raw=b"v3", method="POST")
    assert watch.delta() == {"single_copy": 1, "asked": 0,
                             "fanned_out": 0, "lookups": 0}
    assert _has(url, fid, b"v3")


def test_placement_lowered_leaves_the_old_replica_unwritten_and_back(cluster):
    """Upstream's rule off the happy path: a second location that the
    master still lists for a volume whose own placement says one copy is
    not written to."""
    master, servers = cluster
    fid, url, vid = _assign(master, "001")
    other, = [u for u in _holders(master, vid) if u != url]
    _configure(url, vid, "000")
    watch = _Watch(master)
    call(url, f"/{fid}", raw=b"alone", method="POST")
    assert watch.delta() == {"single_copy": 1, "asked": 0,
                             "fanned_out": 0, "lookups": 0}
    assert _has(url, fid, b"alone") and not _has(other, fid, b"alone")
    _configure(url, vid, "001")
    watch = _Watch(master)
    call(url, f"/{fid}", raw=b"both again", method="POST")
    assert watch.delta() == {"single_copy": 0, "asked": 1,
                             "fanned_out": 1, "lookups": 1}
    assert _has(url, fid, b"both again") and _has(other, fid, b"both again")


# -- (d) a single-copy write needs no master ----------------------------------

@pytest.mark.parametrize("op", ["PUT", "DELETE"])
def test_single_copy_write_is_acknowledged_with_the_master_gone(
        cluster, monkeypatch, op):
    master, servers = cluster
    fid, url, vid = _assign(master)
    if op == "DELETE":
        call(url, f"/{fid}", raw=b"orphan", method="POST")
    def retries():
        return _samples(url, "SeaweedFS_rpc_retries_total",
                        route="/dir/lookup") or 0.0

    master.stop()
    outbound = []
    real = policy.call_policy

    def counted(addr, path, *a, **kw):
        outbound.append((addr, path))
        return real(addr, path, *a, **kw)

    monkeypatch.setattr(policy, "call_policy", counted)
    before, retried = _decisions(url), retries()
    if op == "PUT":
        ack = call(url, f"/{fid}", raw=b"orphan", method="POST")
        assert ack["size"] > 0
    else:
        call(url, f"/{fid}", method="DELETE")
    # the heartbeat loops keep calling the dead master through
    # call_policy and feed its breaker; no handler joined them: no
    # lookup was sent, so none was retried and none met the breaker
    assert [p for _, p in outbound if p.startswith("/dir/lookup")] == []
    after = _decisions(url)
    assert after == dict(before, single_copy=before["single_copy"] + 1)
    assert retries() == retried
    assert _has(url, fid, b"orphan") == (op == "PUT")


# -- (e) a replica that fails still fails the request -------------------------

@pytest.mark.parametrize("replication", ["010", "001"])
def test_replicated_write_fails_when_a_replica_refuses(cluster, replication):
    master, servers = cluster
    fid, url, vid = _assign(master, replication)
    other, = [u for u in _holders(master, vid) if u != url]
    servers[other].store.mark_volume_readonly(vid, True)
    watch = _Watch(master)
    with pytest.raises(RpcError):
        call(url, f"/{fid}", raw=b"refused", method="POST")
    assert watch.delta() == {"single_copy": 0, "asked": 1,
                             "fanned_out": 0, "lookups": 1}
    assert not _has(other, fid, b"refused")
