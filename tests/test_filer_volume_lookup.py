"""The filer finds a volume's holders in its master client's map (PR 47).

  * N chunk misses on one volume ask the master's `/dir/lookup` once, a
    three-chunk object through the S3 gateway at most once a volume, and
    with the watch feed running the map is filled without a question;
  * a map entry that names a dead or a wrong holder: the read answers the
    right bytes and the delete reaches the true holder after one question
    more, and `filer_volume_lookup_total{result="stale"}` counts it;
  * a needle that is gone is still a 404, an unknown volume an `RpcError`
    404;
  * a `/dir/watch` delta that removes a volume's last location makes the
    next lookup ask, a change of `feed_id` and a resync clear the map;
  * `FilerServer.stop()` stops the watch loop and does not wait for its
    long poll."""

import http.client
import socket
import time

import pytest

from seaweedfs_tpu.filer.server import FilerServer
from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc.http_rpc import RpcError, call
from seaweedfs_tpu.s3api.server import S3ApiServer
from seaweedfs_tpu.stats.metrics import REGISTRY
from seaweedfs_tpu.volume_server.server import VolumeServer
from seaweedfs_tpu.wdclient.masterclient import MasterClient


class Cluster:
    """A master that counts its `/dir/lookup` questions, two volume
    servers that answer 404 for a volume they do not hold (readMode
    local: the default, proxy, would hide a wrong holder behind a relay),
    and filers on demand."""

    def __init__(self, tmp_path):
        self.master = MasterServer(port=0, pulse_seconds=0.2)
        self.asked: list[int] = []
        inner = self.master._handle_lookup

        def counted(req):
            self.asked.append(int(req.param("volumeId")))
            return inner(req)

        self.master.server.add("GET", "/dir/lookup", counted)
        self.master.start()
        self.servers = []
        for i in range(2):
            (tmp_path / f"v{i}").mkdir()
            vs = VolumeServer([str(tmp_path / f"v{i}")], self.master.address,
                              port=0, pulse_seconds=0.2, read_mode="local")
            vs.start()
            vs.heartbeat_once()
            self.servers.append(vs)
        self.stoppables = []

    def filer(self, **kwargs) -> FilerServer:
        f = FilerServer(self.master.address, port=0, save_to_filer_limit=0,
                        **kwargs)
        f.start()
        self.stoppables.append(f)
        return f

    def holder(self, fid: str) -> VolumeServer:
        vid = int(fid.split(",")[0])
        return next(vs for vs in self.servers
                    if vs.store.find_volume(vid) is not None)

    def other(self, fid: str) -> VolumeServer:
        return next(vs for vs in self.servers if vs is not self.holder(fid))

    def stop(self):
        for s in reversed(self.stoppables):
            s.stop()
        for vs in self.servers:
            vs.stop()
        self.master.stop()


@pytest.fixture()
def cluster(tmp_path):
    c = Cluster(tmp_path)
    yield c
    c.stop()


@pytest.fixture()
def no_feed(monkeypatch):
    """The map without its watch loop: what a lookup caches, alone."""
    monkeypatch.setattr(MasterClient, "start", lambda self: None)


def ask(address, method, path, body=None):
    host, port = address.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def lookups(result: str) -> float:
    want = f'SeaweedFS_filer_volume_lookup_total{{result="{result}"}}'
    for line in REGISTRY.expose().splitlines():
        if line.rsplit(" ", 1)[0] == want:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def wait_for(cond, seconds=10.0):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def dead_address() -> str:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return "127.0.0.1:%d" % s.getsockname()[1]


def point_map_at(f: FilerServer, fid: str, url: str):
    f._master_client.vid_map.set(int(fid.split(",")[0]),
                                 [{"url": url, "publicUrl": url}])


def stale_holder(cluster, fid: str, kind: str) -> str:
    return dead_address() if kind == "dead" else cluster.other(fid).address


def deleted_needles(vs) -> int:
    return sum(v["delete_count"]
               for v in vs.store.collect_heartbeat()["volumes"])


# -- (a) the master is asked once a volume -------------------------------------

def test_n_chunk_misses_on_one_volume_ask_the_master_once(cluster, no_feed):
    f = cluster.filer()
    bodies = {f"/r/k{i}": b"%d-" % i * 200 for i in range(24)}
    vids = set()
    for path, body in bodies.items():
        assert ask(f.address, "POST", path, body)[0] == 200
        vids.add(int(f.filer.find_entry(path).chunks[0].fid.split(",")[0]))
    assert len(f._master_client.vid_map) == 0 and cluster.asked == []
    hit0, miss0 = lookups("hit"), lookups("miss")
    for path, body in bodies.items():
        assert ask(f.address, "GET", path) == (200, body)
    assert sorted(cluster.asked) == sorted(vids)
    assert lookups("miss") - miss0 == len(vids)
    assert lookups("hit") - hit0 == len(bodies) - len(vids)
    assert len(vids) < len(bodies)


def test_the_gateway_s_get_asks_at_most_once_a_volume(cluster, no_feed):
    f = cluster.filer(chunk_size=1024)
    s3 = S3ApiServer(f, port=0)
    s3.start()
    cluster.stoppables.append(s3)
    body = bytes(range(256)) * 12          # three chunks of 1 KiB
    assert ask(s3.address, "PUT", "/b")[0] == 200
    assert ask(s3.address, "PUT", "/b/k", body)[0] == 200
    chunks = f.filer.find_entry("/buckets/b/k").chunks
    assert len(chunks) == 3
    vids = {int(c.fid.split(",")[0]) for c in chunks}
    del cluster.asked[:]
    f._master_client.vid_map.clear()
    assert ask(s3.address, "GET", "/b/k") == (200, body)
    # three fetches side by side may each find the map empty for a volume
    # they share; never more than one question a chunk, and none after
    assert set(cluster.asked) == vids and len(cluster.asked) <= 3
    del cluster.asked[:]
    for c in chunks:
        f.chunk_cache.invalidate(c.fid, reason="test")
    assert ask(s3.address, "GET", "/b/k") == (200, body)
    assert cluster.asked == []


def test_the_watch_feed_fills_the_map_and_no_question_is_asked(cluster):
    f = cluster.filer()
    body = b"fed" * 100
    assert ask(f.address, "POST", "/r/fed", body)[0] == 200
    vid = int(f.filer.find_entry("/r/fed").chunks[0].fid.split(",")[0])
    assert wait_for(lambda: f._master_client.vid_map.get(vid))
    miss0 = lookups("miss")
    assert ask(f.address, "GET", "/r/fed") == (200, body)
    assert cluster.asked == [] and lookups("miss") == miss0


# -- (b), (c) a stale entry ----------------------------------------------------

@pytest.mark.parametrize("kind", ["dead", "wrong"])
def test_a_read_through_a_stale_entry_answers_after_one_question(
        cluster, no_feed, kind):
    f = cluster.filer()
    body = b"stale-read-" * 90
    assert ask(f.address, "POST", "/r/s", body)[0] == 200
    fid = f.filer.find_entry("/r/s").chunks[0].fid
    point_map_at(f, fid, stale_holder(cluster, fid, kind))
    stale0 = lookups("stale")
    assert ask(f.address, "GET", "/r/s") == (200, body)
    assert cluster.asked == [int(fid.split(",")[0])]
    assert lookups("stale") - stale0 == 1
    assert f._lookup_urls(fid) == [cluster.holder(fid).address]


@pytest.mark.parametrize("kind", ["dead", "wrong"])
def test_a_delete_through_a_stale_entry_reaches_the_true_holder(
        cluster, no_feed, kind):
    f = cluster.filer()
    assert ask(f.address, "POST", "/r/d", b"to-delete" * 100)[0] == 200
    entry = f.filer.find_entry("/r/d")
    fid = entry.chunks[0].fid
    true = cluster.holder(fid)
    point_map_at(f, fid, stale_holder(cluster, fid, kind))
    stale0 = lookups("stale")
    f._delete_chunks(entry.chunks)
    assert deleted_needles(true) == 1
    assert cluster.asked == [int(fid.split(",")[0])]
    assert lookups("stale") - stale0 == 1
    with pytest.raises(RpcError) as e:
        call(true.address, f"/{fid}")
    assert e.value.status == 404


@pytest.mark.parametrize("kind", ["dead", "wrong"])
def test_a_proxied_chunk_through_a_stale_entry(cluster, no_feed, kind):
    f = cluster.filer()
    body = b"proxied" * 120
    assert ask(f.address, "POST", "/r/p", body)[0] == 200
    fid = f.filer.find_entry("/r/p").chunks[0].fid
    point_map_at(f, fid, stale_holder(cluster, fid, kind))
    assert ask(f.address, "GET", f"/?proxyChunkId={fid}") == (200, body)
    assert cluster.asked == [int(fid.split(",")[0])]


def test_a_holder_that_fails_right_after_the_master_named_it_is_not_asked_about_again(
        cluster, no_feed):
    f = cluster.filer()
    assert ask(f.address, "POST", "/r/g", b"gone" * 300)[0] == 200
    fid = f.filer.find_entry("/r/g").chunks[0].fid
    call(cluster.holder(fid).address, f"/{fid}", method="DELETE")
    assert ask(f.address, "GET", "/r/g")[0] == 404     # the map was empty
    assert cluster.asked == [int(fid.split(",")[0])]


# -- (d) a needle that is gone -------------------------------------------------

def test_a_gone_needle_is_a_404_and_costs_one_question(cluster, no_feed):
    f = cluster.filer()
    assert ask(f.address, "POST", "/r/g", b"gone" * 300)[0] == 200
    fid = f.filer.find_entry("/r/g").chunks[0].fid
    true = cluster.holder(fid)
    assert f._lookup_urls(fid) == [true.address]       # now cached
    call(true.address, f"/{fid}", method="DELETE")
    del cluster.asked[:]
    stale0 = lookups("stale")
    assert ask(f.address, "GET", "/r/g")[0] == 404
    # the holder's 404 does not say whether the volume or the needle is
    # missing: the master is asked once, names the same holder, and the
    # 404 stands without a second fetch and without a stale count
    assert cluster.asked == [int(fid.split(",")[0])]
    assert lookups("stale") == stale0
    assert ask(f.address, "GET", "/r/g")[0] == 404
    assert len(cluster.asked) == 2


# -- (g) an unknown volume -----------------------------------------------------

@pytest.mark.parametrize("lookup", ["_lookup_url", "_lookup_urls"])
def test_an_unknown_volume_is_an_rpc_error_404(cluster, lookup):
    f = cluster.filer()
    with pytest.raises(RpcError) as e:
        getattr(f, lookup)("999,01deadbeef")
    assert e.value.status == 404
    assert f._master_client.vid_map.get(999) == []     # nothing cached


def test_a_malformed_file_id_is_refused_and_asks_nobody(cluster):
    f = cluster.filer()
    with pytest.raises(RpcError) as e:
        f._lookup_urls("not-a-fid")
    assert e.value.status == 400
    assert ask(f.address, "GET", "/?proxyChunkId=x,1")[0] == 400
    assert cluster.asked == []


def test_an_answer_without_holders_is_a_404_and_is_not_cached(
        cluster, monkeypatch):
    f = cluster.filer()
    monkeypatch.setattr(f._master_client, "_call_any",
                        lambda path, **kw: {"volumeId": "998"})
    with pytest.raises(RpcError) as e:
        f._lookup_url("998,01deadbeef")
    assert e.value.status == 404
    assert f._master_client.vid_map.get(998) == []


# -- (e) what the feed does to the map -----------------------------------------

LOC = {"url": "10.0.0.1:8080", "publicUrl": "10.0.0.1:8080"}


def fed_client() -> MasterClient:
    mc = MasterClient("127.0.0.1:1", name="t")
    mc._apply_watch_reply({"feed_id": "m1/1", "seq": 2, "deltas": [
        {"op": "add", "volume": 7, **LOC},
        {"op": "add", "volume": 8, **LOC}]})
    assert mc.vid_map.get(7) == [LOC] and mc.vid_map.get(8) == [LOC]
    return mc


@pytest.mark.parametrize("reply,gone,kept", [
    ({"feed_id": "m1/1", "seq": 3, "deltas": [
        {"op": "remove", "volume": 7, **LOC}]}, [7], [8]),
    ({"feed_id": "m2/9", "seq": 1, "deltas": []}, [7, 8], []),
    ({"feed_id": "m1/1", "seq": 9, "deltas": [], "resync": True},
     [7, 8], []),
], ids=["remove-delta", "feed-id-change", "resync"])
def test_the_feed_drops_what_the_next_lookup_then_asks_for(
        reply, gone, kept):
    mc = fed_client()
    mc._apply_watch_reply(reply)
    asked = []

    def master(path, **kw):
        asked.append(path)
        return {"locations": [LOC]}

    mc._call_any = master
    for vid in kept:
        assert mc.lookup(vid) == [LOC]
    assert asked == []
    for vid in gone:
        assert mc.lookup(vid) == [LOC]
    assert asked == [f"/dir/lookup?volumeId={vid}" for vid in gone]


def test_invalidate_makes_the_next_lookup_ask():
    mc = fed_client()
    asked = []
    mc._call_any = lambda path, **kw: (asked.append((path, kw)),
                                       {"locations": [LOC]})[1]
    mc.invalidate(7)
    mc.invalidate(99)                                   # unknown: no error
    assert mc.vid_map.get(7) == [] and mc.vid_map.get(8) == [LOC]
    assert mc.lookup(7, timeout=10) == [LOC]
    assert asked == [("/dir/lookup?volumeId=7", {"timeout": 10})]
    assert mc.lookup(7) == [LOC] and len(asked) == 1


def test_a_volume_that_left_its_server_leaves_the_filer_s_map(cluster):
    f = cluster.filer()
    assert ask(f.address, "POST", "/r/l", b"leaves" * 100)[0] == 200
    fid = f.filer.find_entry("/r/l").chunks[0].fid
    vid = int(fid.split(",")[0])
    assert wait_for(lambda: f._master_client.vid_map.get(vid))
    true = cluster.holder(fid)
    true.store.delete_volume(vid)
    true.heartbeat_once()
    assert wait_for(lambda: not f._master_client.vid_map.get(vid))
    assert ask(f.address, "GET", "/r/l")[0] == 404
    assert cluster.asked == [vid]


# -- (f) stop ------------------------------------------------------------------

def test_stop_stops_the_watch_loop_and_does_not_wait_for_its_poll(cluster):
    f = cluster.filer()
    loop = f._master_client._thread
    assert loop is not None and loop.is_alive() and loop.daemon
    # let the loop settle into its 15 s long poll
    assert ask(f.address, "POST", "/r/q", b"q" * 64)[0] == 200
    time.sleep(0.3)
    cluster.stoppables.remove(f)
    t0 = time.monotonic()
    f.stop()
    assert time.monotonic() - t0 < 5.0
    assert f._master_client._stop.is_set()
    # the poll in flight ends with the next delta (or its 15 s): the loop
    # then exits without asking again
    cluster.master._record_change({"op": "add", "volume": 4242,
                                   "url": "10.0.0.9:1",
                                   "publicUrl": "10.0.0.9:1"})
    loop.join(5.0)
    assert not loop.is_alive()


# -- a poll is a wait, not a slow request --------------------------------------

def test_the_loop_s_idle_polls_leave_no_trace(cluster, monkeypatch):
    from seaweedfs_tpu import tracing

    monkeypatch.setenv("WEED_TRACE_SAMPLE", "1")
    monkeypatch.setenv("WEED_TRACE_SLOW_MS", "100")
    f = cluster.filer()
    assert ask(f.address, "POST", "/r/t", b"t" * 64)[0] == 200
    time.sleep(0.3)
    tracing.RECORDER.reset()
    time.sleep(0.3)     # the poll in flight waits three slow thresholds
    cluster.master._record_change({"op": "add", "volume": 4343,
                                   "url": "10.0.0.9:1",
                                   "publicUrl": "10.0.0.9:1"})
    assert wait_for(lambda: f._master_client.vid_map.get(4343))
    time.sleep(0.1)     # the handler's span is finished after its reply
    kept = call(cluster.master.address, "/debug/traces")["traces"]
    assert [t["root"] for t in kept if "/dir/watch" in t["root"]] == []
