"""The filer as a record store (PR 45): small records that are
overwritten in place through the stand-alone filer's own HTTP front.

  * `-saveToFilerLimit`: 0 sends every body to a volume server, 2048 (and
    no flag at all) keeps a 1 KB body inside its entry, as before;
  * a record typed `application/json` comes back as the bytes written;
  * two callers overwriting one key leave one caller's whole record, and
    the volume server counts exactly the superseded chunks as deleted;
  * a GET whose chunk an overwrite reclaimed under it reads the entry as
    it then stands, never a 404;
  * the mutation's inside and the filer's front are in `/metrics`."""

import http.client
import json
import threading
import time

import pytest

from seaweedfs_tpu.filer import server as filer_server
from seaweedfs_tpu.filer.filer_store import MemoryStore, SqliteStore
from seaweedfs_tpu.filer.server import FilerServer
from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc.http_rpc import RpcError
from seaweedfs_tpu.stats.metrics import REGISTRY
from seaweedfs_tpu.volume_server.server import VolumeServer

FOLDER = "/ycsb/usertable"
JSON = {"Content-Type": "application/json"}


def record(tag: str) -> bytes:
    return json.dumps({f"field{j}": f"{tag}-{j}-".ljust(100, "x")
                       for j in range(10)}, separators=(",", ":")).encode()


@pytest.fixture()
def cluster(tmp_path):
    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    (tmp_path / "v").mkdir()
    vs = VolumeServer([str(tmp_path / "v")], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    vs.heartbeat_once()
    filers = []

    def filer(**kwargs):
        f = FilerServer(master.address, port=0, **kwargs)
        f.start()
        filers.append(f)
        return f

    yield filer, vs
    for f in filers:
        f.stop()
    vs.stop()
    master.stop()


def ask(filer, method, path, body=None, headers=None):
    host, port = filer.address.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def deleted(vs) -> tuple[int, int]:
    vols = vs.store.collect_heartbeat()["volumes"]
    return (sum(v["delete_count"] for v in vols),
            sum(v["deleted_byte_count"] for v in vols))


def metric(name: str, **labels) -> float:
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in labels.items())
                   + "}" if labels else "")
    for line in REGISTRY.expose().splitlines():
        if line.rsplit(" ", 1)[0] == want:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# -- -saveToFilerLimit ----------------------------------------------------------

@pytest.mark.parametrize("limit,chunks", [(0, 1), (2048, 0), (None, 0),
                                          (1121, 0), (1120, 1)])
def test_a_1_kb_body_becomes_a_chunk_or_stays_inline(cluster, limit, chunks):
    filer, _ = cluster
    f = filer() if limit is None else filer(save_to_filer_limit=limit)
    assert f.save_to_filer_limit == (2048 if limit is None else limit)
    body = record("a")
    assert len(body) == 1121
    status, _, _ = ask(f, "POST", FOLDER + "/k", body, JSON)
    assert status == 200
    entry = f.filer.find_entry(FOLDER + "/k")
    assert len(entry.chunks) == chunks
    assert entry.content == (b"" if chunks else body)
    assert ask(f, "GET", FOLDER + "/k")[2] == body
    # a 3 KB body is a chunk under every limit here
    ask(f, "POST", FOLDER + "/big", body * 3, JSON)
    assert len(f.filer.find_entry(FOLDER + "/big").chunks) == 1


def test_the_default_is_the_module_s_constant_and_the_flag_carries_it():
    import weed

    assert filer_server.INLINE_LIMIT == 2048
    parser_args = ["filer", "-master", "127.0.0.1:1"]
    captured = {}

    def fake(args):
        captured["limit"] = args.saveToFilerLimit

    for extra, want in (([], 2048), (["-saveToFilerLimit", "0"], 0),
                        (["-saveToFilerLimit", "4096"], 4096)):
        old = weed.cmd_filer
        weed.cmd_filer = fake
        try:
            weed.main(parser_args + extra)
        finally:
            weed.cmd_filer = old
        assert captured["limit"] == want


def test_the_gateway_s_embedded_filer_inlines_as_before(cluster):
    filer, _ = cluster
    f = filer()     # as `weed.py s3` and `weed.py server` build it
    entry = f.save_bytes("/buckets/b/small", b"x" * 2048)
    assert entry.content and not entry.chunks
    entry = f.save_bytes("/buckets/b/large", b"x" * 2049)
    assert entry.chunks and not entry.content


# -- a JSON record is content, not a reply to parse ----------------------------------

@pytest.mark.parametrize("body", [
    record("json"), b'{"error": "this is a record, not a failure"}' * 40,
    b"[1, 2, 3]" * 200, b"not json at all" * 100],
    ids=["record", "error-shaped", "array", "plain"])
def test_a_json_typed_record_round_trips_byte_for_byte(cluster, body):
    filer, _ = cluster
    f = filer(save_to_filer_limit=0)
    status, _, reply = ask(f, "POST", FOLDER + "/j", body, JSON)
    assert status == 200 and json.loads(reply)["size"] == len(body)
    for _ in range(2):      # from the volume server, then from the cache
        status, headers, got = ask(f, "GET", FOLDER + "/j")
        assert (status, got) == (200, body)
        assert headers["Content-Type"] == "application/json"
        assert headers["Content-Length"] == str(len(body))
        f.chunk_cache.invalidate(
            f.filer.find_entry(FOLDER + "/j").chunks[0].fid)
    # a chunk that is gone is an error, whatever its bytes looked like
    fid = f.filer.find_entry(FOLDER + "/j").chunks[0].fid
    f.chunk_cache.invalidate(fid)
    from seaweedfs_tpu.rpc.http_rpc import call
    call(f._lookup_url(fid), f"/{fid}", method="DELETE")
    with pytest.raises(RpcError):
        f._fetch_chunk(fid)


# -- overwrites -------------------------------------------------------------------------

@pytest.mark.parametrize("store", ["memory", "sqlite"])
def test_two_callers_overwriting_one_key_leave_one_whole_record(
        cluster, tmp_path, store):
    filer, vs = cluster
    f = filer(save_to_filer_limit=0,
              store=SqliteStore(str(tmp_path / "f.db")) if store == "sqlite"
              else MemoryStore())
    path = FOLDER + "/hot"
    assert ask(f, "POST", path, record("v0"), JSON)[0] == 200
    before = deleted(vs)
    overwrites = metric("SeaweedFS_filer_overwrites_total")
    reclaimed = metric("SeaweedFS_filer_reclaimed_chunks_total")
    sent, failures = {0: [], 1: []}, []
    stop = time.monotonic() + 1.5

    def writer(w):
        n = 0
        while time.monotonic() < stop:
            body = record(f"w{w}-{n}")
            status, _, reply = ask(f, "POST", path, body, JSON)
            if status != 200:
                failures.append((status, reply))
            sent[w].append(body)
            n += 1

    def reader():
        while time.monotonic() < stop:
            status, _, body = ask(f, "GET", path)
            if status != 200 or (body != record("v0") and not any(
                    body in sent[w] or body == record(
                        f"w{w}-{len(sent[w])}") for w in sent)):
                failures.append((status, body[:80]))

    threads = [threading.Thread(target=writer, args=(w,)) for w in sent] \
        + [threading.Thread(target=reader) for _ in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not failures, failures[:3]
    writes = len(sent[0]) + len(sent[1])
    assert writes >= 4
    # one caller's whole last record stands
    final = ask(f, "GET", path)[2]
    assert final in (sent[0][-1], sent[1][-1])
    entry = f.filer.find_entry(path)
    assert len(entry.chunks) == 1 and entry.size() == len(final)
    # every superseded chunk was reclaimed exactly once, and the standing
    # one was not: by number and by bytes (a needle is its data and 35
    # bytes: sizes, flags, the mime of the filer's upload, a time stamp)
    needles, nbytes = (a - b for a, b in zip(deleted(vs), before))
    assert needles == writes
    assert nbytes == writes * (len(final) + 35)
    assert metric("SeaweedFS_filer_overwrites_total") - overwrites == writes
    assert metric("SeaweedFS_filer_reclaimed_chunks_total") - reclaimed \
        == writes
    # the name is listed once
    listing = json.loads(ask(f, "GET", FOLDER + "/")[2])
    assert [e["FullPath"] for e in listing["Entries"]] == [path]


def test_a_get_whose_chunk_was_reclaimed_reads_the_entry_as_it_stands(
        cluster):
    filer, _ = cluster
    f = filer(save_to_filer_limit=0)
    path = FOLDER + "/raced"
    ask(f, "POST", path, record("old"), JSON)
    stale = f.filer.find_entry(path)
    ask(f, "POST", path, record("new"), JSON)   # reclaims `stale`'s chunk
    retries = metric("SeaweedFS_filer_read_retries_total")
    # the read that looked the entry up before the overwrite
    lookups = iter([stale])
    real = f.filer.find_entry
    f.filer.find_entry = lambda p: next(lookups, None) or real(p)
    try:
        status, _, body = ask(f, "GET", path)
    finally:
        f.filer.find_entry = real
    assert (status, body) == (200, record("new"))
    assert metric("SeaweedFS_filer_read_retries_total") - retries == 1
    # a chunk that is gone under an entry that still names it stays a 404
    fid = real(path).chunks[0].fid
    f.chunk_cache.invalidate(fid)
    from seaweedfs_tpu.rpc.http_rpc import call
    call(f._lookup_url(fid), f"/{fid}", method="DELETE")
    assert ask(f, "GET", path)[0] == 404


# -- /metrics ---------------------------------------------------------------------------

STAGES = ("lock_wait", "lock_held", "store_write", "notify", "reclaim",
          "http_read", "http_write")


@pytest.mark.parametrize("stage", STAGES)
def test_the_mutation_s_inside_and_the_front_are_in_metrics(cluster, stage):
    filer, _ = cluster
    f = filer(save_to_filer_limit=0)
    path = FOLDER + "/m"
    blocks = "SeaweedFS_filer_stage_blocks_total"
    seconds = "SeaweedFS_filer_stage_seconds_total"
    before = metric(blocks, stage=stage), metric(seconds, stage=stage)
    ask(f, "POST", path, record("one"), JSON)
    ask(f, "POST", path, record("two"), JSON)       # an overwrite
    ask(f, "GET", path)
    after = metric(blocks, stage=stage), metric(seconds, stage=stage)
    # two POSTs, one GET; the first POST also makes two folders (an event
    # each) and only the second reclaims a chunk
    want = {"reclaim": 1, "http_read": 1, "notify": 4}.get(stage, 2)
    assert after[0] - before[0] == want
    assert after[1] > before[1]
    text = ask(f, "GET", "/metrics")[2].decode()
    assert f'{blocks}{{stage="{stage}"}}' in text
    for family in ("SeaweedFS_filer_overwrites_total",
                   "SeaweedFS_filer_reclaimed_chunks_total",
                   "SeaweedFS_filer_reclaimed_bytes_total",
                   "SeaweedFS_filer_read_retries_total"):
        assert f"\n{family} " in text


def test_a_delete_and_an_update_pass_the_same_lock_section(cluster):
    filer, _ = cluster
    f = filer(save_to_filer_limit=0)
    path = FOLDER + "/d"
    ask(f, "POST", path, record("x"), JSON)
    held = metric("SeaweedFS_filer_stage_blocks_total", stage="lock_held")
    writes = metric("SeaweedFS_filer_stage_blocks_total",
                    stage="store_write")
    bytes_before = metric("SeaweedFS_filer_reclaimed_bytes_total")
    assert ask(f, "PUT", path + "?tagging", None,
               {"Seaweed-Kind": "record"})[0] == 202    # update_entry
    assert ask(f, "DELETE", path)[0] == 204             # delete_entry
    assert metric("SeaweedFS_filer_stage_blocks_total",
                  stage="lock_held") - held == 2
    assert metric("SeaweedFS_filer_stage_blocks_total",
                  stage="store_write") - writes == 2
    assert metric("SeaweedFS_filer_reclaimed_bytes_total") - bytes_before \
        == len(record("x"))
    assert ask(f, "GET", path)[0] == 404
