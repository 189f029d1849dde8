"""Reads of a sealed volume beside the transitions of its shard set and
beside an EC bulk job (PR 38: the deployment of cell
`degraded-get-under-rebuild`).

  * deterministically, no race to win: a mounted local shard that fails or
    reads short AFTER the lookup found it (closed by an unmount, an I/O
    error, a truncated file) gives the needle's bytes through
    reconstruction and counts one `local_fallbacks`; an inline volume's
    tail ladder is as it was;
  * the counters that say what was served beside a job (`BULK_JOBS`,
    `needles_beside_job`, the `decode_*_beside_job` three, a rebuild's
    `foreground_reads`, a cache's own `lookups`), and the benchmark's
    readers over a parent's replies, which lack them;
  * over a live volume server: four readers while shard 0 is deleted,
    rebuilt and mounted ten times, every body and every rebuilt shard
    against the plain reference; two rebuilds of one volume at once upload
    the survivors once (ROADMAP C3).

Small sizes, seeded bytes, the CPU backend: results and counts, never a
time."""

import importlib.util
import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import reference  # noqa: E402
import reference_reads  # noqa: E402
import reference_rebuild  # noqa: E402

from seaweedfs_tpu.storage.erasure_coding import \
    recover as recover_mod  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (  # noqa: E402
    READ_STATS, EcError, EcVolume, EcVolumeShard)
from seaweedfs_tpu.storage.erasure_coding.recover import (  # noqa: E402
    BULK_JOBS, RecoveredBlockCache, RecoverStats)
from seaweedfs_tpu.storage.needle import Needle  # noqa: E402
from seaweedfs_tpu.storage.store import Store  # noqa: E402

VID = 1
SIZES = [4 << 20] * 3 + [32 << 10] * 40     # 13.25 MiB: shards of 2 MiB
EXTS = [".ecx", ".vif"] + [reference.shard_ext(s)
                           for s in range(reference.TOTAL_SHARDS)]
NEW_READ_KEYS = ("needles_beside_job", "local_fallbacks")
NEW_RECOVER_KEYS = ("decode_batches_beside_job", "decode_blocks_beside_job",
                    "decode_apply_seconds_beside_job")


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """One volume of 4 MiB and 32 KiB objects sealed through the store on
    a mesh of one device (so that its `.vif` holds the shard CRCs), its
    bodies, its pristine shard files and which objects hold a block of
    shard 0."""
    d = tmp_path_factory.mktemp("under_rebuild")
    mp = pytest.MonkeyPatch()
    mp.setenv("WEED_EC_DEVICE_SHARD", "1")
    store = Store([str(d)], ec_encoder_backend="tpu")
    store.add_volume(VID)
    rng = np.random.default_rng(38)
    sizes = list(SIZES)
    rng.shuffle(sizes)
    bodies = {}
    for nid, nbytes in enumerate(sizes, 1):
        data = rng.bytes(nbytes)
        n = Needle.create(data)
        n.id, n.cookie = nid, 0x3800 + nid
        store.write_needle(VID, n)
        bodies[nid] = (n.cookie, data)
    store.ec_generate(VID)
    base = store.find_volume(VID).file_name()
    store.close()
    mp.undo()
    dat_size = os.path.getsize(base + ".dat")
    plans = {nid: reference_reads.read_plan(
        offset, reference.needle_disk_size(stored), dat_size, [0])
        for nid, (offset, stored)
        in reference.read_ecx(base + ".ecx").items()}
    files = []
    for sid in range(reference.TOTAL_SHARDS):
        with open(base + reference.shard_ext(sid), "rb") as f:
            files.append(f.read())
    on_shard_0 = sorted(nid for nid, p in plans.items() if p["recovered"])
    assert on_shard_0 and len(on_shard_0) < len(bodies)
    return {"dir": str(d), "base": base, "bodies": bodies, "plans": plans,
            "files": files, "on_shard_0": on_shard_0}


@pytest.fixture
def mounted(sealed):
    """All fourteen shards mounted, as after a repair."""
    ev = EcVolume(sealed["dir"], "", VID)
    for sid in range(reference.TOTAL_SHARDS):
        ev.add_shard(EcVolumeShard(sealed["dir"], "", VID, sid))
    yield ev
    ev.close()


def _delta(before, after, keys):
    return {k: after[k] - before[k] for k in keys}


def _read(ev, sealed, nid):
    cookie, data = sealed["bodies"][nid]
    return ev.read_needle(nid, cookie).data == data


# -- a mounted local shard that fails after the lookup ---------------------------

def _closed(shard, monkeypatch):
    shard.close()     # what unmount_ec_shard does beside a reader


def _raises(shard, monkeypatch):
    def read_at(size, offset):
        raise OSError(5, "Input/output error")
    monkeypatch.setattr(shard, "read_at", read_at)


def _reads_short(shard, monkeypatch):
    real = shard.read_at
    monkeypatch.setattr(shard, "read_at",
                        lambda size, offset: real(size, offset)[:size - 1])


@pytest.mark.parametrize("fail", [_closed, _raises, _reads_short],
                         ids=lambda f: f.__name__.strip("_"))
def test_failing_local_shard_is_served_by_reconstruction_and_counted(
        sealed, mounted, monkeypatch, fail):
    """The lookup finds shard 0 mounted (`self.shards.get`) and the read
    then fails: the parent raised (`AttributeError` of the closed file,
    the `OSError`, `EcError` for the short read); now the interval is
    reconstructed from the others and counted, once an interval."""
    ev = mounted
    fail(ev.shards[0], monkeypatch)
    assert 0 in ev.shards          # still there for the lookup to find
    nid = sealed["on_shard_0"][0]
    plan = sealed["plans"][nid]
    read0, recover0 = READ_STATS.snapshot(), recover_mod.STATS.snapshot()
    assert _read(ev, sealed, nid)
    read = _delta(read0, READ_STATS.snapshot(),
                  ("needles", "intervals_recovered", "local_fallbacks"))
    assert read == {"needles": 1,
                    "intervals_recovered": len(plan["recovered"]),
                    "local_fallbacks": len(plan["recovered"])}
    recover = _delta(recover0, recover_mod.STATS.snapshot(),
                     ("cache_hits", "cache_misses", "coalesced"))
    assert sum(recover.values()) == len(plan["blocks"])
    # an object with no block on shard 0 never asks it for anything
    other = next(n for n in sealed["bodies"]
                 if n not in sealed["on_shard_0"])
    before = READ_STATS.snapshot()
    assert _read(ev, sealed, other)
    assert _delta(before, READ_STATS.snapshot(),
                  ("needles", "local_fallbacks")) == {
        "needles": 1, "local_fallbacks": 0}


def test_a_read_that_holds_the_file_finishes_on_it_after_a_close(sealed):
    """`read_at` takes the open file before it reads: a close beside it
    (and the unlink that follows) drops the shard's own hold, the
    descriptor lives until the read lets go, and no other file can get
    its number under the read."""
    shard = EcVolumeShard(sealed["dir"], "", VID, 3)
    held = shard._f
    fd = held.fd
    shard.close()
    assert os.pread(fd, 16, 0) == sealed["files"][3][:16]   # still open
    with pytest.raises(EcError, match="is closed"):
        shard.read_at(16, 0)
    del held
    with pytest.raises(OSError):
        os.fstat(fd)               # the last holder closed it
    shard.close()                  # idempotent


def test_a_failing_survivor_is_not_a_survivor(sealed, mounted, monkeypatch):
    """Shard 0 gone, and shard 1 (the first survivor a recovery would
    pick) fails under it: the recovery takes the next ten."""
    ev = mounted
    ev.delete_shard(0).close()
    _raises(ev.shards[1], monkeypatch)
    for nid in sealed["on_shard_0"][:3]:
        assert _read(ev, sealed, nid)


def test_shard_size_while_the_shard_set_changes(mounted):
    ev = mounted
    size = ev.shard_size
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            shard = ev.delete_shard(13)
            ev.add_shard(shard)

    th = threading.Thread(target=churn)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    th.start()
    try:
        assert {ev.shard_size for _ in range(20000)} == {size}
    finally:
        stop.set()
        th.join()
        sys.setswitchinterval(interval)


@pytest.mark.parametrize("tail", ["serves-the-rest", "row-was-committed"])
def test_inline_volume_s_tail_ladder_is_unchanged(sealed, mounted,
                                                  monkeypatch, tail):
    """An inline volume's shard log may end before the span does: the
    tail stripe serves the rest, or the flusher committed the row
    meanwhile and the second read is whole.  Neither is a fallback."""
    ev = mounted
    nid = sealed["on_shard_0"][0]
    _, at, size = sealed["plans"][nid]["recovered"][0]
    shard = ev.shards[0]
    real = shard.read_at
    reads = []

    def read_at(n, offset):
        reads.append((n, offset))
        short = len(reads) == 1 and (n, offset) == (size, at)
        return real(n, offset)[:n - 7] if short else real(n, offset)

    asked = []

    def tail_reader(shard_id, offset, n):
        asked.append((shard_id, offset, n))
        return real(n, offset) if tail == "serves-the-rest" else None

    monkeypatch.setattr(shard, "read_at", read_at)
    monkeypatch.setattr(ev, "tail_reader", tail_reader, raising=False)
    before = READ_STATS.snapshot()
    assert ev.read_shard_span(0, at, size) == sealed["files"][0][
        at:at + size]
    assert asked == [(0, at + size - 7, 7)]
    assert len(reads) == (1 if tail == "serves-the-rest" else 2)
    assert READ_STATS.snapshot()["local_fallbacks"] \
        == before["local_fallbacks"]


def test_inline_volume_s_short_read_with_no_tail_still_raises(
        sealed, mounted, monkeypatch):
    ev = mounted
    _reads_short(ev.shards[0], monkeypatch)
    monkeypatch.setattr(ev, "tail_reader", lambda *a: None, raising=False)
    with pytest.raises(EcError, match="short read shard 0"):
        ev.read_shard_span(0, 0, 4096)


# -- what was served beside a job ------------------------------------------------

def test_bulk_jobs_nest_and_come_back_to_zero_after_an_error():
    assert BULK_JOBS.in_flight == 0
    with BULK_JOBS.job():
        with BULK_JOBS.job():
            assert BULK_JOBS.in_flight == 2
        assert BULK_JOBS.in_flight == 1
    with pytest.raises(RuntimeError):
        with BULK_JOBS.job():
            raise RuntimeError("the job failed")
    assert BULK_JOBS.in_flight == 0


def test_needles_beside_job_rises_with_a_job_in_flight_and_not_without(
        sealed, mounted):
    ev = mounted
    nids = list(sealed["bodies"])[:5]
    before = READ_STATS.snapshot()
    for nid in nids:
        assert _read(ev, sealed, nid)
    quiet = READ_STATS.snapshot()
    assert _delta(before, quiet, ("needles", "needles_beside_job")) == {
        "needles": 5, "needles_beside_job": 0}
    with BULK_JOBS.job():
        for nid in nids:
            assert _read(ev, sealed, nid)
    assert _delta(quiet, READ_STATS.snapshot(),
                  ("needles", "needles_beside_job")) == {
        "needles": 5, "needles_beside_job": 5}


def test_decodes_beside_a_job_are_counted_a_batch_not_a_get():
    s = RecoverStats()
    s.add_stage("decode_apply", 0.25)
    s.decoded(2, 1000)
    with BULK_JOBS.job():
        s.add_stage("decode_apply", 0.5)
        s.add_stage("decode_h2d", 4.0)      # another stage: not counted
        s.decoded(3, 1000)
    snap = s.snapshot()
    assert snap["decode_apply_seconds"] == 0.75
    assert snap["decode_batches"] == 2 and snap["decode_blocks"] == 5
    assert {k: snap[k] for k in NEW_RECOVER_KEYS} == {
        "decode_batches_beside_job": 1, "decode_blocks_beside_job": 3,
        "decode_apply_seconds_beside_job": 0.5}
    s.reset()
    assert not any(s.snapshot()[k] for k in NEW_RECOVER_KEYS)


def test_a_cache_counts_its_own_lookups():
    stats = RecoverStats()
    one, other = RecoveredBlockCache(stats), RecoveredBlockCache(stats)
    for _ in range(3):
        one.get_or_recover((0, 0, 4), lambda: b"abcd", 1 << 20, True)
    # the LRU off, coalesced or not: a lookup all the same, never a hit
    other.get_or_recover((0, 0, 4), lambda: b"abcd", 0, False)
    other.get_or_recover((0, 0, 4), lambda: b"abcd", 0, True)
    assert (one.lookups, other.lookups) == (3, 2)
    assert (len(one), len(other)) == (1, 0)
    snap = stats.snapshot()
    assert snap["cache_hits"] == 2 and snap["cache_misses"] == 3
    # a follower of a flight is one lookup, as the leader is
    gate, entered = threading.Event(), threading.Event()

    def slow():
        entered.set()
        gate.wait(10)
        return b"wxyz"

    leader = threading.Thread(
        target=one.get_or_recover, args=((1, 0, 4), slow, 1 << 20, True))
    leader.start()
    assert entered.wait(10)
    follower = threading.Thread(
        target=one.get_or_recover,
        args=((1, 0, 4), lambda: b"never", 1 << 20, True))
    follower.start()
    while stats.snapshot()["coalesced"] < 1 and follower.is_alive():
        time.sleep(0.001)
    gate.set()
    leader.join(10)
    follower.join(10)
    snap = stats.snapshot()
    assert one.lookups == 5 == 3 + 2
    assert (snap["cache_misses"], snap["coalesced"]) == (4, 1)


def _reader(kind):
    spec = importlib.util.spec_from_file_location(
        "_reader_" + kind, os.path.join(ROOT, "perfbench", "readers",
                                        kind + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_benchmark_s_readers_read_none_from_a_parent_s_replies():
    """What the new per-layer files name, over replies with the new keys
    and over a parent's, which lack them: nothing to read, no failure."""
    record, admin = _reader("harness_record"), _reader("admin_json")
    served = READ_STATS.snapshot()
    parent_served = {k: v for k, v in served.items()
                     if k not in NEW_READ_KEYS}
    share = {"record": "sealed_read", "key": "read_stats.needles_beside_job",
             "per": "read_stats.needles", "scale": 100}
    fallbacks = {"record": "sealed_read",
                 "key": "read_stats.local_fallbacks", "per": "windows"}
    new = {"records": {"sealed_read": [{
        "read_stats": {**served, "needles": 40, "needles_beside_job": 30,
                       "local_fallbacks": 2}, "windows": 1}]}}
    old = {"records": {"sealed_read": [{
        "read_stats": {**parent_served, "needles": 40}, "windows": 1}]}}
    assert record.read(share, new) == 75.0
    assert record.read(fallbacks, new) == 2.0
    assert record.read(share, old) is None
    assert record.read(fallbacks, old) is None
    reads = {"record": "rebuild", "key": "stage_stats.foreground_reads",
             "per": "rebuilds"}
    replies = [{"rebuilds": 1, "stage_stats": {"foreground_reads": n}}
               for n in (300, 500)]
    assert record.read(reads, {"records": {"rebuild": replies}}) == 400.0
    assert record.read(reads, {"records": {"rebuild": [
        {"rebuilds": 1, "stage_stats": {"wall": 0.3}}]}}) is None
    apply_ms = {"path": "/admin/ec/recover_stats",
                "key": "decode_apply_seconds_beside_job",
                "per": "decode_blocks_beside_job", "scale": 1000}
    stats = recover_mod.STATS.snapshot()
    after = {**stats, "decode_apply_seconds_beside_job":
             stats["decode_apply_seconds_beside_job"] + 0.06,
             "decode_blocks_beside_job":
             stats["decode_blocks_beside_job"] + 20}
    assert admin.read(apply_ms, {"admin": {
        "/admin/ec/recover_stats": [stats, after]}}) == pytest.approx(3.0)
    parent = {k: v for k, v in stats.items() if k not in NEW_RECOVER_KEYS}
    assert admin.read(apply_ms, {"admin": {
        "/admin/ec/recover_stats": [parent, parent]}}) is None


# -- over a live volume server -----------------------------------------------------

@pytest.fixture
def served(sealed, tmp_path, monkeypatch):
    """A master and a volume server (`-ecBackend tpu`, the curator off as
    the benchmark's configurations have it) over a copy of the sealed
    volume, all fourteen shards mounted."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    monkeypatch.setenv("WEED_MAINT", "0")
    monkeypatch.setenv("WEED_EC_DEVICE_SHARD", "1")
    vs_dir = tmp_path / "vs"
    vs_dir.mkdir()
    for ext in EXTS:
        shutil.copy(sealed["base"] + ext, vs_dir)
    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    vs = VolumeServer([str(vs_dir)], master.address, port=0,
                      pulse_seconds=0.2, ec_encoder_backend="tpu",
                      max_volume_counts=[16])
    vs.start()
    yield vs, str(vs_dir / str(VID))
    vs.stop()
    master.stop()


def _lose_shard_0(vs, base):
    from seaweedfs_tpu.rpc.http_rpc import call

    call(vs.address, "/admin/ec/delete_shards",
         {"volume": VID, "collection": "", "shard_ids": [0]})
    assert not os.path.exists(base + reference.shard_ext(0))


def test_four_readers_while_shard_0_is_deleted_rebuilt_and_mounted(
        sealed, served):
    """Ten repairs under readers that hold no lock against them: every
    body byte-identical, no read refused, every rebuilt shard 0 what the
    plain reference reconstructs from the ten survivors the rebuild
    read, and the server's own check of its CRC against the `.vif`."""
    from seaweedfs_tpu.rpc.http_rpc import call

    vs, base = served
    survivors = list(range(1, 11))
    rows = np.stack([np.frombuffer(sealed["files"][s], dtype=np.uint8)
                     for s in survivors])
    want = reference_rebuild.reconstruct(survivors, rows, [0])[0].tobytes()
    assert want == sealed["files"][0]
    stop = threading.Event()
    errors, reads = [], [0] * 4

    def reader(c):
        # the objects that hold a block of shard 0 twice as often
        order = (sealed["on_shard_0"] * 2 + list(sealed["bodies"]))[c::2]
        try:
            while not stop.is_set():
                for nid in order:
                    cookie, data = sealed["bodies"][nid]
                    got = call(vs.address, f"/{VID},{nid:x}{cookie:08x}",
                               parse=False)
                    if got != data:
                        raise AssertionError(f"needle {nid} read back wrong")
                    reads[c] += 1
        except BaseException as e:   # a thread must report, not vanish
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=reader, args=(c,)) for c in range(4)]
    before = call(vs.address, "/admin/ec/read_stats")
    for th in threads:
        th.start()
    served = []       # a rebuild's count of the reads served beside it
    try:
        for cycle in range(10):
            _lose_shard_0(vs, base)
            reply = call(vs.address, "/admin/ec/rebuild",
                         {"volume": VID, "collection": ""}, timeout=300)
            assert reply["rebuilt_shard_ids"] == [0], cycle
            assert reply["backend"] == "device-apply-xla"
            served.append(reply["stage_stats"]["foreground_reads"])
            with open(base + reference.shard_ext(0), "rb") as f:
                assert f.read() == want, cycle
            call(vs.address, "/admin/ec/mount",
                 {"volume": VID, "collection": "", "shard_ids": [0]})
            if errors:
                break
    finally:
        stop.set()
        for th in threads:
            th.join(60)
    assert not errors, errors[0]
    assert all(reads), reads
    assert all(isinstance(n, int) and n >= 0 for n in served), served
    assert 0 < sum(served) <= sum(reads) + len(threads)
    after = call(vs.address, "/admin/ec/read_stats")
    assert after["needles"] - before["needles"] >= sum(reads)
    # ten rebuilds ran beside the readers
    assert after["needles_beside_job"] > before["needles_beside_job"]
    assert after["local_fallbacks"] >= before["local_fallbacks"]
    recover = call(vs.address, "/admin/ec/recover_stats")
    assert recover["volumes"][str(VID)]["lookups"] > 0
    for key in NEW_RECOVER_KEYS:
        assert key in recover, key
    text = call(vs.address, "/metrics", parse=False).decode()
    for family in ("ec_read_local_fallbacks_total",
                   "ec_bulk_jobs_in_flight 0",
                   'ec_read_needles_total{needles="beside_job"}'):
        assert "SeaweedFS_volumeServer_" + family in text, family


def _h2d_bytes(vs):
    from seaweedfs_tpu.rpc.http_rpc import call

    total = 0.0
    for line in call(vs.address, "/metrics", parse=False).decode(
            ).splitlines():
        if line.startswith("SeaweedFS_volumeServer_ec_device_h2d_bytes_total"
                           ) and 'device="host"' not in line:
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_two_rebuilds_of_one_volume_at_once_upload_the_survivors_once(
        sealed, served, monkeypatch):
    """ROADMAP C3: the maintenance script beside the curator.  The second
    request waits for the first (it never enters the store beside it),
    then finds nothing missing."""
    from seaweedfs_tpu.rpc.http_rpc import call

    vs, base = served
    _lose_shard_0(vs, base)
    real = vs.store.ec_rebuild
    inside, most = [0], [0]
    first_in, second_in, go = (threading.Event(), threading.Event(),
                               threading.Event())
    lock = threading.Lock()

    def ec_rebuild(*args, **kw):
        with lock:
            inside[0] += 1
            most[0] = max(most[0], inside[0])
            (second_in if first_in.is_set() else first_in).set()
        try:
            go.wait(60)
            return real(*args, **kw)
        finally:
            with lock:
                inside[0] -= 1

    monkeypatch.setattr(vs.store, "ec_rebuild", ec_rebuild)
    replies = []

    def ask():
        replies.append(call(vs.address, "/admin/ec/rebuild",
                            {"volume": VID, "collection": ""}, timeout=300))

    h2d0 = _h2d_bytes(vs)
    threads = [threading.Thread(target=ask) for _ in range(2)]
    threads[0].start()
    assert first_in.wait(60)
    threads[1].start()
    # without the per-volume lock the second is in the store within
    # milliseconds; with it, it cannot be until the first is out
    assert not second_in.wait(1.0)
    go.set()
    for th in threads:
        th.join(120)
    assert most[0] == 1
    assert sorted(r["rebuilt_shard_ids"] for r in replies) == [[], [0]]
    done = next(r for r in replies if r["rebuilt_shard_ids"])
    idle = next(r for r in replies if not r["rebuilt_shard_ids"])
    assert idle["stage_stats"] == {} and idle["backend"] is None
    assert _h2d_bytes(vs) - h2d0 == done["stage_stats"]["h2d_bytes"] > 0
    with open(base + reference.shard_ext(0), "rb") as f:
        assert f.read() == sealed["files"][0]
