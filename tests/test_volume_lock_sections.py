"""What `Volume.lock` covers (PR 44): the append with its stamp and the
map's put, an overwrite's confirmation, a read's lookup with its hold on
the data file; and what it does not: a needle's build, a record's parse
and CRC, the data file's reads.  Each invariant the shorter sections must
keep is pinned here under threads, over each Python map and, where the
library builds, the native one."""

import errno
import os
import sys
import threading

import pytest

from seaweedfs_tpu.stats import metrics as stats
from seaweedfs_tpu.storage import native_engine, volume_backup
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.backend import DiskFile
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_map import NeedleValue
from seaweedfs_tpu.storage.volume import (CookieMismatchError, Volume,
                                          VolumeError)

JOIN_S = 120


@pytest.fixture(params=["memory", "compact", "sqlite", "native"])
def new_volume(request, tmp_path, monkeypatch):
    """A factory of volumes over one kind of needle map."""
    if request.param == "memory":
        monkeypatch.setattr(native_engine, "available", lambda: False)
    elif request.param == "native" and not native_engine.available():
        pytest.skip("the native engine does not build here")
    made = []

    def make(vid=1, **kw):
        kw.setdefault("needle_map_kind", request.param)
        v = Volume(str(tmp_path), "", vid, **kw)
        assert isinstance(v.nm, native_engine.NativeNeedleMap) == (
            request.param == "native")
        made.append(v)
        return v

    yield make
    for v in made:
        v.close()


@pytest.fixture
def fast_switching():
    """More hand-overs than the default 5 ms allows: what a lost update
    needs to show in a bounded test."""
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    yield
    sys.setswitchinterval(before)


def _needle(nid, data, cookie=0x1234):
    n = Needle.create(data, last_modified=1_700_000_000)
    n.id, n.cookie = nid, cookie
    return n


def _run(threads):
    for th in threads:
        th.start()
    for th in threads:
        th.join(JOIN_S)
    assert not any(th.is_alive() for th in threads)


def _stamps_in_file_order(v):
    return [(off, n.append_at_ns, n.id) for n, off in v.scan()]


def test_writers_beside_readers_keep_the_file_and_its_stamps_in_order(
        new_volume, fast_switching):
    v = new_volume()
    writers, per_writer, readers = 8, 500, 8
    acked: list[tuple[int, bytes]] = []
    failures: list[str] = []
    done = threading.Event()

    def write(w):
        for i in range(per_writer):
            nid = 1 + w * per_writer + i
            data = (b"%08d" % nid) * (16 + nid % 7)
            try:
                _, _, unchanged = v.write_needle(_needle(nid, data))
            except Exception as e:  # noqa: BLE001 - the test's verdict
                failures.append(f"write {nid}: {e!r}")
                return
            if unchanged:
                failures.append(f"write {nid}: unchanged")
            acked.append((nid, data))

    def read(r):
        k = r
        while not done.is_set() or k < len(acked):
            if k >= len(acked):
                continue
            nid, data = acked[k]
            k += readers
            try:
                got = v.read_needle(nid, cookie=0x1234)
            except Exception as e:  # noqa: BLE001
                failures.append(f"read {nid}: {e!r}")
                return
            if got.data != data or got.id != nid:
                failures.append(f"read {nid}: another needle's bytes")

    wt = [threading.Thread(target=write, args=(w,)) for w in range(writers)]
    rt = [threading.Thread(target=read, args=(r,)) for r in range(readers)]
    for th in rt:
        th.start()
    _run(wt)
    done.set()
    for th in rt:
        th.join(JOIN_S)
    assert not any(th.is_alive() for th in rt)
    assert failures == []
    assert len(acked) == writers * per_writer
    for nid, data in acked:
        assert v.read_needle(nid).data == data

    # the .dat walks cleanly record by record, and nothing was cut off it
    v.nm.flush()
    size = v.data.size()
    records = _stamps_in_file_order(v)
    assert len(records) == len(acked)
    assert v._check_integrity(v.file_name(".idx")) == records[-1][1]
    assert v.data.size() == size

    # append_at_ns never decreases along the file (nor along the .idx,
    # which the backup's bisection walks), and the bisection finds each
    stamps = [ns for _, ns, _ in records]
    assert stamps == sorted(stamps)
    assert v.last_append_at_ns == stamps[-1]
    first_at = {}
    for off, ns, _ in records:
        first_at.setdefault(ns, off)
    for ns, off in list(first_at.items())[::7]:
        assert volume_backup.binary_search_by_append_at_ns(v, ns - 1) == off
    assert volume_backup.binary_search_by_append_at_ns(
        v, stamps[-1]) == size


def test_two_overwrites_of_one_id_racing_leave_the_map_at_the_last_record(
        new_volume, fast_switching):
    v = new_volume()
    ids = range(1, 41)
    for nid in ids:
        v.write_needle(_needle(nid, b"first %d" % nid))
    failures = []

    def overwrite(tag):
        for nid in ids:
            try:
                v.write_needle(_needle(nid, b"%s wrote %d" % (tag, nid)))
            except Exception as e:  # noqa: BLE001
                failures.append(f"{tag} {nid}: {e!r}")

    _run([threading.Thread(target=overwrite, args=(tag,))
          for tag in (b"a", b"b")])
    assert failures == []
    last = {}
    count = {}
    for n, off in v.scan():
        last[n.id] = (off, n.data)
        count[n.id] = count.get(n.id, 0) + 1
    for nid in ids:
        # neither overwrite was taken for a re-write of what stood there
        assert count[nid] == 3
        nv = v.nm.get(nid)
        assert (nv.offset, v.read_needle(nid).data) == last[nid]


def test_a_wrong_cookie_racing_a_right_one_is_refused_every_time(
        new_volume, fast_switching):
    v = new_volume()
    ids = range(1, 41)
    for nid in ids:
        v.write_needle(_needle(nid, b"first %d" % nid))
    refused, failures = [], []

    def right():
        for nid in ids:
            try:
                v.write_needle(_needle(nid, b"right %d" % nid))
            except Exception as e:  # noqa: BLE001
                failures.append(f"right {nid}: {e!r}")

    def wrong():
        for nid in ids:
            try:
                v.write_needle(_needle(nid, b"wrong %d" % nid,
                                       cookie=0x4321))
                failures.append(f"wrong cookie written to {nid}")
            except CookieMismatchError:
                refused.append(nid)

    _run([threading.Thread(target=right), threading.Thread(target=wrong)])
    assert failures == []
    assert refused == list(ids)
    assert all(n.cookie == 0x1234 for n, _ in v.scan())
    for nid in ids:
        assert v.read_needle(nid, cookie=0x1234).data == b"right %d" % nid


@pytest.fixture
def meet_after_the_lookup(monkeypatch):
    """Aim at the window: callers of the write path's lookup outside
    the lock wait for each other before they go on to the append."""
    inner = Volume._peek
    state = {"barrier": None}

    def peek(self, nid):
        got = inner(self, nid)
        barrier = state["barrier"]
        me = threading.current_thread()
        if barrier is not None and not getattr(me, "met", False):
            me.met = True  # a thread's later lookups go straight on
            barrier.wait(JOIN_S)
        return got

    monkeypatch.setattr(Volume, "_peek", peek)
    return state


def test_two_first_writes_of_one_id_acknowledge_exactly_one_cookie(
        new_volume, meet_after_the_lookup):
    """Both lookups find nothing; the append confirms that under the
    lock, so the second decides again and meets the first one's cookie
    (on 6bca213's order, and upstream's, the only outcomes)."""
    v = new_volume()
    for nid in range(1, 21):
        acked, refused = [], []

        def first_write(cookie, nid=nid, acked=acked, refused=refused):
            try:
                v.write_needle(_needle(nid, b"from %x" % cookie, cookie))
                acked.append(cookie)
            except CookieMismatchError:
                refused.append(cookie)

        meet_after_the_lookup["barrier"] = threading.Barrier(2)
        _run([threading.Thread(target=first_write, args=(c,))
              for c in (0xaaaa, 0xbbbb)])
        meet_after_the_lookup["barrier"] = None
        assert len(acked) == 1 and len(refused) == 1, (nid, acked, refused)
        # the acknowledged PUT is its owner's to read, at once
        assert v.read_needle(nid, cookie=acked[0]).data == \
            b"from %x" % acked[0]
        with pytest.raises(CookieMismatchError):
            v.read_needle(nid, cookie=refused[0])
    assert sorted(n.id for n, _ in v.scan()) == list(range(1, 21))


def test_two_deletes_of_one_id_free_its_size_once(new_volume,
                                                  meet_after_the_lookup):
    v = new_volume()
    for nid in range(1, 21):
        v.write_needle(_needle(nid, b"d" * 100))
        size, freed = v.nm.get(nid).size, []
        meet_after_the_lookup["barrier"] = threading.Barrier(2)
        _run([threading.Thread(
            target=lambda nid=nid, freed=freed: freed.append(
                v.delete_needle(_needle(nid, b""))))
            for _ in range(2)])
        meet_after_the_lookup["barrier"] = None
        assert sorted(freed) == [0, size], (nid, freed)
    # one record and one tombstone an id
    assert len(list(v.scan())) == 40


def test_an_identical_rewrite_is_still_unchanged_and_appends_nothing(
        new_volume):
    v = new_volume()
    v.write_needle(_needle(7, b"same bytes"))
    size = v.data.size()
    assert v.write_needle(_needle(7, b"same bytes")) == (
        0, len(b"same bytes"), True)
    assert v.data.size() == size
    # a needle without a cookie takes the standing one where the caller
    # says so (replication's hop), and a wrong one is refused
    n = _needle(7, b"other bytes", cookie=0)
    v.write_needle(n, check_cookie=False)
    assert n.cookie == 0x1234
    with pytest.raises(CookieMismatchError):
        v.write_needle(_needle(7, b"more bytes", cookie=0x9999))
    assert v.delete_needle(_needle(7, b"")) > 0
    assert v.delete_needle(_needle(7, b"")) == 0


def test_a_vacuum_s_commit_beside_readers_and_writers_closes_no_file_under_a_read(
        new_volume, fast_switching):
    v = new_volume()
    standing = {nid: b"standing %d" % nid * 20 for nid in range(1, 201)}
    for nid, data in standing.items():
        v.write_needle(_needle(nid, data))
    for nid in range(1, 201, 2):  # garbage for the vacuum to drop
        v.delete_needle(_needle(nid, b""))
        del standing[nid]
    live = sorted(standing)
    failures, written = [], []
    stop = threading.Event()

    def read(r):
        k = r
        while not stop.is_set():
            nid = live[k % len(live)]
            k += 3
            try:
                if v.read_needle(nid).data != standing[nid]:
                    failures.append(f"read {nid}: another needle's bytes")
            except Exception as e:  # noqa: BLE001
                failures.append(f"read {nid}: {e!r}")
                return

    def write(w):
        nid = 1000 + w
        while not stop.is_set():
            data = b"beside the vacuum %d" % nid
            try:
                v.write_needle(_needle(nid, data))
            except Exception as e:  # noqa: BLE001
                failures.append(f"write {nid}: {e!r}")
                return
            written.append((nid, data))
            nid += 4

    threads = [threading.Thread(target=read, args=(r,)) for r in range(4)]
    threads += [threading.Thread(target=write, args=(w,)) for w in range(4)]
    for th in threads:
        th.start()
    try:
        for _ in range(3):
            v.compact()
            v.commit_compact()
    finally:
        stop.set()
        for th in threads:
            th.join(JOIN_S)
    assert not any(th.is_alive() for th in threads)
    assert failures == []
    assert written
    for nid, data in written:
        assert v.read_needle(nid).data == data
    for nid in live:
        assert v.read_needle(nid).data == standing[nid]
    assert v._readers == 0


def test_a_second_vacuum_with_nothing_deleted_since_moves_every_entry(
        new_volume):
    """The new .idx is then as long as the old one was: a map that
    keeps a lookup structure beside it (sqlite) must not take it for
    the same log."""
    v = new_volume()
    written = {}
    for nid in range(1, 41):
        v.write_needle(_needle(nid, b"garbage %d" % nid))
    for nid in range(1, 41):
        v.delete_needle(_needle(nid, b""))
    nid = 100
    for _ in range(3):
        for between in (v.compact, v.commit_compact):
            for _ in range(10):
                written[nid] = b"written %d" % nid
                v.write_needle(_needle(nid, written[nid]))
                nid += 1
            between()
        for k, data in written.items():
            assert v.read_needle(k).data == data


def test_a_lookup_beside_a_merge_of_the_compact_map_finds_every_key(
        monkeypatch, fast_switching):
    """The write path looks an id up outside Volume.lock, beside a put
    under it that merges the overflow into the sorted arrays."""
    from seaweedfs_tpu.storage.needle_map import CompactNeedleMap

    monkeypatch.setattr(CompactNeedleMap, "_MERGE_MIN", 16)
    nm = CompactNeedleMap()
    known = range(1, 2001, 2)
    for nid in known:
        nm.put(nid, nid * 8, nid % 97 + 1)
    failures, done = [], threading.Event()

    def look():
        while not done.is_set():
            for nid in known:
                try:
                    got = nm.get(nid)
                    if (got.offset, got.size) != (nid * 8, nid % 97 + 1):
                        failures.append(f"{nid}: {got}")
                except Exception as e:  # noqa: BLE001
                    failures.append(f"{nid}: {e!r}")
                    return

    readers = [threading.Thread(target=look) for _ in range(4)]
    for th in readers:
        th.start()
    for nid in range(2, 6001, 2):  # fresh keys: 187 merges
        nm.put(nid, nid * 8, 5)
    done.set()
    for th in readers:
        th.join(JOIN_S)
    assert not any(th.is_alive() for th in readers)
    assert failures == []


def test_no_write_lands_once_read_only_is_acknowledged(new_volume,
                                                       fast_switching):
    v = new_volume()
    refused, failures, acked = [], [], []
    sealed = threading.Event()

    def write(w):
        nid = 1 + w
        for _ in range(4000):
            try:
                v.write_needle(_needle(nid, b"until the seal %d" % nid))
                acked.append(nid)
                if sealed.is_set() and v.data.size() != size_at_seal[0]:
                    failures.append(f"write {nid} landed after the seal")
            except VolumeError:
                refused.append(nid)
                return
            nid += 8

    threads = [threading.Thread(target=write, args=(w,)) for w in range(8)]
    for th in threads:
        th.start()
    while len(acked) < 200:
        pass
    size_at_seal = [0]
    with v.lock:  # as tier_upload seals a volume
        v.read_only = True
        size_at_seal[0] = v.data.size()
    sealed.set()
    for th in threads:
        th.join(JOIN_S)
    assert not any(th.is_alive() for th in threads)
    assert failures == []
    assert len(refused) == 8
    assert v.data.size() == size_at_seal[0]
    with pytest.raises(VolumeError):
        v.delete_needle(_needle(acked[0], b""))
    for nid in acked:
        assert v.read_needle(nid).data == b"until the seal %d" % nid


@pytest.fixture
def lock_witness(monkeypatch):
    """Record, for every build, parse and data-file read, whether the
    calling thread held the lock of the volume under test."""
    seen = {"volume": None, "calls": []}

    def wrap(cls, name):
        inner = getattr(cls, name)

        def outer(self, *a, **kw):
            v = seen["volume"]
            if v is not None:
                seen["calls"].append((name, v.lock._is_owned()))
            return inner(self, *a, **kw)

        monkeypatch.setattr(cls, name, outer)

    for name in ("to_bytes", "to_record", "read_bytes"):
        if hasattr(Needle, name):  # to_record: since PR 44
            wrap(Needle, name)
    wrap(DiskFile, "read_at")
    return seen


def test_the_build_the_parse_and_the_data_reads_run_outside_the_lock(
        new_volume, lock_witness):
    v = new_volume()
    lock_witness["volume"] = v
    v.write_needle(_needle(1, b"x" * 1024))
    v.write_needle(_needle(1, b"y" * 1024))  # an overwrite reads the old
    assert v.read_needle(1).data == b"y" * 1024
    v.delete_needle(_needle(1, b""))
    lock_witness["volume"] = None
    calls = lock_witness["calls"]
    names = {name for name, _ in calls}
    assert names & {"to_bytes", "to_record"} and "read_bytes" in names \
        and "read_at" in names
    assert [c for c in calls if c[1]] == []


def test_a_large_needle_s_slice_reads_outside_the_lock_too(new_volume,
                                                           lock_witness):
    v = new_volume()
    v.write_needle(_needle(2, os.urandom(256 << 10)))
    lock_witness["volume"] = v
    n, off, length, fd = v.read_needle_slice(2, 0x1234, min_size=65536)
    os.close(fd)
    lock_witness["volume"] = None
    assert length == 256 << 10 and n.data == b""
    assert ("read_at", False) in lock_witness["calls"]
    assert [c for c in lock_witness["calls"] if c[1]] == []
    # below the caller's floor: the record's head says so, read outside
    v.write_needle(_needle(3, b"z" * 1024))
    lock_witness["volume"] = v
    lock_witness["calls"].clear()
    assert v.read_needle_slice(3, 0x1234, min_size=65536) is None
    assert lock_witness["calls"] == [("read_at", False)]


def _lock_count(op):
    return stats.VolumeLockCounter._values.get((op,), 0.0)


def test_a_served_get_of_1_kb_visits_the_lock_once(tmp_path, new_volume):
    """Through the volume server's handler: the zero-copy path decides
    from the map's size, so only the buffered read takes the lock; a
    second GET is the read cache's and takes none.  (`new_volume`: the
    map's kind is patched for the server's volumes too.)"""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    os.makedirs(tmp_path / "vs")
    vs = VolumeServer([str(tmp_path / "vs")], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    try:
        vs.heartbeat_once()
        a = call(master.address, "/dir/assign")
        url, fid = a["url"], a["fid"]
        writes = _lock_count("write")
        call(url, f"/{fid}", raw=b"k" * 1024, method="POST")
        assert _lock_count("write") - writes == 1
        reads = _lock_count("read")
        assert call(url, f"/{fid}", parse=False) == b"k" * 1024
        assert _lock_count("read") - reads == 1
        assert call(url, f"/{fid}", parse=False) == b"k" * 1024
        assert _lock_count("read") - reads == 1
        # a needle over the zero-copy floor pays its one visit there
        a = call(master.address, "/dir/assign")
        big = os.urandom(128 << 10)
        call(a["url"], f"/{a['fid']}", raw=big, method="POST")
        reads = _lock_count("read")
        assert call(a["url"], f"/{a['fid']}", parse=False) == big
        assert _lock_count("read") - reads == 1
    finally:
        vs.stop()
        master.stop()


def test_the_size_limit_is_still_refused_at_the_append(new_volume,
                                                       monkeypatch):
    v = new_volume()
    v.write_needle(_needle(1, b"fits"))
    monkeypatch.setattr(t, "MAX_POSSIBLE_VOLUME_SIZE", v.data.size() + 40)
    size = v.data.size()
    with pytest.raises(VolumeError, match="size limit"):
        v.write_needle(_needle(2, b"w" * 64))
    assert v.data.size() == size and v.nm.get(2) is None
    # the limit is held against the file's end, whoever wrote up to it:
    # the native plane and volume_backup's replay append beside
    # write_needle, and a record past the limit wraps its stored offset
    monkeypatch.setattr(t, "MAX_POSSIBLE_VOLUME_SIZE", size + 200)
    v.data.append(bytes(_needle(3, b"f" * 64).to_record(v.version)))
    with pytest.raises(VolumeError, match="size limit"):
        v.write_needle(_needle(2, b"w" * 64))
    assert v.delete_needle(_needle(1, b"")) > 0  # a tombstone still fits


def test_the_engine_s_one_call_appends_and_keeps_the_newer_offset(tmp_path):
    """`append_put`: the append and the map's put-if-newer (or a
    delete's entry) in one call, under the engine's two locks."""
    if not native_engine.available():
        pytest.skip("the native engine does not build here")
    from seaweedfs_tpu.storage import idx as idx_mod

    v = Volume(str(tmp_path), "", 3)
    try:
        nm, end = v.nm, v.data.size()
        rec = bytearray(b"r" * 40)
        limit = 1 << 35
        assert nm.append_put(rec, 5, 17, None, limit) == end
        assert (nm.get(5).offset, nm.get(5).size) == (end, 17)
        assert v.data.read_at(40, end) == bytes(rec)
        # an entry further on (a native-port write that landed since
        # the caller's lookup) is not clobbered by the older offset
        nm.set_in_memory(6, 1 << 40, 9)
        off = nm.append_put(bytes(rec), 6, 17, nm.get(6), limit)
        assert off == end + 40 and nm.get(6).offset == 1 << 40
        # a tombstone's entry: the offset stays, the size goes negative
        off = nm.append_put(rec, 5, t.TOMBSTONE_FILE_SIZE, nm.get(5), limit)
        assert off == end + 80
        assert (nm.get(5).offset, nm.get(5).size) == (end, -17)
        # the entry the caller decided against is no longer the map's
        # (here: it was deleted, it is looked for as fresh, the offset
        # is another's): nothing is written, the caller decides again
        for stale in (NeedleValue(end, 17), None, NeedleValue(end + 8, -17)):
            assert nm.append_put(rec, 5, 17, stale, limit) is None
        # and a record that would end past the limit is refused where
        # its offset is allocated
        with pytest.raises(OSError) as e:
            nm.append_put(rec, 9, 17, None, end + 120 + 39)
        assert e.value.errno == errno.EFBIG
        assert v.data.size() == end + 120 and nm.get(9) is None
        entries = []
        idx_mod.walk_index_file(v.file_name(".idx"),
                                lambda *e: entries.append(e))
        assert entries == [(5, end, 17),
                           (5, end + 80, t.TOMBSTONE_FILE_SIZE)]
    finally:
        v.close()
