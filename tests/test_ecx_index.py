"""A mounted sealed volume's `.ecx`, searched in memory (`EcVolume`'s
read-only shared mapping of the file) against the search that reads the
file (`search_sorted_index`, what `rebuild_ecx_file` keeps using) and the
benchmark's plain reader of the format (`perfbench/reference.read_ecx`):
the same positions and entries at 0, 1, 2 and 2,272 entries, a delete seen
by the next read and after a remount with the file's bytes those the
journal's replay writes, readers beside a deleter, no `pread` of the
`.ecx` in `read_needle`, and no mapping left behind by `close()` /
`destroy()`.

Results and counts, never a time."""

import os
import shutil
import struct
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import reference  # noqa: E402

from seaweedfs_tpu.storage import idx as idx_mod  # noqa: E402
from seaweedfs_tpu.storage import types as t  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import encoder as enc  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (  # noqa: E402
    READ_STATS, EcDeletedError, EcNotFoundError, EcVolume, EcVolumeShard,
    rebuild_ecx_file, search_sorted_index)
from seaweedfs_tpu.storage.erasure_coding.inline import \
    InlineEcVolume  # noqa: E402
from seaweedfs_tpu.storage.needle import Needle  # noqa: E402
from seaweedfs_tpu.storage.volume import Volume  # noqa: E402

VID = 1
ENTRY = t.NEEDLE_MAP_ENTRY_SIZE
SIZES = (0, 1, 2, 2272)          # 2,272: the benchmark's sealed volume
TOMBSTONE = struct.pack(">i", t.TOMBSTONE_FILE_SIZE)


def _ids(n):
    """n needle ids with room below, between and above them."""
    return [10 + 3 * k for k in range(n)]


def _write_ecx(directory, n):
    """A bare sorted index of n entries (no shard files: a lookup needs
    none); entry k lies 8 * (k + 1) bytes into a `.dat`, 100 + k long."""
    with open(os.path.join(str(directory), f"{VID}.ecx"), "wb") as f:
        for k, nid in enumerate(_ids(n)):
            f.write(idx_mod.pack_entry(nid, 8 * (k + 1), 100 + k))


def _probes(n):
    """Every id present, and ids below, between and above them."""
    ids = _ids(n)
    return sorted({0, 1, 9, *ids, *(i + 1 for i in ids),
                   *(i + 2 for i in ids), 10 + 3 * n + 7, (1 << 64) - 1})


def _mapped(path):
    """Lines of this process's mappings that name the file."""
    with open("/proc/self/maps") as f:
        return [ln for ln in f if path in ln]


# -- the search -----------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
def test_search_in_memory_equals_the_search_that_reads_the_file(tmp_path, n):
    _write_ecx(tmp_path, n)
    ev = EcVolume(str(tmp_path), "", VID)
    try:
        assert ev.ecx_file_size == n * ENTRY
        with open(os.path.join(str(tmp_path), f"{VID}.ecx"), "rb") as f:
            for nid in _probes(n):
                want = search_sorted_index(f.fileno(), n, nid)
                assert ev._search_ecx(nid) == want, nid
                assert (want is not None) == (nid in _ids(n)), nid
    finally:
        ev.close()


@pytest.mark.parametrize("n", SIZES)
def test_lookups_and_entries_equal_the_reference_s(tmp_path, n):
    _write_ecx(tmp_path, n)
    entries = reference.read_ecx(os.path.join(str(tmp_path), f"{VID}.ecx"))
    assert sorted(entries) == _ids(n)
    ev = EcVolume(str(tmp_path), "", VID)
    try:
        for nid in _probes(n):
            if nid in entries:
                assert ev.find_needle_from_ecx(nid) == entries[nid], nid
            else:
                with pytest.raises(EcNotFoundError):
                    ev.find_needle_from_ecx(nid)
        # the walk the deep scrub makes: entry by entry, in the file's order
        assert [ev._read_ecx_entry(pos) for pos in range(n)] == [
            (nid, *entries[nid]) for nid in _ids(n)]
    finally:
        ev.close()


def test_an_empty_index_has_no_mapping_and_finds_nothing(tmp_path):
    _write_ecx(tmp_path, 0)
    ev = EcVolume(str(tmp_path), "", VID)
    try:
        assert ev._ecx_map is None
        with pytest.raises(EcNotFoundError):
            ev.read_needle(1)
        ev.delete_needle(1)              # absent: nothing written
        assert ev.ecj_file_size == 0
    finally:
        ev.close()


# -- a sealed volume: reads, deletes, remounts -------------------------------------

@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """A small volume sealed on the CPU: fourteen shard files, `.ecx`,
    `.vif`, and the bodies."""
    d = str(tmp_path_factory.mktemp("ecx_index"))
    rng = np.random.default_rng(37)
    v = Volume(d, "", VID)
    bodies = {}
    for nid in range(1, 61):
        data = rng.bytes(int(rng.integers(200, 6000)))
        n = Needle.create(data)
        n.id, n.cookie = nid, 0x3700 + nid
        v.write_needle(n)
        bodies[nid] = (n.cookie, data)
    v.sync()
    base = v.file_name()
    v.close()
    enc.write_ec_files(base)
    enc.write_sorted_file_from_idx(base)
    enc.save_volume_info(base, version=3)
    for ext in (".dat", ".idx"):
        os.remove(base + ext)
    return {"dir": d, "bodies": bodies}


@pytest.fixture
def volume(sealed, tmp_path):
    """A copy of the sealed volume a test may write to."""
    for name in os.listdir(sealed["dir"]):
        shutil.copy(os.path.join(sealed["dir"], name), tmp_path)
    return str(tmp_path)


def _mount(directory):
    ev = EcVolume(directory, "", VID)
    for sid in range(reference.TOTAL_SHARDS):
        ev.add_shard(EcVolumeShard(directory, "", VID, sid))
    return ev


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def test_a_delete_is_seen_by_the_next_read_and_after_a_remount(sealed,
                                                               volume):
    base = os.path.join(volume, str(VID))
    pristine = _read(base + ".ecx")
    gone = [7, 60, 1]
    ev = _mount(volume)
    try:
        for nid in gone:
            cookie, data = sealed["bodies"][nid]
            assert ev.read_needle(nid, cookie=cookie).data == data
            ev.delete_needle(nid)
            with pytest.raises(EcDeletedError):
                ev.read_needle(nid)
        ev.delete_needle(10_000)            # absent: no journal entry
    finally:
        ev.close()

    # the file: the parent's bytes, the size field of each entry -1
    want = bytearray(pristine)
    for nid in gone:                        # ids 1..60 sorted: entry nid - 1
        want[(nid - 1) * ENTRY + 12:nid * ENTRY] = TOMBSTONE
    assert _read(base + ".ecx") == bytes(want)
    assert _read(base + ".ecj") == b"".join(
        struct.pack(">Q", nid) for nid in gone)
    live = reference.read_ecx(base + ".ecx")
    assert sorted(live) == sorted(set(sealed["bodies"]) - set(gone))

    # the journal's replay into the pristine index writes the same file
    replay = os.path.join(volume, "replay")
    with open(replay + ".ecx", "wb") as f:
        f.write(pristine)
    shutil.copy(base + ".ecj", replay + ".ecj")
    rebuild_ecx_file(replay)
    assert _read(replay + ".ecx") == bytes(want)
    assert not os.path.exists(replay + ".ecj")

    ev = _mount(volume)                     # a remount maps what is there
    try:
        for nid, (cookie, data) in sealed["bodies"].items():
            if nid in gone:
                with pytest.raises(EcDeletedError):
                    ev.read_needle(nid)
            else:
                assert ev.read_needle(nid, cookie=cookie).data == data
    finally:
        ev.close()


def test_readers_beside_a_deleter_see_the_body_or_the_tombstone(sealed,
                                                                volume):
    """Eight readers loop over every needle while one thread deletes every
    other one: a read returns the needle's own bytes or raises
    EcDeletedError, and once a delete has returned no later read of that
    needle returns a body."""
    ev = _mount(volume)
    bodies = sealed["bodies"]
    doomed = sorted(bodies)[::2]
    deleted = set()                  # filled AFTER delete_needle returns
    wrong, stop = [], threading.Event()
    outcomes = [[0, 0] for _ in range(8)]

    def reader(k):
        order = sorted(bodies)[k:] + sorted(bodies)[:k]
        while not stop.is_set():
            for nid in order:
                was_deleted = nid in deleted
                try:
                    got = ev.read_needle(nid, cookie=bodies[nid][0]).data
                except EcDeletedError:
                    outcomes[k][1] += 1
                    if nid not in doomed:
                        wrong.append(("tombstone of a live needle", nid))
                    continue
                except Exception as e:     # a torn entry reads as this
                    wrong.append((repr(e), nid))
                    continue
                outcomes[k][0] += 1
                if got != bodies[nid][1]:
                    wrong.append(("other bytes", nid))
                if was_deleted:
                    wrong.append(("a body after its delete returned", nid))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    pool = [threading.Thread(target=reader, args=(k,)) for k in range(8)]
    try:
        for th in pool:
            th.start()
        for nid in doomed:
            ev.delete_needle(nid)
            deleted.add(nid)
        # one more round of every reader over the finished state
        rounds = [sum(o) for o in outcomes]
        deadline = time.monotonic() + 60
        while any(sum(o) < r + 2 * len(bodies)
                  for o, r in zip(outcomes, rounds)) and not wrong:
            assert time.monotonic() < deadline, "the readers stand still"
            stop.wait(0.01)
    finally:
        stop.set()
        for th in pool:
            th.join(60)
        sys.setswitchinterval(interval)
        ev.close()
    assert not any(th.is_alive() for th in pool)
    assert wrong == []
    assert all(bodies_read and tombstones
               for bodies_read, tombstones in outcomes)


@pytest.mark.parametrize("case", ["live", "deleted", "absent"])
def test_read_needle_makes_no_pread_of_the_ecx(sealed, volume, monkeypatch,
                                               case):
    """The test that pins the mechanism: during `read_needle` of a
    mounted sealed volume `os.pread` is never called on the `.ecx`'s
    descriptor, and the needle's `index_preads` is 0."""
    ev = _mount(volume)
    nid = {"live": 17, "deleted": 18, "absent": 10_000}[case]
    if case == "deleted":
        ev.delete_needle(nid)
    by_fd = {}
    real = os.pread

    def counting(fd, *a):
        by_fd[fd] = by_fd.get(fd, 0) + 1
        return real(fd, *a)

    monkeypatch.setattr(os, "pread", counting)
    before = READ_STATS.snapshot()
    try:
        if case == "live":
            cookie, data = sealed["bodies"][nid]
            assert ev.read_needle(nid, cookie=cookie).data == data
            assert sum(by_fd.values()) >= 1      # the shard's interval
        else:
            with pytest.raises(EcDeletedError if case == "deleted"
                               else EcNotFoundError):
                ev.read_needle(nid)
            assert by_fd == {}
        assert ev._ecx.fileno() not in by_fd
        after = READ_STATS.snapshot()
        assert after["index_preads"] == before["index_preads"]
        assert after["needles"] - before["needles"] == (case == "live")
    finally:
        monkeypatch.undo()
        ev.close()


# -- the mapping's life -------------------------------------------------------------

@pytest.mark.parametrize("how", ["close", "destroy"])
def test_no_mapping_outlives_the_volume(sealed, volume, how):
    """After `close()` / `destroy()` the process maps the `.ecx` nowhere,
    the file can be removed, and another index of another size mounted
    under the same name is the one that is searched."""
    path = os.path.join(volume, f"{VID}.ecx")
    ev = _mount(volume)
    ev.read_needle(5)
    assert len(_mapped(path)) == 1
    getattr(ev, how)()
    assert _mapped(path) == []
    assert ev._ecx_map is None and ev._ecx is None
    if how == "close":
        os.remove(path)
    assert not os.path.exists(path)
    if how == "destroy":
        assert not os.path.exists(os.path.join(volume, f"{VID}.ec00"))

    _write_ecx(volume, 3)                   # 60 entries before, 3 now
    again = EcVolume(volume, "", VID)
    try:
        assert again.ecx_file_size == 3 * ENTRY
        assert again.find_needle_from_ecx(13) == (16, 101)
        with pytest.raises(EcNotFoundError):
            again.find_needle_from_ecx(5)
        assert len(_mapped(path)) == 1
    finally:
        again.close()
    assert _mapped(path) == []


def test_a_copied_index_is_the_one_a_remount_searches(sealed, volume):
    """What `/admin/ec/copy` and `rebuild_ecx_file` leave behind: the file
    replaced or rewritten while nothing is mounted; the next mount maps
    the file that is there then."""
    base = os.path.join(volume, str(VID))
    ev = _mount(volume)
    ev.delete_needle(9)
    ev.close()
    rebuild_ecx_file(base)                  # replays and drops the journal
    assert not os.path.exists(base + ".ecj")
    ev = _mount(volume)
    try:
        with pytest.raises(EcDeletedError):
            ev.read_needle(9)
        cookie, data = sealed["bodies"][10]
        assert ev.read_needle(10, cookie=cookie).data == data
    finally:
        ev.close()
    shutil.copy(os.path.join(sealed["dir"], f"{VID}.ecx"), base + ".ecx")
    ev = _mount(volume)                     # the pristine index again
    try:
        cookie, data = sealed["bodies"][9]
        assert ev.read_needle(9, cookie=cookie).data == data
    finally:
        ev.close()


def test_an_inline_volume_still_mounts_over_its_empty_placeholders(
        tmp_path, monkeypatch):
    """Inline volumes mount with an empty `.ecx` (their own map answers
    `find_needle_from_ecx`): no mapping, and reads, a delete and a
    remount work as before."""
    monkeypatch.setenv("WEED_EC_STRIPE_KB", "8")
    ev = InlineEcVolume(str(tmp_path), "pics", 7, family="rs_vandermonde",
                        create=True)
    try:
        assert ev.ecx_file_size == 0 and ev._ecx_map is None
        for nid in (1, 2, 3):
            n = Needle.create(bytes([nid]) * 3000)
            n.id, n.cookie = nid, 0x1234
            ev.write_needle(n, check_cookie=False)
        before = READ_STATS.snapshot()
        assert ev.read_needle(2).data == b"\x02" * 3000
        after = READ_STATS.snapshot()
        assert after["index_preads"] == before["index_preads"]
        ev.delete_needle(3)
        with pytest.raises(EcDeletedError):
            ev.read_needle(3)
    finally:
        ev.close()
    ev = InlineEcVolume(str(tmp_path), "pics", 7)
    try:
        assert ev._ecx_map is None
        assert ev.read_needle(1).data == b"\x01" * 3000
        with pytest.raises(EcDeletedError):
            ev.read_needle(3)
    finally:
        ev.close()
