"""EC lifecycle: encode, locate, read, reconstruct, delete, decode.

Mirrors the reference's ec_test.go round-trip methodology: encode a real
volume with small block sizes, then assert every needle's bytes read from
the shard set equal the bytes in the original .dat — including when read
through reconstruction from random 10-shard subsets."""

import os
import random
import shutil

import numpy as np
import pytest

from conftest import reference_fixture
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.erasure_coding import (DATA_SHARDS_COUNT,
                                                  TOTAL_SHARDS_COUNT, to_ext)
from seaweedfs_tpu.storage.erasure_coding import decoder as dec
from seaweedfs_tpu.storage.erasure_coding import encoder as enc
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (EcDeletedError,
                                                            EcNotFoundError,
                                                            EcVolume,
                                                            EcVolumeShard,
                                                            ShardBits,
                                                            rebuild_ecx_file)
from seaweedfs_tpu.storage.erasure_coding.locate import Interval, locate_data
from seaweedfs_tpu.storage.needle import get_actual_size
from seaweedfs_tpu.storage.needle_map import load_needle_map_from_idx
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.storage.needle import Needle

LARGE, SMALL = 10000, 100  # ec_test.go:16-19 uses the same scaled-down sizes


def make_volume(tmp_path, vid=1, count=50, data_size=300):
    v = Volume(str(tmp_path), "", vid)
    rng = np.random.default_rng(vid)
    for i in range(1, count + 1):
        n = Needle.create(rng.integers(0, 256, data_size).astype(
            np.uint8).tobytes(), name=f"f{i}".encode())
        n.id, n.cookie = i, 0x1000 + i
        v.write_needle(n)
    v.sync()
    return v


@pytest.fixture
def encoded(tmp_path):
    """A volume encoded to shards with scaled-down block sizes."""
    v = make_volume(tmp_path, vid=1)
    base = v.file_name()
    v.close()
    enc.write_ec_files(base, large_block_size=LARGE, small_block_size=SMALL)
    enc.write_sorted_file_from_idx(base)
    return base, str(tmp_path)


class TestLocate:
    def test_single_byte_after_large_rows(self):
        # pinned from TestLocateData (ec_test.go:188-196)
        intervals = locate_data(LARGE, SMALL, DATA_SHARDS_COUNT * LARGE + 1,
                                DATA_SHARDS_COUNT * LARGE, 1)
        assert len(intervals) == 1
        iv = intervals[0]
        assert (iv.block_index, iv.inner_block_offset, iv.size,
                iv.is_large_block, iv.large_block_rows_count) == (0, 0, 1,
                                                                  False, 1)

    def test_span_crossing_large_to_small(self):
        dat_size = DATA_SHARDS_COUNT * LARGE + 1
        offset = DATA_SHARDS_COUNT * LARGE // 2 + 100
        size = dat_size - offset
        intervals = locate_data(LARGE, SMALL, dat_size, offset, size)
        assert sum(iv.size for iv in intervals) == size
        # spans both tiers
        assert any(iv.is_large_block for iv in intervals)
        assert any(not iv.is_large_block for iv in intervals)

    def test_interval_to_shard_id(self):
        iv = Interval(block_index=13, inner_block_offset=7, size=1,
                      is_large_block=True, large_block_rows_count=2)
        sid, off = iv.to_shard_id_and_offset(LARGE, SMALL)
        assert sid == 3 and off == LARGE + 7
        iv2 = Interval(block_index=25, inner_block_offset=3, size=1,
                       is_large_block=False, large_block_rows_count=2)
        sid2, off2 = iv2.to_shard_id_and_offset(LARGE, SMALL)
        assert sid2 == 5 and off2 == 2 * LARGE + 2 * SMALL + 3

    def test_offsets_reassemble_dat(self):
        """Striping is a bijection: every .dat byte maps to exactly one
        (shard, offset)."""
        dat_size = DATA_SHARDS_COUNT * LARGE * 1 + 777
        seen = set()
        pos = 0
        while pos < dat_size:
            span = min(997, dat_size - pos)
            for iv in locate_data(LARGE, SMALL, dat_size, pos, span):
                sid, off = iv.to_shard_id_and_offset(LARGE, SMALL)
                for k in range(iv.size):
                    key = (sid, off + k)
                    assert key not in seen
                    seen.add(key)
            pos += span
        assert len(seen) == dat_size


class TestEncode:
    def test_shard_files_created_with_equal_size(self, encoded):
        base, _ = encoded
        sizes = {os.path.getsize(base + to_ext(i))
                 for i in range(TOTAL_SHARDS_COUNT)}
        assert len(sizes) == 1
        dat_size = os.path.getsize(base + ".dat")
        n_small_rows = -(-dat_size // (SMALL * DATA_SHARDS_COUNT))
        assert sizes.pop() == n_small_rows * SMALL

    def test_data_shards_are_systematic_copy(self, encoded):
        """Interleaved concat of .ec00-.ec09 must reproduce the .dat."""
        base, _ = encoded
        dat = open(base + ".dat", "rb").read()
        reassembled = bytearray()
        shard_files = [open(base + to_ext(i), "rb").read()
                       for i in range(DATA_SHARDS_COUNT)]
        pos = 0
        while len(reassembled) < len(dat):
            for s in shard_files:
                reassembled += s[pos:pos + SMALL]
            pos += SMALL
        assert bytes(reassembled[:len(dat)]) == dat

    def test_every_needle_readable_from_shards(self, encoded):
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        for i in range(TOTAL_SHARDS_COUNT):
            ev.add_shard(EcVolumeShard(d, "", 1, i))
        nm = load_needle_map_from_idx(base + ".idx")
        dat = open(base + ".dat", "rb").read()
        checked = 0
        for nid, nv in nm.items_ascending():
            if nv.size < 0:
                continue
            n = ev.read_needle(nid)
            assert n.id == nid
            # byte-identical to the original .dat record
            blob = dat[nv.offset:nv.offset + get_actual_size(nv.size, 3)]
            parts = [ev._read_interval(iv)
                     for iv in ev.locate_needle(nid)[2]]
            assert b"".join(parts)[:len(blob)] == blob
            checked += 1
        assert checked > 0
        ev.close()

    def test_read_with_four_shards_missing(self, encoded):
        """ec_test.go readFromOtherEcFiles analogue: reads must succeed via
        reconstruction with any 4 shards gone."""
        base, d = encoded
        rng = random.Random(7)
        missing = set(rng.sample(range(TOTAL_SHARDS_COUNT), 4))
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        for i in range(TOTAL_SHARDS_COUNT):
            if i not in missing:
                ev.add_shard(EcVolumeShard(d, "", 1, i))
        nm = load_needle_map_from_idx(base + ".idx")
        for nid, nv in list(nm.items_ascending())[:10]:
            if nv.size < 0:
                continue
            n = ev.read_needle(nid)
            assert n.id == nid  # CRC verified inside read
        ev.close()

    def test_degraded_read_fans_out_survivor_fetches(self, encoded):
        """Remote survivor fetches must run in PARALLEL (the reference
        fans out per-shard goroutines, store_ec.go:328-382): a fetch
        that does not answer until a second one is out beside it would
        hang a serial loop, and here the recovery completes."""
        import threading

        base, d = encoded
        shard_bytes = {i: open(base + to_ext(i), "rb").read()
                       for i in range(TOTAL_SHARDS_COUNT)}
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        # NO local shards: every survivor is a remote fetch
        calls = []
        beside = threading.Condition()
        out = [0, 0]  # fetches out now, and the most at once

        def remote(sid, offset, size):
            calls.append(sid)
            if sid == 0:  # the target shard is lost cluster-wide
                return None
            with beside:
                out[0] += 1
                out[1] = max(out)
                beside.notify_all()
                # a deadline so that serial fetches fail, not hang
                beside.wait_for(lambda: out[1] >= 2, timeout=30)
                out[0] -= 1
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = remote
        span = ev.read_shard_span(0, 0, 64)
        assert span == shard_bytes[0][:64]
        assert len(calls) >= DATA_SHARDS_COUNT
        assert out[1] >= 2, "survivor fetches look serial"
        ev.close()

    def test_degraded_read_survives_failing_survivors(self, encoded):
        """First-10-wins with 3 of 13 remotes erroring/timing out."""
        base, d = encoded
        shard_bytes = {i: open(base + to_ext(i), "rb").read()
                       for i in range(TOTAL_SHARDS_COUNT)}
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)

        def flaky_remote(sid, offset, size):
            if sid == 0:  # the target shard is lost cluster-wide
                return None
            if sid in (1, 5, 12):
                raise OSError("connection refused")
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = flaky_remote
        assert ev.read_shard_span(0, 0, 64) == shard_bytes[0][:64]
        ev.close()

    def test_too_many_missing_fails(self, encoded):
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        for i in range(DATA_SHARDS_COUNT - 1):  # only 9 shards
            ev.add_shard(EcVolumeShard(d, "", 1, i))
        # spans on the present shards still read fine...
        assert len(ev.read_shard_span(0, 0, 50)) == 50
        # ...but a missing shard cannot be recovered from only 9 survivors
        with pytest.raises(Exception, match="shards"):
            ev.read_shard_span(9, 0, 50)
        ev.close()


class TestDegradedReadPath:
    """The fast degraded-read pipeline: recovered-block cache,
    single-flight coalescing, and decode-plan integrity under survivor
    faults (recover.py + ec_volume.py _recover_span)."""

    def _volume_without_local_shards(self, encoded):
        base, d = encoded
        shard_bytes = {i: open(base + to_ext(i), "rb").read()
                       for i in range(TOTAL_SHARDS_COUNT)}
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        return ev, shard_bytes

    def test_single_flight_one_fanout_for_concurrent_readers(self, encoded):
        """16 concurrent readers of one dead span must trigger ONE
        survivor fan-out (<= 13 survivor fetches), not sixteen."""
        import threading
        import time as _t

        ev, shard_bytes = self._volume_without_local_shards(encoded)
        survivor_calls = []
        calls_lock = threading.Lock()
        gate = threading.Barrier(17)  # 16 readers + main

        def slow_remote(sid, offset, size):
            if sid == 0:  # the target shard is lost cluster-wide
                return None
            with calls_lock:
                survivor_calls.append(sid)
            _t.sleep(0.05)  # keep the flight open while followers pile in
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = slow_remote
        results = [None] * 16

        def reader(i):
            gate.wait()
            results[i] = ev.read_shard_span(0, 0, 64)

        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(16)]
        for th in threads:
            th.start()
        gate.wait()
        for th in threads:
            th.join()
        assert all(r == shard_bytes[0][:64] for r in results)
        # one fan-out submits at most the 13 survivor candidates; a
        # second fan-out would at least double that
        assert len(survivor_calls) <= TOTAL_SHARDS_COUNT - 1, (
            f"{len(survivor_calls)} survivor fetches for 16 readers")
        ev.close()

    def test_recovered_block_cache_hit_skips_refetch(self, encoded):
        ev, shard_bytes = self._volume_without_local_shards(encoded)
        calls = []

        def remote(sid, offset, size):
            if sid == 0:
                return None
            calls.append(sid)
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = remote
        first = ev.read_shard_span(0, 0, 64)
        n_after_first = len(calls)
        assert n_after_first >= DATA_SHARDS_COUNT
        again = ev.read_shard_span(0, 0, 64)
        assert again == first == shard_bytes[0][:64]
        assert len(calls) == n_after_first, "cache hit refetched survivors"
        ev.close()

    def test_short_remote_target_read_degrades_to_recovery(self, encoded):
        """A truncated answer from the shard's holder must fall through
        to reconstruction, not fail the read."""
        ev, shard_bytes = self._volume_without_local_shards(encoded)

        def remote(sid, offset, size):
            if sid == 0:
                return shard_bytes[0][offset:offset + size // 2]  # short!
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = remote
        assert ev.read_shard_span(0, 0, 64) == shard_bytes[0][:64]
        ev.close()

    def test_raising_remote_target_read_degrades_to_recovery(self, encoded):
        ev, shard_bytes = self._volume_without_local_shards(encoded)

        def remote(sid, offset, size):
            if sid == 0:
                raise OSError("connection reset")
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = remote
        assert ev.read_shard_span(0, 0, 64) == shard_bytes[0][:64]
        ev.close()

    def test_faulty_survivor_does_not_poison_plan_cache(self, encoded):
        """Mid-recovery survivor faults (short data, then a timeout-ish
        error) must not leave a bad decode plan behind: the winning
        survivor set keys the plan, and later reads — same or different
        fault pattern — still answer byte-identical data."""
        ev, shard_bytes = self._volume_without_local_shards(encoded)
        faulty = {3: "short", 7: "raise"}

        def flaky_remote(sid, offset, size):
            if sid == 0:
                return None
            mode = faulty.get(sid)
            if mode == "short":
                return shard_bytes[sid][offset:offset + max(1, size // 3)]
            if mode == "raise":
                raise TimeoutError("survivor fetch timed out")
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = flaky_remote
        assert ev.read_shard_span(0, 0, 64) == shard_bytes[0][:64]
        # heal the survivors and read a DIFFERENT span of the same shard:
        # the fresh fan-out may pick a different survivor set, and any
        # plan cached from the faulty round must not corrupt it
        faulty.clear()
        assert ev.read_shard_span(0, 64, 64) == shard_bytes[0][64:128]
        # different fault pattern, different offset again
        faulty[1] = "raise"
        faulty[9] = "short"
        assert ev.read_shard_span(0, 128, 32) == shard_bytes[0][128:160]
        ev.close()

    def test_coalesce_and_cache_knobs_off_still_correct(self, encoded,
                                                        monkeypatch):
        monkeypatch.setenv("WEED_EC_RECOVER_CACHE_MB", "0")
        monkeypatch.setenv("WEED_EC_RECOVER_COALESCE", "0")
        monkeypatch.setenv("WEED_EC_RECOVER_BLOCK_KB", "0")
        ev, shard_bytes = self._volume_without_local_shards(encoded)
        calls = []

        def remote(sid, offset, size):
            if sid == 0:
                return None
            calls.append(sid)
            return shard_bytes[sid][offset:offset + size]

        ev.remote_reader = remote
        assert ev.read_shard_span(0, 0, 64) == shard_bytes[0][:64]
        n_first = len(calls)
        # caching disabled: the same span refetches
        assert ev.read_shard_span(0, 0, 64) == shard_bytes[0][:64]
        assert len(calls) > n_first
        ev.close()

    def test_block_aligned_recovery_serves_neighbor_spans(self, encoded):
        """With local survivors and a block size covering the whole
        (scaled-down) shard, the FIRST recovery warms the cache for
        every later span on the dead shard."""
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        for i in range(1, DATA_SHARDS_COUNT + 1):  # shard 0 dead
            ev.add_shard(EcVolumeShard(d, "", 1, i))
        shard0 = open(base + to_ext(0), "rb").read()
        assert ev.read_shard_span(0, 0, 50) == shard0[:50]
        assert ev.recover_stats()["cache_blocks"] >= 1
        # a read elsewhere in the same block never re-decodes
        hits_before = ev.recover_stats()["cache_hits"]
        assert ev.read_shard_span(0, 60, 40) == shard0[60:100]
        assert ev.recover_stats()["cache_hits"] > hits_before
        ev.close()


class TestRebuild:
    def test_rebuild_missing_shards(self, encoded):
        base, d = encoded
        golden = {i: open(base + to_ext(i), "rb").read()
                  for i in range(TOTAL_SHARDS_COUNT)}
        for i in (2, 7, 11, 13):
            os.remove(base + to_ext(i))
        generated = enc.rebuild_ec_files(base)
        assert sorted(generated) == [2, 7, 11, 13]  # dict of sid -> crc
        for i in range(TOTAL_SHARDS_COUNT):
            assert open(base + to_ext(i), "rb").read() == golden[i], i

    def test_rebuild_noop_when_complete(self, encoded):
        base, _ = encoded
        assert enc.rebuild_ec_files(base) == {}


class TestEcxEcj:
    def test_ecx_sorted_and_live_only(self, encoded):
        base, _ = encoded
        prev = -1
        count = 0
        with open(base + ".ecx", "rb") as f:
            while True:
                e = f.read(16)
                if not e:
                    break
                nid, off, size = idx_mod.unpack_entry(e)
                assert nid > prev
                assert t.size_is_valid(size)
                prev = nid
                count += 1
        assert count == 50

    def test_delete_marks_ecx_and_journals(self, encoded):
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        for i in range(TOTAL_SHARDS_COUNT):
            ev.add_shard(EcVolumeShard(d, "", 1, i))
        ev.read_needle(5)
        ev.delete_needle(5)
        with pytest.raises(EcDeletedError):
            ev.read_needle(5)
        assert os.path.getsize(base + ".ecj") == 8
        # absent id deletion is a no-op
        ev.delete_needle(99999)
        assert os.path.getsize(base + ".ecj") == 8
        ev.close()

    def test_rebuild_ecx_replays_journal(self, encoded):
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        ev.delete_needle(3)
        ev.close()
        # wipe the in-place tombstone, keeping only the journal
        enc.write_sorted_file_from_idx(base)
        rebuild_ecx_file(base)
        assert not os.path.exists(base + ".ecj")
        ev2 = EcVolume(d, "", 1, large_block_size=LARGE,
                       small_block_size=SMALL)
        with pytest.raises(EcDeletedError):
            ev2.locate_needle(3)
        ev2.close()

    def test_missing_needle(self, encoded):
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        with pytest.raises(EcNotFoundError):
            ev.read_needle(777777)
        ev.close()


class TestDecode:
    def test_decode_back_to_volume(self, encoded):
        """ec.decode path: shards -> .dat/.idx -> regular volume reads."""
        base, d = encoded
        golden_dat = open(base + ".dat", "rb").read()
        os.remove(base + ".dat")
        os.remove(base + ".idx")
        dat_size = dec.find_dat_file_size(base, base)
        dec.write_dat_file(base, dat_size, large_block_size=LARGE,
                           small_block_size=SMALL)
        dec.write_idx_file_from_ec_index(base)
        assert open(base + ".dat", "rb").read() == golden_dat[:dat_size]
        v = Volume(d, "", 1)
        assert v.file_count() == 50
        for i in (1, 25, 50):
            assert v.read_needle(i).id == i
        v.close()

    def test_decode_with_journal_deletions(self, encoded):
        base, d = encoded
        ev = EcVolume(d, "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        ev.delete_needle(10)
        ev.close()
        os.remove(base + ".dat")
        os.remove(base + ".idx")
        dat_size = dec.find_dat_file_size(base, base)
        dec.write_dat_file(base, dat_size, large_block_size=LARGE,
                           small_block_size=SMALL)
        dec.write_idx_file_from_ec_index(base)
        v = Volume(d, "", 1)
        from seaweedfs_tpu.storage.volume import DeletedError, NotFoundError
        # the tombstoned ecx entry replays as a deletion (doLoading treats
        # TombstoneFileSize as delete), so the key is absent after decode
        with pytest.raises((DeletedError, NotFoundError)):
            v.read_needle(10)
        assert v.read_needle(11).id == 11
        v.close()


class TestShardBits:
    def test_ops(self):
        b = ShardBits().add(0).add(13).add(5)
        assert b.shard_ids() == [0, 5, 13]
        assert b.count() == 3
        assert b.has(5) and not b.has(6)
        assert b.remove(5).shard_ids() == [0, 13]
        assert b.add(0).count() == 3  # idempotent
        assert b.minus(ShardBits().add(0)).shard_ids() == [5, 13]
        assert b.plus(ShardBits().add(1)).shard_ids() == [0, 1, 5, 13]

    def test_hash_consistent_with_eq(self):
        """ShardBits defines __eq__, so it must define __hash__ too —
        without it, equal values land in different dict/set buckets and
        ShardBits silently stops working as a topology map key."""
        a = ShardBits().add(3).add(7)
        b = ShardBits().add(7).add(3)
        assert a == b
        assert hash(a) == hash(b)
        assert len({a, b}) == 1
        d = {a: "x"}
        assert d[b] == "x"
        assert hash(a) != hash(a.add(1))  # distinct sets hash apart


@pytest.mark.skipif(reference_fixture("weed/storage/erasure_coding/1.dat")
                    is None, reason="reference fixture not mounted")
class TestReferenceFixtureRoundTrip:
    def test_reference_volume_ec_roundtrip(self, tmp_path):
        """The reference's own test data through our full EC path."""
        shutil.copy(reference_fixture("weed/storage/erasure_coding/1.dat"),
                    tmp_path / "1.dat")
        shutil.copy(reference_fixture("weed/storage/erasure_coding/1.idx"),
                    tmp_path / "1.idx")
        base = str(tmp_path / "1")
        enc.write_ec_files(base, large_block_size=LARGE,
                           small_block_size=SMALL)
        enc.write_sorted_file_from_idx(base)
        ev = EcVolume(str(tmp_path), "", 1, large_block_size=LARGE,
                      small_block_size=SMALL)
        missing = {1, 4, 12}
        for i in range(TOTAL_SHARDS_COUNT):
            if i not in missing:
                ev.add_shard(EcVolumeShard(str(tmp_path), "", 1, i))
        nm = load_needle_map_from_idx(base + ".idx")
        read = 0
        for nid, nv in nm.items_ascending():
            if nv.size < 0:
                continue
            n = ev.read_needle(nid)  # CRC-verifies real data
            assert n.id == nid
            read += 1
        assert read > 0
        ev.close()
