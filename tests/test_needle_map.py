"""Needle map kinds: conformance across memory/compact/sqlite + the
compact map's 10M-entry scale test (compact_map_perf_test.go's role).
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle_map import (CompactNeedleMap, NeedleMap,
                                              SqliteNeedleMap,
                                              load_needle_map_from_idx,
                                              new_needle_map)

KINDS = ["memory", "compact", "sqlite"]


def _idx_path(tmp_path, kind):
    return str(tmp_path / f"{kind}.idx")


class TestKindConformance:
    """All kinds implement identical semantics (needle_map.go:24-38)."""

    @pytest.mark.parametrize("kind", KINDS)
    def test_put_get_delete(self, tmp_path, kind):
        nm = new_needle_map(kind, _idx_path(tmp_path, kind))
        nm.put(5, 1024, 100)
        nm.put(3, 2048, 50)
        assert nm.get(5).offset == 1024 and nm.get(5).size == 100
        assert nm.get(4) is None
        assert 3 in nm and 4 not in nm
        nm.delete(5, 4096)
        got = nm.get(5)
        assert got is not None and got.size == -100  # negated, kept
        assert nm.file_count == 2
        assert nm.deleted_count == 1 and nm.deleted_bytes == 100
        assert nm.content_bytes == 150
        assert nm.max_file_key() == 5
        nm.close()

    @pytest.mark.parametrize("kind", KINDS)
    def test_overwrite_counts_prev_deleted(self, tmp_path, kind):
        nm = new_needle_map(kind, _idx_path(tmp_path, kind))
        nm.put(9, 512, 10)
        nm.put(9, 1024, 20)
        assert nm.get(9).offset == 1024 and nm.get(9).size == 20
        assert nm.deleted_count == 1 and nm.deleted_bytes == 10
        nm.close()

    @pytest.mark.parametrize("kind", KINDS)
    def test_reload_from_idx(self, tmp_path, kind):
        path = _idx_path(tmp_path, kind)
        nm = new_needle_map(kind, path)
        for i in range(1, 200):
            nm.put(i, i * 8, i)
        for i in range(1, 200, 3):
            nm.delete(i, 99999 * 8)
        stats = (nm.file_count, nm.deleted_count, nm.deleted_bytes,
                 nm.content_bytes, nm.max_key, len(nm))
        nm.close()
        nm2 = new_needle_map(kind, path)
        assert (nm2.file_count, nm2.deleted_count, nm2.deleted_bytes,
                nm2.content_bytes, nm2.max_key, len(nm2)) == stats
        assert nm2.get(2).offset == 16
        assert nm2.get(1).size == -1  # deleted keeps negated size
        nm2.close()

    @pytest.mark.parametrize("kind", KINDS)
    def test_ascending_visit_order(self, tmp_path, kind):
        nm = new_needle_map(kind, _idx_path(tmp_path, kind))
        ids = [70, 1, 999, 42, (1 << 62) + 3, 7]
        for i in ids:
            nm.put(i, 8 * i % (1 << 20) + 8, 1)
        seen = [nid for nid, _ in nm.items_ascending()]
        assert seen == sorted(ids)
        nm.close()

    @pytest.mark.parametrize("kind", KINDS)
    def test_delete_then_revive(self, tmp_path, kind):
        nm = new_needle_map(kind, _idx_path(tmp_path, kind))
        nm.put(1, 8, 10)
        nm.delete(1, 16)
        nm.put(1, 24, 30)
        assert nm.get(1).offset == 24 and nm.get(1).size == 30
        assert nm.deleted_count == 1
        nm.close()


class TestCompactMap:
    def test_overflow_merges(self, tmp_path):
        nm = CompactNeedleMap()
        for i in range(10000):
            nm.set_in_memory(i * 2 + 1, 8 * (i + 1), 7)
        assert len(nm) == 10000
        # force-merge happens on visit; all entries appear
        assert sum(1 for _ in nm.items_ascending()) == 10000
        assert nm._overflow == {}
        assert nm.get(19999).size == 7

    def test_u64_keys(self):
        nm = CompactNeedleMap()
        big = (1 << 64) - 5
        nm.set_in_memory(big, 8, 3)
        assert nm.get(big).size == 3
        assert nm.max_file_key() == big

    def test_bulk_load_matches_dict_replay(self, tmp_path):
        """The vectorised loader must agree with per-entry dict replay on a
        log with overwrites, deletes, revives and delete-only keys."""
        path = str(tmp_path / "v.idx")
        rng = np.random.default_rng(0)
        with open(path, "wb") as f:
            for _ in range(5000):
                nid = int(rng.integers(1, 700))
                if rng.random() < 0.3:
                    f.write(idx_mod.pack_entry(nid, 0,
                                               t.TOMBSTONE_FILE_SIZE))
                else:
                    # size 0 is legal and must not count as deletable
                    # content when superseded (_apply's prev[1] > 0 guard)
                    f.write(idx_mod.pack_entry(
                        nid, 8 * int(rng.integers(1, 1 << 20)),
                        int(rng.integers(0, 1000))))
        ref = load_needle_map_from_idx(path, kind="memory")
        got = load_needle_map_from_idx(path, kind="compact")
        assert (got.file_count, got.deleted_count, got.deleted_bytes,
                got.content_bytes, got.max_key) == (
            ref.file_count, ref.deleted_count, ref.deleted_bytes,
            ref.content_bytes, ref.max_key)
        ref_items = [(n, v.offset, v.size) for n, v in ref.items_ascending()]
        got_items = [(n, v.offset, v.size) for n, v in got.items_ascending()]
        assert ref_items == got_items


class TestCompactMapScale:
    N = 10_000_000

    def test_10m_entries_load_and_lookup(self, tmp_path):
        """compact_map_perf_test.go's role: bulk-load 10M entries, check
        memory footprint (<= 24 bytes/entry core arrays — actual: 16) and
        10,000 random lookups."""
        path = str(tmp_path / "big.idx")
        n = self.N
        arr = np.zeros(n, dtype=np.dtype([("key", ">u8"), ("off", ">u4"),
                                          ("size", ">i4")]))
        arr["key"] = np.arange(1, n + 1, dtype=np.uint64)
        arr["off"] = np.arange(1, n + 1, dtype=np.uint32)
        arr["size"] = 100
        arr.tofile(path)

        nm = load_needle_map_from_idx(path, kind="compact")
        assert len(nm) == n
        assert nm.bytes_per_entry() <= 24
        assert nm.file_count == n and nm.content_bytes == n * 100

        rng = np.random.default_rng(1)
        probes = rng.integers(1, n + 1, size=10000)
        for nid in probes:
            got = nm.get(int(nid))
            assert (got.offset, got.size) == (
                int(nid) * t.NEEDLE_PADDING_SIZE, 100)
