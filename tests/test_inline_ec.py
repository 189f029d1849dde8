"""Inline write-path erasure coding: needles stream straight into
striped shard logs at ingest — parity is current at ack time, there is
no .dat, no replica fan-out, and no seal-time read-back.

Covers the stripe writer (append / tail reads / commit records), the
EcVolume read ladder over partially-filled tail stripes, degraded
byte-identity across all three code families, crash recovery (torn
.scl records), the assign-time policy knobs, and the store-level
routing (PUT/GET/DELETE + heartbeat) for inline volumes.
"""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.storage.erasure_coding import inline
from seaweedfs_tpu.storage.erasure_coding.inline import (
    InlineEcVolume,
    inline_family_for,
    inline_shard_extent,
    read_commit_log,
    verify_inline_volume,
)
from seaweedfs_tpu.storage.needle import Needle

FAMILIES = ("rs_vandermonde", "cauchy", "pm_msr")


def _needle(nid: int, payload: bytes, cookie: int = 0x1234) -> Needle:
    n = Needle.create(payload)
    n.id, n.cookie = nid, cookie
    return n


def _fill(ev: InlineEcVolume, count: int, seed: int = 0,
          lo: int = 100, hi: int = 9000) -> dict:
    """Write ``count`` variable-size needles; returns {nid: payload}."""
    rng = np.random.default_rng(seed)
    written = {}
    for i in range(count):
        payload = rng.integers(0, 256, int(rng.integers(lo, hi)),
                               dtype=np.uint8).tobytes()
        nid = i + 1
        ev.write_needle(_needle(nid, payload), check_cookie=False)
        written[nid] = payload
    return written


def _mk(tmp_path, family: str, vid: int = 7, unit_kb: int = 8,
        monkeypatch=None) -> InlineEcVolume:
    if monkeypatch is not None:
        monkeypatch.setenv("WEED_EC_STRIPE_KB", str(unit_kb))
    return InlineEcVolume(str(tmp_path), "pics", vid,
                          family=family, create=True)


class TestStripeWriter:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_roundtrip_and_write_amp(self, tmp_path, monkeypatch, family):
        ev = _mk(tmp_path, family, monkeypatch=monkeypatch)
        try:
            written = _fill(ev, 80, seed=3)
            ev.writer.drain(tail=True)
            for nid, payload in written.items():
                assert ev.read_needle(nid).data == payload
            fam = ev.family
            # the write amp is the code rate plus the tiny commit-log /
            # index overhead — nowhere near the 3x-replica-then-encode
            # legacy floor.  pm_msr's 9/5 geometry has a higher rate.
            rate = fam.total_shards / fam.data_shards
            assert rate <= ev.writer.write_amp() <= rate + 0.15
            if family != "pm_msr":
                assert ev.writer.write_amp() <= 1.5
        finally:
            ev.close()

    def test_tail_served_before_any_commit(self, tmp_path, monkeypatch):
        # timer off: the only parity flushes are the ones we ask for,
        # so these reads MUST come from the in-memory tail stripe
        monkeypatch.setenv("WEED_EC_INLINE_FLUSH_MS", "0")
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        try:
            payload = b"tail-resident needle " * 40
            ev.write_needle(_needle(1, payload), check_cookie=False)
            assert ev.writer.stripes_committed == 0
            assert ev.read_needle(1).data == payload
            ev.writer.drain(tail=True)
            assert ev.writer.stripes_committed >= 1
            assert ev.read_needle(1).data == payload
        finally:
            ev.close()

    def test_commit_records_monotonic_and_crc_clean(self, tmp_path,
                                                    monkeypatch):
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        try:
            _fill(ev, 60, seed=9)
            ev.writer.drain(tail=True)
            base = ev.base_file_name()
        finally:
            ev.close()
        records = read_commit_log(base + ".scl")
        assert records
        assert os.path.getsize(base + ".scl") == \
            len(records) * inline.SCL_RECORD_SIZE  # no torn bytes
        full_rows = [r["row_index"] for r in records
                     if r["kind"] == inline.KIND_FULL]
        assert full_rows == sorted(full_rows)
        assert records[-1]["logical_size"] > 0

    @pytest.mark.parametrize("family", FAMILIES)
    def test_degraded_reads_byte_identical(self, tmp_path, monkeypatch,
                                           family):
        ev = _mk(tmp_path, family, monkeypatch=monkeypatch)
        try:
            written = _fill(ev, 60, seed=17)
            ev.writer.drain(tail=True)
            fam = ev.family
            # lose as many shards as the family tolerates for a plain
            # (k-of-n) decode: 2 data + 1 parity, or p for pm_msr
            losses = ([0, fam.data_shards - 1, fam.data_shards]
                      if family != "pm_msr" else [0, 2, 5, 13])
            for sid in losses[:fam.parity_shards]:
                shard = ev.shards.pop(sid)
                shard.close()
                os.remove(ev.base_file_name() + f".ec{sid:02d}")
            for nid, payload in written.items():
                assert ev.read_needle(nid).data == payload, \
                    f"{family}: needle {nid} diverged degraded"
        finally:
            ev.close()

    def test_delete_tombstones(self, tmp_path, monkeypatch):
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        try:
            written = _fill(ev, 10, seed=23)
            ev.delete_needle(5)
            with pytest.raises(Exception):
                ev.read_needle(5)
            assert ev.read_needle(6).data == written[6]
            assert ev.deleted_count() == 1
        finally:
            ev.close()


class TestRecovery:
    def test_remount_replays_acked_writes(self, tmp_path, monkeypatch):
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        written = _fill(ev, 50, seed=31)
        ev.writer.drain(tail=True)
        ev.close()
        ev = InlineEcVolume(str(tmp_path), "pics", 7)
        try:
            for nid, payload in written.items():
                assert ev.read_needle(nid).data == payload
            report = inline.audit_inline_volume(ev)
            assert report["ok"], report
        finally:
            ev.close()

    def test_torn_commit_record_is_discarded(self, tmp_path, monkeypatch):
        """A crash mid-.scl-append leaves a torn record; mount must
        truncate it and recommit from the data logs — every acked
        needle stays readable."""
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        written = _fill(ev, 40, seed=37)
        ev.writer.drain(tail=True)
        base = ev.base_file_name()
        ev.close()
        with open(base + ".scl", "r+b") as f:
            f.seek(0, os.SEEK_END)
            # half a record of garbage: the torn tail of an append
            f.write(b"\xde\xad" * (inline.SCL_RECORD_SIZE // 4))
        ev = InlineEcVolume(str(tmp_path), "pics", 7)
        try:
            for nid, payload in written.items():
                assert ev.read_needle(nid).data == payload
            assert os.path.getsize(base + ".scl") % \
                inline.SCL_RECORD_SIZE == 0  # garbage truncated away
            assert inline.audit_inline_volume(ev)["ok"]
        finally:
            ev.close()

    def test_corrupt_record_crc_stops_the_scan(self, tmp_path,
                                               monkeypatch):
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        _fill(ev, 40, seed=41)
        ev.writer.drain(tail=True)
        base = ev.base_file_name()
        ev.close()
        records = read_commit_log(base + ".scl")
        assert len(records) >= 2
        # flip a byte inside the LAST record's body: the scan must keep
        # every record before it and drop the corrupt one
        with open(base + ".scl", "r+b") as f:
            f.seek((len(records) - 1) * inline.SCL_RECORD_SIZE + 10)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        kept = read_commit_log(base + ".scl")
        assert len(kept) == len(records) - 1

    @pytest.mark.parametrize("family", FAMILIES)
    def test_remount_heals_deleted_shard_logs(self, tmp_path,
                                              monkeypatch, family):
        """A shard log missing at mount (lost device) must be rebuilt
        from the survivors, not silently recreated empty by O_CREAT:
        reads stay byte-identical and the deep scrub comes back clean
        without any shard marked absent."""
        from seaweedfs_tpu.storage.erasure_coding import to_ext

        ev = _mk(tmp_path, family, monkeypatch=monkeypatch)
        written = _fill(ev, 60, seed=47)
        ev.writer.drain(tail=True)
        base = ev.base_file_name()
        k = ev.writer.k
        ev.close()
        # one data shard and one parity shard, gone before the mount
        os.remove(base + to_ext(1))
        os.remove(base + to_ext(k + 1))
        ev = InlineEcVolume(str(tmp_path), "pics", 7)
        try:
            for nid, payload in written.items():
                assert ev.read_needle(nid).data == payload
            assert inline.audit_inline_volume(ev)["ok"]
            # the healed logs are back at their full committed extent
            for sid in (1, k + 1):
                assert os.path.getsize(base + to_ext(sid)) \
                    == ev.writer.shard_extent(sid)
        finally:
            ev.close()

    def test_remount_beyond_tolerance_fails_loudly(self, tmp_path,
                                                   monkeypatch):
        from seaweedfs_tpu.storage.erasure_coding import to_ext

        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        _fill(ev, 40, seed=53)
        ev.writer.drain(tail=True)
        base = ev.base_file_name()
        ev.close()
        for sid in range(5):  # 5 lost > the RS(10,4) tolerance
            os.remove(base + to_ext(sid))
        with pytest.raises(OSError, match="beyond the"):
            InlineEcVolume(str(tmp_path), "pics", 7)

    def test_verify_inline_volume_clean(self, tmp_path, monkeypatch):
        ev = _mk(tmp_path, "pm_msr", vid=9, monkeypatch=monkeypatch)
        _fill(ev, 30, seed=43)
        ev.writer.drain(tail=True)
        ev.close()
        report = verify_inline_volume(str(tmp_path), "pics", 9)
        assert report["ok"] and report["inline"]
        assert report["needles_checked"] == 30
        assert not report["corrupt"]


class TestGeometry:
    def test_shard_extent_partition(self):
        """Per-shard extents always partition the logical size."""
        unit, k = 4096, 10
        for logical in (0, 1, unit - 1, unit, unit * k,
                        unit * k + 5, unit * k * 3 + unit + 17):
            total = sum(inline_shard_extent(logical, unit, k, sid)
                        for sid in range(k))
            assert total == logical

    def test_stripe_unit_alpha_alignment(self, monkeypatch):
        from seaweedfs_tpu.storage.erasure_coding import codes as ec_codes

        monkeypatch.setenv("WEED_EC_STRIPE_KB", "3")
        fam = ec_codes.get_family("pm_msr")
        unit = inline.stripe_unit_bytes(fam)
        assert unit % (fam.sub_shards * 8) == 0
        assert unit >= 3 << 10


class TestPolicy:
    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("WEED_EC_INLINE", raising=False)
        monkeypatch.setenv("WEED_EC_CODE_PICS", "cauchy")
        assert inline_family_for("pics") is None

    def test_explicit_collection_policy(self, monkeypatch):
        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.setenv("WEED_EC_CODE_PICS", "cauchy")
        assert inline_family_for("pics") == "cauchy"

    def test_unconfigured_collection_stays_legacy(self, monkeypatch):
        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.delenv("WEED_EC_CODE", raising=False)
        monkeypatch.delenv("WEED_EC_CODE_LOGS", raising=False)
        assert inline_family_for("logs") is None

    def test_path_conf_and_global_fallback(self, monkeypatch):
        class PathConf:
            ec_code = "pm_msr"

        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.delenv("WEED_EC_CODE_DOCS", raising=False)
        assert inline_family_for("docs", PathConf()) == "pm_msr"
        monkeypatch.setenv("WEED_EC_CODE", "rs_vandermonde")
        assert inline_family_for("docs") == "rs_vandermonde"

    def test_bad_family_raises_before_any_log_is_cut(self, monkeypatch):
        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.setenv("WEED_EC_CODE_PICS", "no_such_code")
        with pytest.raises(Exception):
            inline_family_for("pics")


class TestStoreRouting:
    def test_assign_write_read_delete_heartbeat(self, tmp_path,
                                                monkeypatch):
        from seaweedfs_tpu.storage.store import Store

        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.setenv("WEED_EC_CODE_PICS", "rs_vandermonde")
        monkeypatch.setenv("WEED_EC_STRIPE_KB", "8")
        store = Store([str(tmp_path)])
        store.add_volume(42, "pics")
        ev = store.find_ec_volume(42)
        assert ev is not None and getattr(ev, "writer", None)
        payload = os.urandom(5000)
        size, unchanged = store.write_needle(42, _needle(1, payload))
        assert size > 0 and not unchanged
        assert store.read_needle(42, 1).data == payload
        hb = store.collect_heartbeat()
        vols = [v for v in hb["volumes"] if v["id"] == 42]
        assert vols and vols[0]["collection"] == "pics"
        assert not vols[0]["read_only"]
        # inline volumes are writable volumes to the master — they must
        # NOT also show up as sealed ec shard entries
        assert all(s["id"] != 42 for s in hb.get("ec_shards", []))
        store.delete_needle(42, _needle(1, b""))
        with pytest.raises(Exception):
            store.read_needle(42, 1)
        store.close()

    def test_legacy_collections_untouched(self, tmp_path, monkeypatch):
        from seaweedfs_tpu.storage.store import Store

        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.delenv("WEED_EC_CODE", raising=False)
        store = Store([str(tmp_path)])
        store.add_volume(3, "logs")  # no EC policy -> classic volume
        assert store.find_volume(3) is not None
        assert store.find_ec_volume(3) is None
        store.close()

    def test_remount_via_disk_location(self, tmp_path, monkeypatch):
        from seaweedfs_tpu.storage.store import Store

        monkeypatch.setenv("WEED_EC_INLINE", "1")
        monkeypatch.setenv("WEED_EC_CODE_PICS", "cauchy")
        monkeypatch.setenv("WEED_EC_STRIPE_KB", "8")
        store = Store([str(tmp_path)])
        store.add_volume(9, "pics")
        payload = os.urandom(3000)
        store.write_needle(9, _needle(4, payload))
        ev = store.find_ec_volume(9)
        ev.writer.drain(tail=True)
        store.close()
        store = Store([str(tmp_path)])  # load_existing_volumes remounts
        ev = store.find_ec_volume(9)
        assert ev is not None and ev.family.name == "cauchy"
        assert store.read_needle(9, 4).data == payload
        store.close()


@pytest.mark.qos
class TestQosIsolation:
    def test_degraded_reads_correct_under_inline_ingest(self, tmp_path,
                                                        monkeypatch):
        """Stripe flushes ride the background device lane: a degraded-
        read storm returns every needle's bytes while the inline writer
        commits stripes underneath it, and those commits are counted on
        the background lane.  What they cost the storm's p99 is not
        measured here."""
        from seaweedfs_tpu.qos.lanes import LANES

        monkeypatch.setenv("WEED_EC_STRIPE_KB", "8")
        LANES.reset()
        ev = _mk(tmp_path, "rs_vandermonde", monkeypatch=monkeypatch)
        try:
            written = _fill(ev, 120, seed=53, lo=2000, hi=6000)
            ev.writer.drain(tail=True)
            for sid in (0, 1, 11):  # force reconstruction per read
                shard = ev.shards.pop(sid)
                shard.close()
            nids = list(written)
            before = LANES.snapshot()["background_batches"]
            stop = threading.Event()

            def ingest():
                w = InlineEcVolume(str(tmp_path), "bg", 77,
                                   family="rs_vandermonde", create=True)
                i = 0
                blob = os.urandom(4096)
                try:
                    while not stop.is_set():
                        i += 1
                        w.write_needle(_needle(i, blob),
                                       check_cookie=False)
                finally:
                    w.close()

            th = threading.Thread(target=ingest, daemon=True)
            th.start()
            try:
                # at least 150 reads, and on until the writer has put a
                # stripe through the lane beside them
                deadline = time.monotonic() + 60
                reads = 0
                while (reads < 150 or LANES.snapshot()
                       ["background_batches"] == before):
                    assert time.monotonic() < deadline, "no stripe flushed"
                    nid = nids[reads % len(nids)]
                    assert ev.read_needle(nid).data == written[nid]
                    reads += 1
            finally:
                stop.set()
                th.join(timeout=30)
            assert not th.is_alive()
            assert LANES.snapshot()["background_batches"] > before
        finally:
            ev.close()
