"""Write durability (-fsync group commit) and in-flight byte throttles
(volume_write.go:233-306, volume_server.go:21-50)."""

import threading
import time

import pytest

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume, _FsyncBatcher
from seaweedfs_tpu.volume_server.server import _InflightGate


def _mk(nid, data, cookie=1):
    n = Needle.create(data)
    n.id, n.cookie = nid, cookie
    return n


class TestFsyncGroupCommit:
    def test_write_is_synced_before_ack(self, tmp_path, monkeypatch):
        v = Volume(str(tmp_path), "", 1, fsync=True)
        synced = []
        real = v._durable_sync
        monkeypatch.setattr(v, "_durable_sync",
                            lambda: (synced.append(1), real()))
        v.write_needle(_mk(1, b"durable"))
        assert synced, "ack returned before any fsync"
        v.close()

    def test_concurrent_writers_share_fsyncs(self, tmp_path, monkeypatch):
        v = Volume(str(tmp_path), "", 2, fsync=True)
        syncs = []
        real = v._durable_sync

        def slow_sync():
            time.sleep(0.05)
            syncs.append(1)
            real()

        monkeypatch.setattr(v, "_durable_sync", slow_sync)
        v._batcher = None  # rebuild the worker against the patched sync
        n_writers = 16
        gate = threading.Barrier(n_writers)

        def writer(i):
            gate.wait()  # all writers race at once: group commit must
            # coalesce them (without the barrier, staggered starts could
            # legally produce one sync per write on a 1-core box)
            v.write_needle(_mk(10 + i, b"x" * 100))

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(n_writers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        # group commit: far fewer fsyncs than writers, but >= 1
        assert 1 <= len(syncs) < n_writers
        v.close()

    def test_survives_crash_without_close(self, tmp_path):
        """Simulated crash: write with fsync, drop the handles without
        flushing/closing, reload from disk — the write must be there."""
        v = Volume(str(tmp_path), "", 3, fsync=True)
        v.write_needle(_mk(7, b"must survive"))
        # crash: no close(), no flush — just forget the object (the idx
        # append-log buffer was fsynced by the group commit)
        del v
        v2 = Volume(str(tmp_path), "", 3)
        assert v2.read_needle(7, cookie=1).data == b"must survive"
        v2.close()

    def test_batcher_close_releases_waiters(self):
        b = _FsyncBatcher(lambda: time.sleep(0.01))
        b.wait_durable()
        b.close()


class TestInflightGate:
    def test_unlimited_by_default(self):
        g = _InflightGate(0)
        assert g.acquire(1 << 40)
        g.release(1 << 40)

    def test_blocks_over_limit_until_release(self):
        g = _InflightGate(100)
        assert g.acquire(80)
        done = []

        def second():
            done.append(g.acquire(50, timeout=5))

        th = threading.Thread(target=second)
        th.start()
        time.sleep(0.1)
        assert not done  # parked: 80 + 50 > 100
        g.release(80)
        th.join(timeout=5)
        assert done == [True]
        g.release(50)

    def test_times_out_to_429(self):
        g = _InflightGate(10)
        assert g.acquire(8)
        assert not g.acquire(5, timeout=0.2)
        g.release(8)

    def test_single_oversized_request_allowed_when_alone(self):
        g = _InflightGate(10)
        assert g.acquire(500)  # alone: may exceed (reference semantics)
        g.release(500)


class TestServerThrottle:
    def test_upload_429_when_saturated(self, tmp_path):
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.rpc.http_rpc import RpcError, call
        from seaweedfs_tpu.volume_server.server import VolumeServer

        master = MasterServer(port=0, pulse_seconds=0.2)
        master.start()
        d = tmp_path / "v"
        d.mkdir()
        vs = VolumeServer([str(d)], master.address, port=0,
                          pulse_seconds=0.2, upload_limit_mb=1)
        vs.start()
        vs.heartbeat_once()
        try:
            a = call(master.address, "/dir/assign")
            # saturate the gate from another "request"
            vs.upload_gate.timeout = 1.0
            vs.upload_gate.acquire(900 << 10)
            cond = vs.upload_gate._cond
            parked, wait = [], cond.wait

            def counted_wait(left):
                parked.append(left)
                return wait(left)

            cond.wait = counted_wait
            with pytest.raises(RpcError) as e:
                call(a["url"], f"/{a['fid']}", raw=b"y" * (300 << 10),
                     method="POST", timeout=60)
            assert e.value.status == 429
            assert parked, "refused without waiting on the gate"
            vs.upload_gate.release(900 << 10)
            # and succeeds once the gate frees up
            w = call(a["url"], f"/{a['fid']}", raw=b"y" * (300 << 10),
                     method="POST", timeout=60)
            assert w["size"] > 0
        finally:
            vs.stop()
            master.stop()
