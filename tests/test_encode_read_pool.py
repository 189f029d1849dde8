"""The read stage of the device encode pipeline as a coordinator and a pool
of I/O workers (parallel/batched_encode.py, _PipelineIO): the same shard
bytes and .vif CRCs whatever the worker count, in both staging layouts, and
a worker's failure fails the seal and leaves no thread behind.  Results,
never timings: the CPU backend says nothing about the chip host's files.
"""

import functools
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c as crc_host
from seaweedfs_tpu.ops.rs_numpy import NumpyEncoder
from seaweedfs_tpu.parallel import batched_encode as be
from seaweedfs_tpu.storage.erasure_coding import encoder as ec_encoder
from seaweedfs_tpu.storage.erasure_coding import to_ext

# (large block, small block): the chunk is the small block.  A chunk that
# packs into int32 words takes the pooled step ("kb" staging, buf[i, k]);
# an odd one the XLA step ("bk" staging, buf[k, i])
LAYOUTS = {"kb": (10000, 100), "bk": (500, 50)}
BACKENDS = {"kb": "device-pooled-swar", "bk": "device-xla"}


def _size(large: int, small: int) -> int:
    """Three large rows, three full small rows, and a tail row of two
    full blocks, one partial block and seven blocks of padding."""
    return large * 10 * 3 + small * 10 * 3 + small * 2 + small // 2 + 7


def _make_volume(tmp_path, name: str, size: int) -> str:
    base = str(tmp_path / name)
    rng = np.random.default_rng(size)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    return base


def _force_workers(monkeypatch, n: int):
    """Through the constructor's argument, as a caller with its own rule
    for N would; no environment variable reaches it."""
    monkeypatch.setattr(be, "_PipelineIO", functools.partial(
        be._PipelineIO, read_workers=n))


def _seal(base: str, layout: str) -> tuple[list[int], dict]:
    large, small = LAYOUTS[layout]
    st: dict = {}
    # the fewest units a batch the mesh allows: several batches, so
    # their order is exercised
    crcs = be.encode_volumes([base], large_block=large, small_block=small,
                             batch_units=2, stage_stats=st)[base]
    ec_encoder.save_volume_info(base, version=3,
                                extra={"shard_crc32c": crcs})
    return crcs, st


def _shards(base: str) -> list[bytes]:
    out = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


def _pipeline_threads() -> list[str]:
    return [t.name for t in threading.enumerate()
            if t.name.startswith("ec-encode")]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("workers", [1, 2, 4, 32])   # 32: more than cores
def test_shards_and_vif_crcs_do_not_depend_on_the_worker_count(
        tmp_path, monkeypatch, eager_switching, workers, layout):
    large, small = LAYOUTS[layout]
    size = _size(large, small)
    base = _make_volume(tmp_path, "v", size)
    one = _make_volume(tmp_path, "one", size)       # same seed, same bytes

    _force_workers(monkeypatch, workers)
    crcs, st = _seal(base, layout)
    _force_workers(monkeypatch, 1)
    crcs_one, st_one = _seal(one, layout)

    assert st["backend"] == BACKENDS[layout]
    assert st["read_workers"] == workers and st_one["read_workers"] == 1
    assert st["batches"] > 3, st["batch_units"]
    for s in (st, st_one):
        assert isinstance(s["read_worker_busy"], float)
        cap = s["read"] * s["read_workers"] + 0.002
        assert s["read_dat"] + s["read_data_write"] \
            <= s["read_worker_busy"] + 0.002 <= cap + 0.002

    got, want = _shards(base), _shards(one)
    assert got == want
    assert crcs == crcs_one
    assert ec_encoder.load_volume_info(base)["shard_crc32c"] == crcs
    assert crcs == [crc_host.crc32c(s) for s in got]

    # the data shards are the .dat's striping (zero-padded), and the
    # parity is the plain codec's over them
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    pos = 0
    data = [bytearray() for _ in range(10)]
    for block, count in ((large, 3), (small, 4)):
        for _ in range(count):
            for i in range(10):
                piece = dat[pos:pos + block]
                data[i] += piece + bytes(block - len(piece))
                pos += block
    assert [bytes(d) for d in data] == got[:10]
    full = NumpyEncoder(10, 4).encode(
        [np.frombuffer(s, dtype=np.uint8) for s in got[:10]] + [None] * 4)
    assert [np.asarray(p).tobytes() for p in full[10:]] == got[10:]
    assert not _pipeline_threads()


@pytest.mark.parametrize("cores,want", [(1, 1), (2, 1), (13, 4), (30, 4)])
def test_worker_count_follows_the_cores_the_process_may_use(
        monkeypatch, cores, want):
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(cores)))
    assert be._read_workers() == want


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("workers", [1, 4])
def test_dat_truncated_under_the_seal_fails_it(tmp_path, monkeypatch,
                                               workers, layout):
    large, small = LAYOUTS[layout]
    size = _size(large, small)
    base = _make_volume(tmp_path, "cut", size)
    plan_volume = be._plan_volume

    def plan_then_truncate(b, *a):
        plan = plan_volume(b, *a)
        os.truncate(b + ".dat", size - 3 * small)   # after it was planned
        return plan

    monkeypatch.setattr(be, "_plan_volume", plan_then_truncate)
    _force_workers(monkeypatch, workers)
    with pytest.raises(OSError, match="shorter than planned"):
        be.encode_volumes([base], large_block=large, small_block=small,
                          batch_units=2)
    assert not _pipeline_threads()


@pytest.mark.parametrize("workers", [1, 4])
def test_failing_data_shard_write_fails_the_seal(tmp_path, monkeypatch,
                                                 workers):
    large, small = LAYOUTS["kb"]
    base = _make_volume(tmp_path, "nowrite", _size(large, small))
    _force_workers(monkeypatch, workers)
    monkeypatch.setattr(os, "pwritev", lambda fd, bufs, off: 0)
    with pytest.raises(OSError, match="no progress"):
        be.encode_volumes([base], large_block=large, small_block=small,
                          batch_units=2)
    assert not _pipeline_threads()
