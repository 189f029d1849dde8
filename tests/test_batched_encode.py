"""Streaming batched TPU encode pipeline: byte parity with the host path,
fused shard-file CRC32Cs, multi-volume batching (parallel/batched_encode.py).
"""

import errno
import itertools
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c as crc_host
from seaweedfs_tpu.parallel import batched_encode as be
from seaweedfs_tpu.parallel.batched_encode import (_chunk_len, _plan_volume,
                                                   encode_volumes)
from seaweedfs_tpu.storage.erasure_coding import encoder as ec_encoder
from seaweedfs_tpu.storage.erasure_coding import to_ext

LARGE, SMALL = 10000, 100  # ec_test.go's scaled-down block sizes


def _make_volume(tmp_path, name: str, size: int, seed: int) -> str:
    base = str(tmp_path / name)
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size).astype(np.uint8).tobytes())
    return base


def _host_reference(tmp_path, base: str, tag: str) -> str:
    ref = str(tmp_path / tag)
    os.link(base + ".dat", ref + ".dat")
    ec_encoder.write_ec_files(ref, large_block_size=LARGE,
                              small_block_size=SMALL, batched=False)
    return ref


class TestBatchedEncode:
    @pytest.mark.parametrize("size", [1, 999, SMALL * 10, SMALL * 10 * 7 + 13,
                                      LARGE * 10 + 1, LARGE * 10 * 2 + 12345])
    def test_bytes_match_host_path(self, tmp_path, size):
        base = _make_volume(tmp_path, "v", size, size)
        crcs = encode_volumes([base], large_block=LARGE, small_block=SMALL)
        ref = _host_reference(tmp_path, base, "ref")
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                got = f.read()
            with open(ref + to_ext(i), "rb") as f:
                want = f.read()
            assert got == want, f"shard {i} differs for size {size}"
            assert crcs[base][i] == crc_host.crc32c(got), f"crc shard {i}"

    def test_multi_volume_one_pipeline(self, tmp_path):
        """Chunks of several volumes share device dispatches (config 4)."""
        bases = [_make_volume(tmp_path, f"v{k}", 997 * (k + 1) + k, k)
                 for k in range(5)]
        crcs = encode_volumes(bases, large_block=LARGE, small_block=SMALL)
        for k, base in enumerate(bases):
            ref = _host_reference(tmp_path, base, f"ref{k}")
            for i in range(14):
                with open(base + to_ext(i), "rb") as f:
                    got = f.read()
                with open(ref + to_ext(i), "rb") as f:
                    want = f.read()
                assert got == want, f"vol {k} shard {i}"
                assert crcs[base][i] == crc_host.crc32c(got)

    def test_empty_volume(self, tmp_path):
        base = _make_volume(tmp_path, "empty", 0, 0)
        crcs = encode_volumes([base], large_block=LARGE, small_block=SMALL)
        assert crcs[base] == [0] * 14
        for i in range(14):
            assert os.path.getsize(base + to_ext(i)) == 0

    @pytest.mark.parametrize("size", [1, SMALL * 10 * 7 + 13,
                                      LARGE * 10 * 2 + 12345])
    def test_host_pipeline_mode_matches(self, tmp_path, size):
        """encode_volumes(host_codec=True): the same pipeline with the
        native codec as the compute stage — byte-identical shards and
        correct rolling CRCs (the link-capped auto-fallback path)."""
        base = _make_volume(tmp_path, "hp", size, size % 97)
        crcs = encode_volumes([base], large_block=LARGE,
                              small_block=SMALL, host_codec=True)
        ref = _host_reference(tmp_path, base, "hpref")
        for i in range(14):
            with open(base + to_ext(i), "rb") as f:
                got = f.read()
            with open(ref + to_ext(i), "rb") as f:
                assert got == f.read(), f"shard {i}"
            assert crcs[base][i] == crc_host.crc32c(got), f"crc {i}"

    def test_odd_chunk_length_on_cpu_mesh(self, tmp_path):
        """Chunk lengths not divisible by 4 must keep working on CPU
        meshes (the SWAR packing needs %4; the step falls back to the
        bit-matmul formulation — round-4 review finding)."""
        base = _make_volume(tmp_path, "odd", 1230, 3)
        crcs = encode_volumes([base], large_block=500, small_block=50)
        ref = str(tmp_path / "oddref")
        os.link(base + ".dat", ref + ".dat")
        ec_encoder.write_ec_files(ref, large_block_size=500,
                                  small_block_size=50, batched=False)
        for i in range(14):
            with open(base + to_ext(i), "rb") as a, \
                    open(ref + to_ext(i), "rb") as b:
                got = a.read()
                assert got == b.read(), f"shard {i}"
            assert crcs[base][i] == crc_host.crc32c(got)

    def test_host_pipeline_multi_volume(self, tmp_path):
        bases = [_make_volume(tmp_path, f"hm{k}", 977 * (k + 1), k)
                 for k in range(5)]
        crcs = encode_volumes(bases, large_block=LARGE, small_block=SMALL,
                              host_codec=True)
        for k, base in enumerate(bases):
            ref = _host_reference(tmp_path, base, f"hmref{k}")
            for i in range(14):
                with open(base + to_ext(i), "rb") as f:
                    got = f.read()
                with open(ref + to_ext(i), "rb") as f:
                    assert got == f.read(), f"vol {k} shard {i}"
                assert crcs[base][i] == crc_host.crc32c(got)

    def test_write_ec_files_default_is_batched(self, tmp_path):
        """write_ec_files with no codec returns the fused shard CRCs."""
        from seaweedfs_tpu.util.platform import jax_usable

        if not jax_usable():
            pytest.skip("jax backend unreachable; default path falls back")
        base = _make_volume(tmp_path, "w", 54321, 3)
        crcs = ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                         small_block_size=SMALL)
        assert isinstance(crcs, list) and len(crcs) == 14
        with open(base + to_ext(12), "rb") as f:
            assert crcs[12] == crc_host.crc32c(f.read())


class TestBackendAutoSelection:
    """Link-throughput-aware default: behind a slow host<->device link the
    default ec.encode must never lose to the host codec (round-3 verdict
    item 2); -ec.backend=tpu still forces the device pipeline."""

    def test_slow_link_prefers_host_codec(self, tmp_path, monkeypatch):
        from seaweedfs_tpu.util import platform as plat

        monkeypatch.setattr(plat, "_probe", lambda: (True, "tpu"))
        monkeypatch.setattr(plat, "link_throughput",
                            lambda **kw: (5.0, 2.0))  # a link in single MB/s
        assert plat.predicted_batched_gibps() < 0.01
        assert plat.prefer_batched_encode() is False
        # multi-core host: the fallback is the PIPELINED host mode,
        # which still returns shard CRCs (worker sizing reads
        # available_cpu_count — the affinity mask, not os.cpu_count)
        monkeypatch.setattr(plat, "available_cpu_count", lambda: 8)
        base = _make_volume(tmp_path, "slow", 12345, 5)
        crcs = ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                         small_block_size=SMALL)
        assert isinstance(crcs, list) and len(crcs) == 14
        with open(base + to_ext(12), "rb") as f:
            assert crcs[12] == crc_host.crc32c(f.read())
        # 1-core host: the host pipeline runs inline (no reader thread /
        # worker pool — they convoy the GIL on one core) but still
        # produces identical shards and fused CRCs
        monkeypatch.setattr(plat, "available_cpu_count", lambda: 1)
        base2 = _make_volume(tmp_path, "slow1c", 12345, 5)
        crcs2 = ec_encoder.write_ec_files(base2, large_block_size=LARGE,
                                          small_block_size=SMALL)
        assert crcs2 == crcs
        for i in range(14):
            with open(base + to_ext(i), "rb") as a, \
                    open(base2 + to_ext(i), "rb") as b:
                assert a.read() == b.read(), f"shard {i}"

    def test_fast_link_prefers_batched(self, tmp_path):
        from seaweedfs_tpu.util import platform as plat

        # a fast-link TPU picks batched...
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(plat, "_probe", lambda: (True, "tpu"))
            mp.setattr(plat, "link_throughput", lambda **kw: (1e6, 1e6))
            assert plat.prefer_batched_encode() is True
        # ...and so does the CPU/virtual-mesh backend (device == host, no
        # link to lose on); the actual write runs on the real backend
        assert plat.prefer_batched_encode() is True
        base = _make_volume(tmp_path, "fast", 12345, 6)
        crcs = ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                         small_block_size=SMALL)
        assert isinstance(crcs, list) and len(crcs) == 14

    def test_backend_tpu_forces_batched_on_slow_link(self, monkeypatch,
                                                     tmp_path):
        from seaweedfs_tpu.util import platform as plat

        monkeypatch.setattr(plat, "link_throughput",
                            lambda **kw: (5.0, 2.0))
        base = _make_volume(tmp_path, "forced", 23456, 7)
        # batched=True is what store.ec_generate passes for -ec.backend=tpu
        crcs = ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                         small_block_size=SMALL,
                                         batched=True)
        assert isinstance(crcs, list) and len(crcs) == 14

    def test_slow_link_encode_decode_roundtrip(self, tmp_path,
                                               monkeypatch):
        """The host-selected path must produce byte-identical shards to
        the batched path."""
        from seaweedfs_tpu.util import platform as plat

        base = _make_volume(tmp_path, "rt", 77777, 8)
        ref = _make_volume(tmp_path, "rtref", 77777, 8)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(plat, "_probe", lambda: (True, "tpu"))
            mp.setattr(plat, "link_throughput", lambda **kw: (5.0, 2.0))
            ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                      small_block_size=SMALL)
        ec_encoder.write_ec_files(ref, large_block_size=LARGE,
                                  small_block_size=SMALL)
        for i in range(14):
            with open(base + to_ext(i), "rb") as a, \
                    open(ref + to_ext(i), "rb") as b:
                assert a.read() == b.read(), f"shard {i}"


class TestPlan:
    def test_row_plan_matches_striping(self, tmp_path):
        base = _make_volume(tmp_path, "p", LARGE * 10 * 2 + 5, 9)
        plan = _plan_volume(base, LARGE, SMALL)
        # two large rows (the loop keeps striping while remaining exceeds
        # one large row, ec_encoder.go:201), then small rows for the tail
        assert plan.rows[0][2] == LARGE and plan.rows[1][2] == LARGE
        assert all(b == SMALL for _, _, b in plan.rows[2:])
        # shard offsets accumulate block sizes
        assert plan.rows[1][1] == LARGE
        assert plan.rows[2][1] == 2 * LARGE

    def test_chunk_len_divides_blocks(self):
        assert _chunk_len(1 << 30, 1 << 20) == 1 << 20
        assert _chunk_len(10000, 100) == 100
        assert _chunk_len(300, 77) == 1  # gcd fallback


class TestBatchedRebuild:
    @pytest.mark.parametrize("missing", [[0], [11], [0, 5, 11, 13],
                                         [6, 7, 8, 9], [10, 11, 12, 13]])
    def test_rebuilt_bytes_match_originals(self, tmp_path, missing):
        from seaweedfs_tpu.parallel.batched_encode import rebuild_shards

        base = _make_volume(tmp_path, "r", LARGE * 10 + 4321, 11)
        ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                  small_block_size=SMALL)
        golden = {}
        for sid in missing:
            with open(base + to_ext(sid), "rb") as f:
                golden[sid] = f.read()
            os.unlink(base + to_ext(sid))
        crcs = rebuild_shards(base)
        assert sorted(crcs) == sorted(missing)
        for sid in missing:
            with open(base + to_ext(sid), "rb") as f:
                got = f.read()
            assert got == golden[sid], f"shard {sid} differs"
            assert crcs[sid] == crc_host.crc32c(got)

    def test_rebuild_via_encoder_api_default_batched(self, tmp_path):
        base = _make_volume(tmp_path, "ra", 99999, 12)
        ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                  small_block_size=SMALL)
        with open(base + to_ext(3), "rb") as f:
            want = f.read()
        os.unlink(base + to_ext(3))
        from seaweedfs_tpu.util.platform import jax_usable

        if not jax_usable():
            pytest.skip("jax backend unreachable")
        assert sorted(ec_encoder.rebuild_ec_files(base)) == [3]
        with open(base + to_ext(3), "rb") as f:
            assert f.read() == want

    def test_rebuild_noop_and_too_few(self, tmp_path):
        from seaweedfs_tpu.parallel.batched_encode import rebuild_shards

        base = _make_volume(tmp_path, "rn", 5000, 13)
        ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                  small_block_size=SMALL)
        assert rebuild_shards(base) == {}
        for sid in range(5):
            os.unlink(base + to_ext(sid))
        with pytest.raises(ValueError):
            rebuild_shards(base)


class TestScrub:
    def test_scrub_detects_and_repairs_corruption(self, tmp_path):
        from seaweedfs_tpu.storage.tools import scrub_ec_volume

        base = _make_volume(tmp_path, "5", 77777, 21)
        crcs = ec_encoder.write_ec_files(base, large_block_size=LARGE,
                                         small_block_size=SMALL)
        ec_encoder.save_volume_info(base, version=3,
                                    extra={"shard_crc32c": crcs})
        clean = scrub_ec_volume(str(tmp_path), "", 5)
        assert clean["checked"] == list(range(14))
        assert not clean["corrupt"] and not clean["missing"]

        # flip a byte in one shard, delete another
        with open(base + to_ext(2), "r+b") as f:
            f.seek(100)
            b = f.read(1)
            f.seek(100)
            f.write(bytes([b[0] ^ 0xFF]))
        os.unlink(base + to_ext(12))

        bad = scrub_ec_volume(str(tmp_path), "", 5)
        assert bad["corrupt"] == [2] and bad["missing"] == [12]

        fixed = scrub_ec_volume(str(tmp_path), "", 5, repair=True)
        assert sorted(fixed["repaired"]) == [2, 12]
        final = scrub_ec_volume(str(tmp_path), "", 5)
        assert final["checked"] == list(range(14))
        assert not final["corrupt"] and not final["missing"]


def test_host_pipeline_tiny_blocks_iov_cap(tmp_path):
    """Block sizes small enough that a span would exceed IOV_MAX rows
    must still encode (pwritev is capped at 1024 iovecs)."""
    import numpy as np

    from seaweedfs_tpu.ops.crc32c import crc32c
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes
    from seaweedfs_tpu.storage.erasure_coding import to_ext

    base = str(tmp_path / "tiny")
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 2_000_000, dtype=np.uint8)
    data.tofile(base + ".dat")
    crcs = encode_volumes([base], large_block=10000, small_block=100,
                          host_codec=True)[base]
    ref = str(tmp_path / "tinyref")
    os.link(base + ".dat", ref + ".dat")
    ec_encoder.write_ec_files(ref, large_block_size=10000,
                              small_block_size=100, batched=False)
    for i in range(14):
        got = np.fromfile(base + to_ext(i), dtype=np.uint8)
        want = np.fromfile(ref + to_ext(i), dtype=np.uint8)
        assert np.array_equal(got, want), f"shard {i}"
        assert crcs[i] == crc32c(got.tobytes())


def test_host_pipeline_large_block_col_chunks(tmp_path):
    """Rows whose block size exceeds _HOST_SPAN_MAX_BLOCK take the
    column-chunk path (strided preads per shard instead of one
    contiguous span) — byte- and CRC-identical to the sync loop."""
    import numpy as np

    from seaweedfs_tpu.parallel import batched_encode as be
    from seaweedfs_tpu.ops.crc32c import crc32c
    from seaweedfs_tpu.storage.erasure_coding import to_ext

    large, small = 16 << 20, 1 << 20
    base = str(tmp_path / "big")
    rng = np.random.default_rng(9)
    # > large*10 so the plan emits one 16 MB-block large row (the col
    # path: 16 MB > _HOST_SPAN_MAX_BLOCK) plus small-row tail
    n = large * 10 + 3 * small * 10 + 12345
    with open(base + ".dat", "wb") as f:
        left = n
        while left:
            take = min(32 << 20, left)
            f.write(rng.integers(0, 256, take, dtype=np.uint8).tobytes())
            left -= take
    crcs = be.encode_volumes([base], large_block=large, small_block=small,
                             host_codec=True)[base]
    ref = str(tmp_path / "bigref")
    os.link(base + ".dat", ref + ".dat")
    ec_encoder.write_ec_files(ref, large_block_size=large,
                              small_block_size=small, batched=False)
    for i in range(14):
        with open(base + to_ext(i), "rb") as a, \
                open(ref + to_ext(i), "rb") as b:
            got = a.read()
            assert got == b.read(), f"shard {i}"
        assert crcs[i] == crc_host.crc32c(got), f"crc {i}"


class TestWriteBehindStage:
    """The decoupled writer stage (three-stage host pipeline): async
    write-behind must be byte- and CRC-identical to the inline path,
    partial pwritev must hard-fail the encode, and the stage-stats
    schema must attribute write and flush separately."""

    def _encode(self, tmp_path, monkeypatch, tag, size=1_234_567, seed=21,
                **env):
        base = _make_volume(tmp_path, tag, size, seed)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        # a pacing window this small volume fills
        monkeypatch.setattr(be, "WRITE_FLUSH_BYTES", 1 << 20)
        st: dict = {}
        crcs = encode_volumes([base], large_block=LARGE, small_block=SMALL,
                              host_codec=True, stage_stats=st)[base]
        return base, crcs, st

    def test_write_behind_matches_inline(self, tmp_path, monkeypatch):
        """Async write-behind (4 workers, so 2 writers; tiny pacing
        window) produces shards byte- and CRC-identical to the
        single-threaded inline path on the same input."""
        b_async, c_async, st = self._encode(
            tmp_path, monkeypatch, "wb", WEED_EC_HOST_WORKERS="4")
        assert st["write_behind"] is True and st["writers"] == 2
        b_inline, c_inline, st2 = self._encode(
            tmp_path, monkeypatch, "inl", WEED_EC_HOST_WORKERS="1")
        assert st2["write_behind"] is False and st2["writers"] == 0
        assert c_async == c_inline
        for i in range(14):
            with open(b_async + to_ext(i), "rb") as a, \
                    open(b_inline + to_ext(i), "rb") as b:
                got = a.read()
                assert got == b.read(), f"shard {i}"
            assert c_async[i] == crc_host.crc32c(got), f"crc {i}"

    def test_stage_stats_schema(self, tmp_path, monkeypatch):
        """With the writer stage enabled and >=2 workers, stage stats
        attribute read / encode_crc / write / flush separately, plus the
        pipeline-shape fields."""
        _, _, st = self._encode(
            tmp_path, monkeypatch, "ss", WEED_EC_HOST_WORKERS="2")
        for k in ("read", "encode_crc", "write", "flush", "wall"):
            assert isinstance(st[k], float), k
            assert st[k] >= 0.0, k
        for k in ("read", "encode_crc", "write", "flush"):
            assert isinstance(st[f"{k}_frac"], float), k
        assert st["workers"] == 2
        assert st["writers"] == 1          # workers // 2, at least 1
        assert st["write_behind"] is True
        assert isinstance(st["flushes"], int)
        assert st["items"] >= 1
        # busy seconds never double-count: write excludes flush time
        assert st["write"] + st["flush"] <= st["wall"] * (st["workers"] + 1)

    @pytest.mark.parametrize("workers", ["1", "4"])
    def test_partial_pwritev_zero_progress_is_hard_error(
            self, tmp_path, monkeypatch, workers):
        """A pwritev that makes no progress must fail the encode — never
        silently truncate a shard whose CRC was computed from memory."""
        base = _make_volume(tmp_path, f"zp{workers}", 123_456, 7)
        monkeypatch.setenv("WEED_EC_HOST_WORKERS", workers)
        monkeypatch.setattr(os, "pwritev", lambda fd, bufs, off: 0)
        with pytest.raises(OSError, match="no progress"):
            encode_volumes([base], large_block=LARGE, small_block=SMALL,
                           host_codec=True)

    def test_short_pwritev_retries_to_full_length(self, tmp_path,
                                                  monkeypatch):
        """Transient short kernel writes (partial progress) are retried
        from where the kernel stopped until every byte lands — output
        stays byte-identical."""
        real_pwritev = os.pwritev
        calls = {"n": 0}

        def short_pwritev(fd, bufs, offset):
            calls["n"] += 1
            mv = memoryview(bufs[0]).cast("B")
            # write at most half of the first iovec (>=1 byte)
            return real_pwritev(fd, [mv[:max(1, mv.nbytes // 2)]], offset)

        base = _make_volume(tmp_path, "short", 234_567, 13)
        monkeypatch.setenv("WEED_EC_HOST_WORKERS", "2")
        monkeypatch.setattr(os, "pwritev", short_pwritev)
        crcs = encode_volumes([base], large_block=LARGE, small_block=SMALL,
                              host_codec=True)[base]
        monkeypatch.setattr(os, "pwritev", real_pwritev)
        assert calls["n"] > 0
        ref = _host_reference(tmp_path, base, "shortref")
        for i in range(14):
            with open(base + to_ext(i), "rb") as a, \
                    open(ref + to_ext(i), "rb") as b:
                got = a.read()
                assert got == b.read(), f"shard {i}"
            assert crcs[i] == crc_host.crc32c(got), f"crc {i}"

    def testpwritev_full_unit(self, tmp_path):
        """pwritev_full unit coverage: multi-iovec writes land fully at
        the right offset; zero progress raises."""
        from seaweedfs_tpu.parallel.batched_encode import pwritev_full

        path = str(tmp_path / "f")
        fd = os.open(path, os.O_CREAT | os.O_WRONLY, 0o644)
        try:
            bufs = [b"aa", b"bbb", b"cccc"]
            n = pwritev_full(fd, bufs, 3)
            assert n == 9
        finally:
            os.close(fd)
        with open(path, "rb") as f:
            assert f.read() == b"\0\0\0aabbbcccc"


JOBS = ("seal", "rebuild", "host")
LOST = (0, 3, 11, 13)


def _writer_thread() -> bool:
    return threading.current_thread().name in ("ec-encode-writer",
                                               "ec-rebuild-writer")


def _pipeline_threads() -> list:
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("ec-encode", "ec-rebuild"))]


class TestWriteStage:
    """The write-behind stage the seal, the rebuild and the host
    pipeline compose (`_WriteStage`): what a failed write does to each
    job, the order of its writes, and `put` on a stopped job."""

    def _job(self, job, tmp_path, monkeypatch, before=lambda: None):
        """(base, the shard ids the job writes, run): `run()` makes
        several batches (or spans) of a small volume and returns
        {shard id: CRC32C}.  `before` runs once the rebuild's volume is
        sealed and its shards are lost."""
        monkeypatch.setenv("WEED_EC_DEVICE_SHARD", "1")
        base = _make_volume(tmp_path, job, LARGE * 10 * 2 + 12345, 31)
        kw = dict(large_block=LARGE, small_block=SMALL)
        if job == "seal":
            before()
            return base, range(14), lambda: dict(enumerate(
                encode_volumes([base], batch_units=40, **kw)[base]))
        if job == "host":
            monkeypatch.setenv("WEED_EC_HOST_WORKERS", "4")
            # a row of large blocks a span, four rows of small ones
            monkeypatch.setattr(be, "_HOST_SPAN_BYTES", SMALL * 10 * 4)
            before()
            return base, range(14), lambda: dict(enumerate(
                encode_volumes([base], host_codec=True, **kw)[base]))
        encode_volumes([base], **kw)
        for sid in LOST:
            os.remove(base + to_ext(sid))
        monkeypatch.setattr(be, "MAX_CHUNK_BYTES", 4096)
        before()
        return base, LOST, lambda: be.rebuild_shards(base, batch_units=1)

    @pytest.mark.parametrize("job", JOBS)
    def test_a_failed_write_fails_the_job_and_frees_everything(
            self, job, tmp_path, monkeypatch):
        """The third pwritev of the writer thread fails: the job raises
        that OSError, every thread of it is joined, every lease of the
        slab pool is back, and a rebuild leaves none of the files it
        created."""
        from seaweedfs_tpu.ops.device_pool import get_pool

        real = os.pwritev
        calls = itertools.count(1)

        def pwritev(fd, bufs, offset):
            if _writer_thread() and next(calls) == 3:
                raise OSError(errno.EIO, "injected into the writer")
            return real(fd, bufs, offset)

        base, _, run = self._job(
            job, tmp_path, monkeypatch,
            before=lambda: monkeypatch.setattr(os, "pwritev", pwritev))
        with pytest.raises(OSError, match="injected into the writer"):
            run()
        assert next(calls) > 3
        assert not _pipeline_threads()
        assert get_pool().snapshot()["leased_slots"] == 0
        if job == "rebuild":
            for sid in range(14):
                assert os.path.exists(base + to_ext(sid)) == \
                    (sid not in LOST)

    @pytest.mark.parametrize("job", JOBS)
    def test_a_writer_writes_in_the_order_of_put(self, job, tmp_path,
                                                 monkeypatch):
        """Batches are put in order, so each writer's offsets in a file
        only rise, and the rolling CRCs chained in that order are the
        files' CRC32Cs."""
        real = os.pwritev
        offsets: dict = {}

        def pwritev(fd, bufs, offset):
            if _writer_thread():
                offsets.setdefault((threading.get_ident(), fd),
                                   []).append(offset)
            return real(fd, bufs, offset)

        base, shards, run = self._job(
            job, tmp_path, monkeypatch,
            before=lambda: monkeypatch.setattr(os, "pwritev", pwritev))
        crcs = run()
        writers = {tid for tid, _ in offsets}
        if job == "host":   # two writers, and either may take every span
            assert len(writers) in (1, 2)
            assert len({fd for _, fd in offsets}) == 14
        else:
            assert len(writers) == 1
            assert min(map(len, offsets.values())) > 2
        for offs in offsets.values():
            assert offs == sorted(set(offs))
        for sid in shards:
            with open(base + to_ext(sid), "rb") as f:
                assert crcs[sid] == crc_host.crc32c(f.read()), sid

    def test_put_gives_up_when_the_job_stops_with_the_queue_full(self):
        errors: list = []
        stop, gate = threading.Event(), threading.Event()
        written: list = []

        def write(item):
            gate.wait(10)
            written.append(item)

        stage = be._WriteStage("ec.encode", write, lambda key, s: None,
                               errors, stop, depth=1)
        stage.start()
        assert stage.put("taken by the writer")
        assert stage.put("fills the queue")
        got: list = []
        blocked = threading.Thread(
            target=lambda: got.append(stage.put("one too many")))
        blocked.start()
        blocked.join(0.2)
        assert blocked.is_alive() and not got   # the queue is full
        stop.set()
        blocked.join(5)
        assert got == [False]
        assert stage.put("after the stop") is False
        gate.set()
        stage.close()
        assert written == ["taken by the writer"] and not errors
        assert not _pipeline_threads()

    def test_every_item_is_written_once_by_a_pool_that_gathers(self):
        """Four writers that each take what is queued behind their item
        (`take_if`, as the host pipeline's client does), more threads
        than items in the queue, a short switch interval: no item is
        lost and none is written twice."""
        import sys

        errors: list = []
        stop = threading.Event()
        written: list = []

        def write(item):
            group = [item]
            while len(group) < 8:
                nxt = stage.take_if(lambda n: n == group[-1] + 1)
                if nxt is None:
                    break
                group.append(nxt)
            written.extend(group)

        stage = be._WriteStage("ec.encode", write, lambda key, s: None,
                               errors, stop, depth=6, writers=4)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            stage.start()
            for i in range(3000):
                assert stage.put(i)
            stage.close()
        finally:
            sys.setswitchinterval(interval)
        assert not errors and not _pipeline_threads()
        assert sorted(written) == list(range(3000))

    def test_a_file_set_opens_the_shards_it_is_given(self, tmp_path,
                                                     monkeypatch):
        """A rebuild's set holds the missing shards only, at their final
        size; a set that fails to open one closes those it opened."""
        base = str(tmp_path / "v")
        files = be._ShardFileSet(base, to_ext, 100, shards=(3, 11))
        assert sorted(files.fds) == [3, 11]
        assert files.write(11, [b"abc", b"de"], 7) == 5
        files.close()
        assert sorted(os.listdir(tmp_path)) == ["v.ec03", "v.ec11"]
        with open(base + to_ext(11), "rb") as f:
            assert f.read() == bytes(7) + b"abcde" + bytes(88)
        opened, closed = [], []
        real_open, real_close = os.open, os.close

        def failing_truncate(fd, size):
            if len(opened) == 2:
                raise OSError(errno.ENOSPC, "injected into ftruncate")

        monkeypatch.setattr(os, "open", lambda *a: opened.append(
            real_open(*a)) or opened[-1])
        monkeypatch.setattr(os, "close", lambda fd: closed.append(fd)
                            or real_close(fd))
        monkeypatch.setattr(os, "ftruncate", failing_truncate)
        with pytest.raises(OSError, match="injected into ftruncate"):
            be._ShardFileSet(base, to_ext, 100, shards=(0, 5, 9))
        assert len(opened) == 2 and closed == opened


class TestDevicePoolPipeline:
    """The HBM slab-pool dispatch path (ops/device_pool.py + the pooled
    _encode_units_device): cross-volume identity on an explicit CPU-device
    mesh, donation safety under inflight slot reuse, and the zero
    per-batch-allocation steady state."""

    def _assert_identical(self, tmp_path, bases, crcs, tag):
        for k, base in enumerate(bases):
            ref = _host_reference(tmp_path, base, f"{tag}{k}")
            for i in range(14):
                with open(base + to_ext(i), "rb") as a, \
                        open(ref + to_ext(i), "rb") as b:
                    got = a.read()
                    assert got == b.read(), f"vol {k} shard {i}"
                assert crcs[base][i] == crc_host.crc32c(got), \
                    f"vol {k} crc {i}"

    def test_cross_volume_identity_on_mesh(self, tmp_path):
        """Mixed block sizes and padded tails batched through ONE pooled
        dispatch on an explicit CPU-device mesh must be byte- and
        CRC-identical to the reference host encode."""
        import jax

        from seaweedfs_tpu.parallel.mesh import make_mesh

        sizes = [LARGE * 10 + SMALL * 3 + 57,   # large rows + small tail
                 SMALL * 10,                     # exactly one full unit
                 999,                            # sub-unit, padded tail
                 1]                              # single byte
        bases = [_make_volume(tmp_path, f"mesh{k}", size, 100 + k)
                 for k, size in enumerate(sizes)]
        st: dict = {}
        crcs = encode_volumes(bases, large_block=LARGE, small_block=SMALL,
                              mesh=make_mesh(jax.devices()),
                              stage_stats=st)
        assert st["backend"].startswith("device-")
        self._assert_identical(tmp_path, bases, crcs, "meshref")

    @pytest.mark.parametrize("depth", ["1", "4"])
    def test_donation_slot_reuse_is_safe(self, tmp_path, monkeypatch,
                                         depth):
        """The donated output ring and recycled staging slots must not
        corrupt results at any inflight depth — a slot re-filled before
        its batch's completion sync would show up as shard corruption."""
        monkeypatch.setenv("WEED_EC_DEVICE_INFLIGHT", depth)
        bases = [_make_volume(tmp_path, f"d{depth}v{k}",
                              SMALL * 10 * 3 + 7 * k, 200 + k)
                 for k in range(6)]
        crcs = encode_volumes(bases, large_block=LARGE, small_block=SMALL,
                              batch_units=2)  # several batches in flight
        self._assert_identical(tmp_path, bases, crcs, f"d{depth}ref")

    def test_steady_state_makes_zero_allocations(self, tmp_path):
        """Repeat encodes with the same geometry re-lease pooled slabs:
        the pool's alloc counter must not move after the first run."""
        from seaweedfs_tpu.ops.device_pool import get_pool, reset_pool

        reset_pool()
        size = SMALL * 10 * 4 + 11
        for rep in range(3):
            bases = [_make_volume(tmp_path, f"s{rep}v{k}", size, k)
                     for k in range(3)]
            st: dict = {}
            encode_volumes(bases, large_block=LARGE, small_block=SMALL,
                           stage_stats=st)
            snap = get_pool().snapshot()
            if rep == 0:
                first_allocs = snap["allocs"]
            else:
                assert snap["allocs"] == first_allocs, \
                    f"rep {rep} allocated new slabs: {snap}"
                assert snap["lease_hits"] > 0
        assert st["backend"].startswith("device-")
        reset_pool()
