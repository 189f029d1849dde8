"""Native C++ layer: CRC32C and the AVX2 GF codec (CPU baseline backend)."""

import ctypes

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c, gf256, native
from seaweedfs_tpu.ops.codec import NativeEncoder, new_encoder
from seaweedfs_tpu.ops.rs_numpy import NumpyEncoder, gf_apply_matrix


class TestCrc32c:
    def test_known_vector(self):
        # Canonical CRC32C check value
        assert crc32c.crc32c(b"123456789") == 0xE3069283
        assert crc32c.crc32c(b"") == 0

    def test_python_fallback_matches_native(self):
        rng = np.random.default_rng(0)
        for n in [0, 1, 7, 8, 9, 63, 1000]:
            data = rng.integers(0, 256, size=n).astype(np.uint8).tobytes()
            assert crc32c._crc32c_py(0, data) == crc32c.crc32c(data)

    def test_incremental(self):
        data = b"hello, seaweed tpu world"
        c1 = crc32c.crc32c(data)
        c2 = crc32c.crc32c(data[10:], crc32c.crc32c(data[:10]))
        assert c1 == c2

    def test_legacy_value(self):
        # needle_read.go accepts either raw crc or the rotated Value() form
        c = crc32c.crc32c(b"abc")
        v = crc32c.value(c)
        assert v == (((c >> 15) | (c << 17) & 0xFFFFFFFF) + 0xA282EAD8) & 0xFFFFFFFF


@pytest.mark.skipif(native.lib() is None, reason="no native toolchain")
class TestNativeCodec:
    def test_apply_matrix_matches_numpy(self):
        rng = np.random.default_rng(1)
        enc = NativeEncoder(10, 4)
        matrix = gf256.parity_matrix(10, 4 + 10)
        data = rng.integers(0, 256, size=(10, 3001)).astype(np.uint8)
        shards = enc.encode(list(data) + [None] * 4)
        expect = gf_apply_matrix(matrix, data)
        for i in range(4):
            assert np.array_equal(shards[10 + i], expect[i])

    def test_reconstruct_matches_numpy(self):
        rng = np.random.default_rng(2)
        ref = NumpyEncoder(10, 4)
        enc = NativeEncoder(10, 4)
        data = [rng.integers(0, 256, size=500).astype(np.uint8)
                for _ in range(10)]
        shards = ref.encode(data + [None] * 4)
        damaged = list(shards)
        for i in (0, 7, 10, 13):
            damaged[i] = None
        restored = enc.reconstruct(damaged)
        for i in range(14):
            assert np.array_equal(restored[i], shards[i])


def test_factory_backends():
    for backend in ("numpy", "cpu", "tpu"):
        try:
            enc = new_encoder(10, 4, backend=backend)
        except RuntimeError:
            continue  # native lib unavailable
        rng = np.random.default_rng(3)
        data = [rng.integers(0, 256, size=256).astype(np.uint8)
                for _ in range(10)]
        shards = enc.encode(data + [None] * 4)
        ref = NumpyEncoder(10, 4).encode(data + [None] * 4)
        for i in range(14):
            assert np.array_equal(np.asarray(shards[i]), ref[i])


@pytest.mark.skipif(native.lib() is None, reason="no native toolchain")
class TestKernelLadder:
    """Every kernel level (scalar / AVX2-PSHUFB / GFNI) must agree with
    the NumPy reference bit for bit, including ragged tails that exercise
    the 256/64-byte block edges and the scalar remainder."""

    def test_all_levels_match_numpy(self):
        rng = np.random.default_rng(11)
        best = native.cpu_level()
        for p, d, L in [(4, 10, 4096), (4, 10, 257), (4, 10, 321),
                        (6, 10, 1000), (1, 5, 63), (10, 10, 130)]:
            matrix = rng.integers(0, 256, size=(p, d)).astype(np.uint8)
            data = rng.integers(0, 256, size=(d, L)).astype(np.uint8)
            expect = gf_apply_matrix(matrix, data)
            for level in range(best + 1):
                got = np.zeros((p, L), dtype=np.uint8)
                native.lib().sw_gf_apply_matrix_force(
                    matrix.ctypes.data_as(ctypes.c_char_p), p, d,
                    data.ctypes.data_as(ctypes.c_char_p), L,
                    got.ctypes.data_as(ctypes.c_char_p), level)
                assert np.array_equal(got, expect), (p, d, L, level)

    def test_encode_rows_fused_crcs(self):
        """sw_encode_rows chains per-shard CRC32Cs across rows exactly
        like the rolling CRC of the concatenated shard-file bytes."""
        from seaweedfs_tpu.ops.crc32c import crc32c

        rng = np.random.default_rng(12)
        enc = NativeEncoder(10, 4)
        pm = np.ascontiguousarray(enc.matrix[10:])
        R, L = 3, 2048
        data = rng.integers(0, 256, size=(R, 10, L)).astype(np.uint8)
        parity = np.empty((R, 4, L), dtype=np.uint8)
        crcs = enc.encode_rows(pm, data, parity)
        for j in range(10):
            want = crc32c(np.concatenate([data[r, j] for r in range(R)]))
            assert crcs[j] == want
        for i in range(4):
            expect_rows = [gf_apply_matrix(pm, data[r])[i]
                           for r in range(R)]
            assert np.array_equal(parity[:, i, :], np.stack(expect_rows))
            assert crcs[10 + i] == crc32c(np.concatenate(expect_rows))
