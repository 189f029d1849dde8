"""The plain reference under perfbench/: GF(2^8), CRC32C, extents."""

import os
import struct
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference.py")) as f:
        src = f.read()
    assert "import seaweedfs_tpu" not in src
    assert "from seaweedfs_tpu" not in src


def test_crc32c_known_vector():
    assert reference.crc32c(b"123456789") == 0xE3069283
    assert reference.crc32c(b"6789", reference.crc32c(b"12345")) == 0xE3069283


def test_parity_matrix_is_klauspost_rs_10_4():
    m = reference.parity_matrix()
    assert m.shape == (4, 10)
    # the systematic Vandermonde construction agrees with the program's
    from seaweedfs_tpu.ops import gf256
    assert np.array_equal(m, gf256.parity_matrix(10, 14))


def test_gf_apply_identity_and_linearity():
    rng = np.random.default_rng(7)
    x = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    y = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    m = reference.parity_matrix()
    assert np.array_equal(reference.gf_apply(np.eye(10, dtype=np.uint8), x), x)
    assert np.array_equal(reference.gf_apply(m, x ^ y),
                          reference.gf_apply(m, x) ^ reference.gf_apply(m, y))


@pytest.mark.parametrize("size", [1, 7, 8, 12, 1029, 32773, 4194309])
def test_needle_disk_size_matches_the_volume_writer(size):
    from seaweedfs_tpu.storage.needle import get_actual_size
    assert reference.needle_disk_size(size) == get_actual_size(size, 3)


@pytest.mark.parametrize("offset,length,want", [
    (8, 100, {0}),
    ((1 << 20) - 4, 8, {0, 1}),
    (3 << 20, 32768, {3}),
    (8, 4 << 20, {0, 1, 2, 3, 4}),
    (9 << 20, 2 << 20, {9, 0}),
    (9 << 20, (2 << 20) + 1, {9, 0, 1}),
    (0, 11 << 20, set(range(10))),
])
def test_shards_of_extent_small_block_rows(offset, length, want):
    assert reference.shards_of_extent(offset, length, 1_006_723_848) == want


def test_shards_of_extent_agrees_with_the_program_locator():
    from seaweedfs_tpu.storage.erasure_coding.locate import locate_data
    rng = np.random.default_rng(3)
    dat = 1_006_723_848
    for _ in range(200):
        off = int(rng.integers(0, dat - (5 << 20)))
        length = int(rng.integers(1, 5 << 20))
        theirs = {iv.to_shard_id_and_offset(1 << 30, 1 << 20)[0]
                  for iv in locate_data(1 << 30, 1 << 20, dat, off, length)}
        assert reference.shards_of_extent(off, length, dat) == theirs


def test_read_ecx_skips_tombstones(tmp_path):
    path = tmp_path / "x.ecx"
    rows = [(1, 8 // 8, 100), (2, 4096 // 8, 0xFFFFFFFF), (3, 8192 // 8, 5)]
    path.write_bytes(b"".join(struct.pack(">QII", *r) for r in rows))
    assert reference.read_ecx(str(path)) == {1: (8, 100), 3: (8192, 5)}


def test_fid_format():
    assert reference.fid(3, 0x1a2, 0xBEEF) == "3,1a20000beef"
