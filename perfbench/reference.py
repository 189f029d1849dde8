"""The plain reference: what decides `correct`, with nothing of the program.

RS(10,4) over GF(2^8) as klauspost/reedsolomon and upstream SeaweedFS
define it (polynomial 0x11D, generator 2, systematic Vandermonde matrix),
CRC32C, the on-disk extent of a needle, the `.ecx` index and the striping
of a `.dat` over shard files.  Copied in spirit from `ops/gf256.py` and
`ops/rs_numpy.py` at PR 21 so that no later PR can move the yardstick; it
imports nothing from `seaweedfs_tpu`.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import random

import numpy as np

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS
LARGE_BLOCK = 1 << 30        # upstream ec_encoder.go: 1 GB rows first ...
SMALL_BLOCK = 1 << 20        # ... then 1 MB rows for the remainder
MIB = 1 << 20

NEEDLE_HEADER = 16           # cookie 4 + id 8 + size 4
NEEDLE_CHECKSUM = 4
NEEDLE_TIMESTAMP = 8         # version 3 appends append_at_ns
NEEDLE_PADDING = 8
ECX_ENTRY = 16               # id 8 + offset/8 4 + size 4, big endian


def shard_ext(shard_id: int) -> str:
    return f".ec{shard_id:02d}"


# -- GF(2^8) -----------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _mul_table() -> np.ndarray:
    exp = np.zeros(510, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:] = exp[:255]
    table = exp[(log[:, None] + log[None, :]) % 255].astype(np.uint8)
    table[0, :] = 0
    table[:, 0] = 0
    return table


def _gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.bitwise_xor.reduce(_mul_table()[a[:, :, None], b[None, :, :]],
                                 axis=1)


def _gf_invert(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    mt = _mul_table()
    work = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)],
                          axis=1)
    for r in range(n):
        if work[r, r] == 0:
            swap = next(b for b in range(r + 1, n) if work[b, r])
            work[[r, swap]] = work[[swap, r]]
        pivot = int(work[r, r])
        inv = next(v for v in range(1, 256) if mt[pivot, v] == 1)
        work[r] = mt[inv, work[r]]
        for other in range(n):
            if other != r and work[other, r]:
                work[other] ^= mt[int(work[other, r]), work[r]]
    return work[:, n:].copy()


@functools.lru_cache(maxsize=4)
def parity_matrix(data_shards: int = DATA_SHARDS,
                  total_shards: int = TOTAL_SHARDS) -> np.ndarray:
    """Parity rows of klauspost's buildMatrix: vm[r, c] = r**c, times the
    inverse of its top square, rows data..total-1."""
    mt = _mul_table()
    vm = np.zeros((total_shards, data_shards), dtype=np.uint8)
    for r in range(total_shards):
        acc = 1
        for c in range(data_shards):
            vm[r, c] = acc
            acc = int(mt[acc, r])
    return _gf_matmul(vm, _gf_invert(vm[:data_shards]))[data_shards:]


def gf_apply(matrix: np.ndarray, inputs: np.ndarray) -> np.ndarray:
    """(r, d) coefficients times (d, L) bytes over GF(2^8) -> (r, L)."""
    mt = _mul_table()
    out = np.zeros((matrix.shape[0], inputs.shape[1]), dtype=np.uint8)
    for i in range(matrix.shape[0]):
        for j in range(matrix.shape[1]):
            out[i] ^= mt[int(matrix[i, j])][inputs[j]]
    return out


# -- CRC32C --------------------------------------------------------------------

def crc32c(data, crc: int = 0) -> int:
    """Castagnoli CRC of `data`, continuing from `crc`.  google-crc32c is
    part of the installation and shares no code with the program."""
    import google_crc32c

    return google_crc32c.extend(crc, bytes(data) if not isinstance(
        data, (bytes, bytearray)) else data)


def file_crc32c(path: str, chunk: int = 32 * MIB) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                return crc
            crc = crc32c(buf, crc)


def digest(data) -> bytes:
    return hashlib.blake2b(data, digest_size=16).digest()


# -- needles, the index and the striping ---------------------------------------

def needle_disk_size(size: int) -> int:
    """Bytes a version-3 needle with `size` body bytes occupies in the
    .dat (header, body, checksum, timestamp, padded to 8)."""
    raw = NEEDLE_HEADER + size + NEEDLE_CHECKSUM + NEEDLE_TIMESTAMP
    return raw + (NEEDLE_PADDING - raw % NEEDLE_PADDING)


def read_ecx(path: str) -> dict[int, tuple[int, int]]:
    """needle id -> (.dat offset, stored size) of the live entries."""
    raw = np.fromfile(path, dtype=">u4").reshape(-1, ECX_ENTRY // 4)
    out = {}
    for hi, lo, off8, size in raw.tolist():
        if 0 < size < 0x80000000:  # tombstones carry a negative size
            out[(hi << 32) | lo] = (off8 * NEEDLE_PADDING, size)
    return out


def shards_of_extent(offset: int, length: int, dat_size: int) -> set[int]:
    """Data shards that hold bytes [offset, offset+length) of a .dat of
    `dat_size` bytes, under upstream's row striping."""
    large_rows = (dat_size + DATA_SHARDS * SMALL_BLOCK) // (
        LARGE_BLOCK * DATA_SHARDS)
    large_end = large_rows * LARGE_BLOCK * DATA_SHARDS
    touched = set()
    pos, end = offset, offset + length
    while pos < end and len(touched) < DATA_SHARDS:
        if pos < large_end:
            block, base = LARGE_BLOCK, 0
        else:
            block, base = SMALL_BLOCK, large_end
        index = (pos - base) // block
        touched.add(index % DATA_SHARDS)
        pos = base + (index + 1) * block
    return touched


def fid(volume: int, needle_id: int, cookie: int) -> str:
    return f"{volume},{needle_id:x}{cookie:08x}"


# -- checks on a sealed volume --------------------------------------------------

def check_shard_crcs(base: str) -> int:
    """Shard files whose CRC32C differs from the `.vif` record (14 when
    the record is missing)."""
    try:
        with open(base + ".vif") as f:
            recorded = json.load(f).get("shard_crc32c")
    except (OSError, ValueError):
        recorded = None
    if not isinstance(recorded, list) or len(recorded) != TOTAL_SHARDS:
        return TOTAL_SHARDS
    return sum(1 for sid in range(TOTAL_SHARDS)
               if file_crc32c(base + shard_ext(sid)) != recorded[sid])


def check_stripe_sample(base: str, dat_path: str, seed: int,
                        sample_bytes: int) -> dict:
    """A seeded sample of stripe columns from the 14 shard files: parity
    rows against this file's GF(2^8) apply of the data rows, and data
    rows against the bytes of the pristine `.dat` they were cut from.
    Returns the counts of differing bytes and the data bytes compared."""
    shard_size = os.path.getsize(base + shard_ext(0))
    dat_size = os.path.getsize(dat_path)
    piece = 64 << 10
    n_pieces = max(1, min(shard_size // piece,
                          -(-sample_bytes // (DATA_SHARDS * piece))))
    offsets = sorted(random.Random(seed).sample(
        range(shard_size // piece), n_pieces))
    cols = np.zeros((TOTAL_SHARDS, n_pieces * piece), dtype=np.uint8)
    for sid in range(TOTAL_SHARDS):
        with open(base + shard_ext(sid), "rb") as f:
            for k, off in enumerate(offsets):
                f.seek(off * piece)
                f.readinto(memoryview(cols[sid, k * piece:(k + 1) * piece]))
    expect = gf_apply(parity_matrix(), cols[:DATA_SHARDS])
    parity_diff = int(np.count_nonzero(expect != cols[DATA_SHARDS:]))
    # the same columns straight from the .dat (zero past its end)
    large_rows = (dat_size + DATA_SHARDS * SMALL_BLOCK) // (
        LARGE_BLOCK * DATA_SHARDS)
    data_diff = 0
    with open(dat_path, "rb") as f:
        for k, off in enumerate(offsets):
            shard_off = off * piece
            for sid in range(DATA_SHARDS):
                if shard_off < large_rows * LARGE_BLOCK:
                    row, inner = divmod(shard_off, LARGE_BLOCK)
                    pos = (row * DATA_SHARDS + sid) * LARGE_BLOCK + inner
                else:
                    row, inner = divmod(
                        shard_off - large_rows * LARGE_BLOCK, SMALL_BLOCK)
                    pos = (large_rows * LARGE_BLOCK * DATA_SHARDS
                           + (row * DATA_SHARDS + sid) * SMALL_BLOCK + inner)
                want = np.zeros(piece, dtype=np.uint8)
                if pos < dat_size:
                    f.seek(pos)
                    f.readinto(memoryview(want[:min(piece, dat_size - pos)]))
                data_diff += int(np.count_nonzero(
                    want != cols[sid, k * piece:(k + 1) * piece]))
    return {"parity_bytes_differ": parity_diff,
            "data_bytes_differ": data_diff,
            "data_bytes_compared": DATA_SHARDS * n_pieces * piece}
