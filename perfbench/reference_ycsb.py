"""The plain reference of the YCSB cells: the key space, the request
distribution, the records, and a store that knows which answers a
register may give.

Nothing here comes from `seaweedfs_tpu/` or from the driver's request
code: the generators are YCSB's, written out as recalled (no network in
the sandbox; the configuration's `assumed` says so), one draw at a time;
the store is a dict of versions a key.

  * `fnvhash64`, `key_of`: `Utils.fnvhash64` on Java's signed 64 bits, and
    `CoreWorkload.buildKeyName` under `insertorder=hashed`:
    "user" + fnvhash64(record number).
  * `Zipfian`, `ScrambledZipfian`: `ZipfianGenerator` (Gray et al., "Quickly
    generating billion-record synthetic databases") and the scrambled
    one, which draws a rank over a fixed space of ten thousand million
    items under a zeta computed once (26.46902820178302, constant 0.99)
    and hashes it onto the records: the hottest record draws 1 / zetan,
    3.78% of all requests, whatever the record count.
  * `Records`: the loaded state and every later field value, from the
    seed.  A record is a JSON object of `fieldcount` fields of
    `fieldlength` bytes, written without spaces.
  * `Register`: per key the versions written, each with the time its
    write began and the time it was acknowledged.  A read is right if
    its body is, byte for byte, a version whose write began before the
    read ended, and no other write of that key both began after that
    version was acknowledged and was itself acknowledged before the read
    began (read-your-acknowledged-write; a version in flight may be read).
"""

from __future__ import annotations

import json
import math

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_MASK = (1 << 64) - 1

ZIPFIAN_CONSTANT = 0.99
# ScrambledZipfianGenerator: the item space and its zeta, fixed
SCRAMBLED_ITEM_COUNT = 10_000_000_000
SCRAMBLED_ZETAN = 26.46902820178302

ALPHABET = (b"abcdefghijklmnopqrstuvwxyz"
            b"ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_")
POOL_BYTES = 1 << 22
_STRIDE = 104_729   # a prime: neighbouring fields lie far apart in the pool


def fnvhash64(val: int) -> int:
    """FNV-1 over the eight octets of `val`, low one first, in Java's
    signed longs, then `Math.abs`."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * FNV_PRIME_64) & _MASK
    if h >= 1 << 63:            # the long is negative: Math.abs
        h = (1 << 64) - h
    return h


def key_of(record: int) -> str:
    return f"user{fnvhash64(record)}"


class Zipfian:
    """Ranks 0..items-1, rank 0 the most popular."""

    def __init__(self, items: int, constant: float = ZIPFIAN_CONSTANT,
                 zetan: float | None = None):
        self.items = items
        self.theta = constant
        self.zetan = zetan if zetan is not None else sum(
            1.0 / (i + 1) ** constant for i in range(items))
        zeta2 = 1.0 + 0.5 ** constant
        self.alpha = 1.0 / (1.0 - constant)
        self.eta = ((1.0 - (2.0 / items) ** (1.0 - constant))
                    / (1.0 - zeta2 / self.zetan))

    def rank(self, u: float) -> int:
        """The rank that the uniform draw `u` of [0, 1) stands for."""
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < 1.0 + 0.5 ** self.theta:
            return 1
        return int(self.items * (self.eta * u - self.eta + 1.0) ** self.alpha)


class ScrambledZipfian:
    """A zipfian rank over the fixed item space, hashed onto `records`
    record numbers: popular records lie scattered over the key space."""

    def __init__(self, records: int):
        self.records = records
        self.zipfian = Zipfian(SCRAMBLED_ITEM_COUNT + 1, ZIPFIAN_CONSTANT,
                               SCRAMBLED_ZETAN)

    def record(self, u: float) -> int:
        return fnvhash64(self.zipfian.rank(u)) % self.records

    def hottest(self) -> tuple[int, float]:
        """The record rank 0 falls on, and the share of draws that is
        rank 0's alone."""
        return fnvhash64(0) % self.records, 1.0 / self.zipfian.zetan


class Records:
    """What a seed loads, and the value an update writes."""

    def __init__(self, seed: int, fieldcount: int = 10,
                 fieldlength: int = 100):
        self.fieldcount = fieldcount
        self.fieldlength = fieldlength
        letters = np.frombuffer(ALPHABET, dtype=np.uint8)
        draws = np.random.default_rng(seed).integers(
            0, len(letters), POOL_BYTES)
        self.pool = letters[draws].tobytes().decode()
        self.room = POOL_BYTES - fieldlength

    def value(self, serial: int) -> str:
        off = (serial * _STRIDE) % self.room
        return self.pool[off:off + self.fieldlength]

    def loaded(self, record: int) -> dict:
        return {f"field{j}": self.value(record * self.fieldcount + j)
                for j in range(self.fieldcount)}

    def updated(self, caller: int, sequence: int) -> str:
        """The value the `sequence`-th update of `caller` writes: tagged
        with both, so that no two writes of a run put the same bytes."""
        tag = f"c{caller:02d}-{sequence:07d}-"
        return tag + self.value(-1 - caller - 64 * sequence)[len(tag):]

    @staticmethod
    def encode(fields: dict) -> bytes:
        return json.dumps(fields, separators=(",", ":")).encode()

    def loaded_body(self, record: int) -> bytes:
        return self.encode(self.loaded(record))


class Version:
    __slots__ = ("body", "began", "acked")

    def __init__(self, body: bytes, began: float, acked: float):
        self.body = body
        self.began = began
        self.acked = acked


OK, UNKNOWN, STALE = "ok", "unknown_or_torn", "stale"


class Register:
    """The versions of every key, as the callers report their writes."""

    def __init__(self, records: Records):
        self.records = records
        self.versions: dict[int, list[Version]] = {}

    def _of(self, record: int) -> list[Version]:
        got = self.versions.get(record)
        if got is None:
            got = self.versions[record] = [Version(
                self.records.loaded_body(record), -math.inf, -math.inf)]
        return got

    def write_began(self, record: int, body: bytes, at: float) -> Version:
        v = Version(body, at, math.inf)
        self._of(record).append(v)
        return v

    @staticmethod
    def write_acked(version: Version, at: float):
        version.acked = at

    def check_read(self, record: int, began: float, ended: float,
                   body: bytes) -> str:
        versions = self._of(record)
        verdict = UNKNOWN
        for v in versions:
            if v.body != body or v.began > ended:
                continue
            if any(w.acked < began and w.began > v.acked for w in versions):
                verdict = STALE
                continue
            return OK
        return verdict

    def written(self) -> list[int]:
        """The records some write of the run began on."""
        return [r for r, vs in self.versions.items() if len(vs) > 1]

    def acknowledged_writes(self) -> int:
        return sum(1 for vs in self.versions.values() for v in vs[1:]
                   if v.acked < math.inf)

    def superseded_bytes(self, standing: dict[int, bytes]) -> tuple[int, int]:
        """(chunks, payload bytes) that stand no longer once every write
        is acknowledged: of each written key every version but the one
        that `standing` says is read now."""
        chunks = nbytes = 0
        for record in self.written():
            sizes = [len(v.body) for v in self._of(record)]
            chunks += len(sizes) - 1
            nbytes += sum(sizes) - len(standing.get(record, b""))
        return chunks, nbytes
