"""EC volume runtime: shard handles, sorted-index search, EC reads, deletes.

Parity with ec_volume.go / ec_shard.go / ec_volume_delete.go / store_ec.go:
  * .ecx binary search over 16-byte sorted entries (SearchNeedleFromSortedIndex,
    ec_volume.go:230-255); a mounted volume searches a read-only shared
    mapping of the file, so a lookup makes no system call
  * read ladder per interval: local shard pread, else remote fetch (hook),
    else reconstruct the interval from >=10 other shards
    (readOneEcShardInterval/recoverOneRemoteEcShardInterval,
    store_ec.go:188-218,328-382)
  * delete = tombstone the size field inside .ecx in place + append the id to
    the .ecj journal (ec_volume_delete.go:13-50); RebuildEcxFile replays the
    journal (ec_volume_delete.go:53-98)
"""

from __future__ import annotations

import itertools
import mmap
import os
import struct
import threading
import time
from typing import Callable, Optional

import numpy as np

from ...ops import codec as codec_mod
from .. import idx as idx_mod
from .. import types as t
from ..needle import Needle, get_actual_size
from . import (DATA_SHARDS_COUNT, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE,
               TOTAL_SHARDS_COUNT, to_ext)
from ... import tracing
from .locate import Interval, locate_data
from .recover import (BULK_JOBS, STATS as RECOVER_STATS,
                      RecoveredBlockCache, SpanDecodeBatcher, recover_knobs)

_recover_pool_lock = threading.Lock()
_recover_pool_inst = None


def _recover_pool():
    """Shared fan-out pool for degraded-read survivor fetches: built
    once, sized for a few concurrent recoveries, never rebuilt on the
    hot path of an outage."""
    global _recover_pool_inst
    with _recover_pool_lock:
        if _recover_pool_inst is None:
            import concurrent.futures as cf

            _recover_pool_inst = cf.ThreadPoolExecutor(
                max_workers=32, thread_name_prefix="ec-recover")
        return _recover_pool_inst


# Stage keys of ReadStats.add_stage, in reply order.
_READ_STAGES = ("locate", "shard", "assemble")


class ReadStats:
    """Cumulative sealed-read telemetry, process-wide like RecoverStats:
    needles served by `EcVolume.read_needle`, their intervals and bytes
    split by how each was served (plain = a local shard, the tail stripe
    or a remote holder; recovered = through `_recover_span`), and busy
    seconds of the three `ec.read.*` stages (locate = .ecx search +
    interval maths, shard = the plain local `read_at` of one interval,
    assemble = join + needle parse with its CRC), and `index_preads`,
    the `.ecx` preads their lookups made (`search_sorted_index` counts
    its probes; the mounted volume's mapping makes none).
    `needles_beside_job` are the needles served while an EC bulk job
    (`generate`, `rebuild`) was in flight in this process; a
    `local_fallbacks` is an interval whose mounted local shard failed or
    read short after the lookup (unmounted or deleted beside the read, an
    I/O error, a truncated file) and which went on down the ladder to the
    remote hook and to reconstruction (the span `ec.read.local_fallback`
    is the rest of such an interval's ladder; it has no seconds here:
    `ec.recover.*` count them).  The counts are
    of every needle; the seconds are of the `timed_needles` among them
    (`tracing.sampled_stage`: a sampled request, or a profiler session),
    so a stage's cost a needle is its seconds over `timed_needles`.
    Updated on every GET of a sealed volume, so an update is one lock
    and a few additions; the Prometheus vectors are brought up to it at
    scrape time (`export`), as the native engine's counters are."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self._seconds = dict.fromkeys(_READ_STAGES, 0.0)
            self.needles = 0
            self.timed_needles = 0
            self.intervals_plain = 0
            self.intervals_recovered = 0
            self.bytes_plain = 0
            self.bytes_recovered = 0
            self.index_preads = 0
            self.needles_beside_job = 0
            self.local_fallbacks = 0

    def add_stage(self, stage: str, seconds: float):
        """The stage accumulator handed to tracing.stage()."""
        with self._lock:
            self._seconds[stage] += seconds

    def needle(self, intervals: int, nbytes: int, recovered: int,
               recovered_bytes: int, timed: bool, index_preads: int = 0):
        """One needle served: `recovered` of its `intervals` (and
        `recovered_bytes` of its `nbytes`) came through a recovery;
        `timed`: its stages added their seconds; `index_preads`: the
        preads of the `.ecx` its lookup made."""
        with self._lock:
            self.needles += 1
            self.index_preads += index_preads
            self.timed_needles += timed
            self.intervals_plain += intervals - recovered
            self.intervals_recovered += recovered
            self.bytes_plain += nbytes - recovered_bytes
            self.bytes_recovered += recovered_bytes
            if BULK_JOBS.in_flight:
                self.needles_beside_job += 1

    def local_fallback(self):
        with self._lock:
            self.local_fallbacks += 1

    def snapshot(self) -> dict:
        with self._lock:
            out = {f"{k}_seconds": round(self._seconds[k], 6)
                   for k in _READ_STAGES}
            out.update({
                "needles": self.needles,
                "timed_needles": self.timed_needles,
                "intervals": self.intervals_plain + self.intervals_recovered,
                "intervals_plain": self.intervals_plain,
                "intervals_recovered": self.intervals_recovered,
                "bytes_plain": self.bytes_plain,
                "bytes_recovered": self.bytes_recovered,
                "index_preads": self.index_preads,
                "needles_beside_job": self.needles_beside_job,
                "local_fallbacks": self.local_fallbacks,
            })
        return out

    def export(self):
        """Bring the Prometheus ec_read_* vectors up to the counters."""
        from ...stats import metrics as stats

        snap = self.snapshot()
        stats.EcReadNeedleCounter.labels("all").set_cumulative(
            snap["needles"])
        stats.EcReadNeedleCounter.labels("timed").set_cumulative(
            snap["timed_needles"])
        stats.EcReadNeedleCounter.labels("beside_job").set_cumulative(
            snap["needles_beside_job"])
        stats.EcReadLocalFallbackCounter.set_cumulative(
            snap["local_fallbacks"])
        for served in ("plain", "recovered"):
            stats.EcReadIntervalCounter.labels(served).set_cumulative(
                snap["intervals_" + served])
            stats.EcReadBytesCounter.labels(served).set_cumulative(
                snap["bytes_" + served])
        stats.EcReadIndexPreadCounter.set_cumulative(snap["index_preads"])
        for stage in _READ_STAGES:
            stats.EcReadStageSeconds.labels(stage).set(
                snap[stage + "_seconds"])


READ_STATS = ReadStats()


def _no_counter(key: str, seconds: float):
    """The accumulator of a stage that is a span and no counter."""


class EcError(Exception):
    pass


class EcNotFoundError(EcError):
    pass


class EcDeletedError(EcError):
    pass


class ShardBits:
    """uint32 bitmask of shard ids (ec_volume_info.go:65-117)."""

    def __init__(self, bits: int = 0):
        self.bits = bits & 0xFFFFFFFF

    def add(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits | (1 << shard_id))

    def remove(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits & ~(1 << shard_id))

    def has(self, shard_id: int) -> bool:
        return bool(self.bits & (1 << shard_id))

    def shard_ids(self) -> list[int]:
        return [i for i in range(TOTAL_SHARDS_COUNT) if self.has(i)]

    def count(self) -> int:
        return bin(self.bits).count("1")

    def minus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self.bits & ~other.bits)

    def plus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self.bits | other.bits)

    def __eq__(self, other):
        return isinstance(other, ShardBits) and self.bits == other.bits

    def __hash__(self):
        # __eq__ without __hash__ made instances unhashable (None __hash__),
        # silently breaking set/dict membership; hash the identity __eq__ uses
        return hash(self.bits)

    def __repr__(self):
        return f"ShardBits({self.shard_ids()})"


class _ShardFile:
    """An open shard file's descriptor, closed when its last holder lets
    go of it: the shard that opened it, or a read that took it before the
    shard was closed."""

    __slots__ = ("fd",)

    def __init__(self, fd: int):
        self.fd = fd

    def __del__(self):
        os.close(self.fd)


class EcVolumeShard:
    """One open .ecNN file (ec_shard.go:17-97)."""

    def __init__(self, directory: str, collection: str, vid: int,
                 shard_id: int):
        self.dir = directory
        self.collection = collection
        self.volume_id = vid
        self.shard_id = shard_id
        self._f: Optional[_ShardFile] = _ShardFile(
            os.open(self.file_name(), os.O_RDONLY))
        self.ecd_file_size = os.path.getsize(self.file_name())

    def base_file_name(self) -> str:
        base = (f"{self.collection}_{self.volume_id}" if self.collection
                else str(self.volume_id))
        return os.path.join(self.dir, base)

    def file_name(self) -> str:
        return self.base_file_name() + to_ext(self.shard_id)

    def read_at(self, size: int, offset: int) -> bytes:
        """Reads hold no lock against `close()` (an unmount, a
        `delete_shards` beside them), they hold the file: a read that
        took it before the close finishes on a descriptor that is still
        this file's, and one that comes after raises, as upstream's
        `ReadAt` of a closed file does."""
        f = self._f
        if f is None:
            raise EcError(f"shard {self.volume_id}.{self.shard_id} is closed")
        return os.pread(f.fd, size, offset)

    def close(self):
        self._f = None

    def destroy(self):
        self.close()
        os.remove(self.file_name())


# Remote fetch hook: (shard_id, offset, size) -> bytes | None
ShardReader = Callable[[int, int, int], Optional[bytes]]


# per thread: the `.ecx` preads since its read_needle began
# (search_sorted_index adds its probes; read_needle hands the sum to
# ReadStats); the intervals (and their bytes) the current needle
# recovered; and the fetch+decode busy seconds inside the current
# recover span, so the serve stage reports assembly/wait overhead, not a
# double count
_tls = threading.local()

# What a mounted EcVolume's survivor spans are named by on the device
# (`_recover_block`): one number a mounted volume and a new one whenever
# its set of shards changes, so a volume closed and mounted again,
# restored, replaced by another of the same id, or given a rebuilt shard
# never meets a slab of other bytes.  A counter, never `id(self)`:
# addresses are reused.
_SLAB_TOKENS = itertools.count(1)

_KEY = struct.Struct(">Q").unpack_from


def search_sorted_index(fileno: int, n_entries: int,
                        needle_id: int) -> Optional[int]:
    """Binary search 16-byte sorted entries by pread; returns entry index
    (SearchNeedleFromSortedIndex, ec_volume.go:230-255).  For a file
    nobody has mounted (`rebuild_ecx_file`): a mounted volume searches
    its mapping (`EcVolume._search_ecx`)."""
    lo, hi = 0, n_entries
    probes = 0
    try:
        while lo < hi:
            mid = (lo + hi) // 2
            probes += 1
            key, = _KEY(os.pread(fileno, t.NEEDLE_MAP_ENTRY_SIZE,
                                 mid * t.NEEDLE_MAP_ENTRY_SIZE))
            if key == needle_id:
                return mid
            if key < needle_id:
                lo = mid + 1
            else:
                hi = mid
        return None
    finally:
        _tls.index_preads = getattr(_tls, "index_preads", 0) + probes


class EcVolume:
    """A mounted EC volume: local shard subset + .ecx/.ecj handles."""

    # inline EC volumes install a hook serving shard-log spans from the
    # in-memory tail stripe: (shard_id, offset, size) -> bytes | None.
    # Sealed volumes leave it None and the classic ladder applies.
    tail_reader: Optional[ShardReader] = None

    def __init__(self, directory: str, collection: str, vid: int,
                 version: int = 3, encoder=None,
                 large_block_size: int = LARGE_BLOCK_SIZE,
                 small_block_size: int = SMALL_BLOCK_SIZE):
        self.dir = directory
        self.collection = collection
        self.volume_id = vid
        self.version = version
        self.large_block_size = large_block_size
        self.small_block_size = small_block_size
        self.shards: dict[int, EcVolumeShard] = {}
        self.shard_locations: dict[int, list[str]] = {}  # shard id -> addrs
        self.remote_reader: Optional[ShardReader] = None
        # code family rides in .vif metadata: volumes encoded before the
        # coding tier existed have no record and resolve to the RS default,
        # so mixed clusters keep reading old volumes correctly
        from .codes import get_family
        from .encoder import load_volume_info
        info = load_volume_info(self.base_file_name()) or {}
        self.family = get_family(info.get("code_family"))
        # lazy: backend selection probes device availability, which must
        # not stall mount/admin paths — only reconstruction needs it
        self._encoder = encoder
        # degraded-read machinery: per-volume recovered-block LRU (keys
        # are shard offsets, which only mean anything within one volume)
        # + the same-survivor-set span-decode batcher
        self._recover_cache = RecoveredBlockCache()
        self._recover_batcher = SpanDecodeBatcher(self._decode_span)
        self._slab_token = next(_SLAB_TOKENS)
        self._ecx_lock = threading.Lock()
        self._ecj_lock = threading.Lock()
        base = self.base_file_name()
        self._ecx = open(base + ".ecx", "r+b")
        self.ecx_file_size = os.path.getsize(base + ".ecx")
        # the index as reads see it: a read-only shared mapping of the
        # file, so a lookup is loads and no system call (a pread a probe
        # gave the GIL up 12-13 times a GET), the page cache is the only
        # memory it takes, and the size field `_mark_ecx_deleted` pwrites
        # shows in it at once: the mapping is the file.  An empty file
        # cannot be mapped: no entries, nothing is found.
        self._ecx_map = (mmap.mmap(self._ecx.fileno(), 0, mmap.MAP_SHARED,
                                   mmap.PROT_READ)
                         if self.ecx_file_size >= t.NEEDLE_MAP_ENTRY_SIZE
                         else None)
        self._ecj = open(base + ".ecj", "a+b")
        self.ecj_file_size = os.path.getsize(base + ".ecj")

    def base_file_name(self) -> str:
        base = (f"{self.collection}_{self.volume_id}" if self.collection
                else str(self.volume_id))
        return os.path.join(self.dir, base)

    # -- shard management ----------------------------------------------------
    def add_shard(self, shard: EcVolumeShard) -> bool:
        if shard.shard_id in self.shards:
            return False
        self.shards[shard.shard_id] = shard
        self._slab_token = next(_SLAB_TOKENS)
        return True

    def delete_shard(self, shard_id: int) -> Optional[EcVolumeShard]:
        self._slab_token = next(_SLAB_TOKENS)
        return self.shards.pop(shard_id, None)

    def shard_bits(self) -> ShardBits:
        bits = ShardBits()
        for sid in self.shards:
            bits = bits.add(sid)
        return bits

    @property
    def shard_size(self) -> int:
        while True:
            try:
                return next(iter(self.shards.values())).ecd_file_size
            except StopIteration:
                return 0
            except RuntimeError:
                continue    # a mount or unmount changed the dict: again

    # -- sorted-index search -------------------------------------------------
    def find_needle_from_ecx(self, needle_id: int) -> tuple[int, int]:
        """Binary search the sorted .ecx -> (offset, size); raises
        EcNotFoundError when absent."""
        entry_pos = self._search_ecx(needle_id)
        if entry_pos is None:
            raise EcNotFoundError(f"needle {needle_id:x} not found")
        _, offset, size = self._read_ecx_entry(entry_pos)
        return offset, size

    def _read_ecx_entry(self, pos: int) -> tuple[int, int, int]:
        """Entry `pos` -> (needle_id, offset, size), from one 16-byte
        copy out of the mapping: the size field a delete overwrites is
        read whole."""
        at = pos * t.NEEDLE_MAP_ENTRY_SIZE
        return idx_mod.unpack_entry(
            self._ecx_map[at:at + t.NEEDLE_MAP_ENTRY_SIZE])

    def _search_ecx(self, needle_id: int) -> Optional[int]:
        """Binary search of the mapped index -> the entry's position."""
        index, key_at, width = self._ecx_map, _KEY, t.NEEDLE_MAP_ENTRY_SIZE
        lo, hi = 0, self.ecx_file_size // width
        while lo < hi:
            mid = (lo + hi) >> 1
            key, = key_at(index, mid * width)
            if key < needle_id:
                lo = mid + 1
            elif key > needle_id:
                hi = mid
            else:
                return mid
        return None

    # -- needle read (store_ec.go ReadEcShardNeedle:125-163) ------------------
    def locate_needle(self, needle_id: int
                      ) -> tuple[int, int, list[Interval]]:
        offset, size = self.find_needle_from_ecx(needle_id)
        if t.size_is_deleted(size):
            raise EcDeletedError(f"needle {needle_id:x} deleted")
        intervals = locate_data(
            self.large_block_size, self.small_block_size,
            self.family.data_shards * self.shard_size,
            offset, get_actual_size(size, self.version),
            data_shards=self.family.data_shards)
        return offset, size, intervals

    def read_needle(self, needle_id: int,
                    cookie: Optional[int] = None) -> Needle:
        tls = _tls
        tls.recovered = tls.recovered_bytes = tls.index_preads = 0
        with tracing.sampled_stage("ec.read.locate", READ_STATS.add_stage,
                                   "locate") as timed:
            offset, size, intervals = self.locate_needle(needle_id)
        parts = [self._read_interval(iv) for iv in intervals]
        nbytes = sum(iv.size for iv in intervals)
        with tracing.sampled_stage("ec.read.assemble", READ_STATS.add_stage,
                                   "assemble", len(parts), nbytes):
            blob = b"".join(parts)
            n = Needle()
            n.read_bytes(blob, offset, size, self.version)
        READ_STATS.needle(len(parts), nbytes, tls.recovered,
                          tls.recovered_bytes, timed is not None,
                          tls.index_preads)
        if cookie is not None and n.cookie != cookie:
            raise EcError(f"cookie mismatch for needle {needle_id:x}")
        return n

    def _read_interval(self, iv: Interval) -> bytes:
        shard_id, inner_offset = iv.to_shard_id_and_offset(
            self.large_block_size, self.small_block_size,
            data_shards=self.family.data_shards)
        return self.read_shard_span(shard_id, inner_offset, iv.size)

    def read_shard_span(self, shard_id: int, offset: int, size: int) -> bytes:
        """Read ladder: local shard -> in-memory tail stripe (inline
        volumes) -> remote hook -> reconstruct.  A mounted local shard
        that cannot give the span goes on down the ladder, counted
        (readOneEcShardInterval, store_ec.go:188-218: any local error
        goes on to the remote read and to recovery): closed beside this
        read (the lookup holds no lock against an unmount), an I/O
        error, or a sealed shard file that ends early."""
        shard = self.shards.get(shard_id)
        if shard is None:
            return self._read_elsewhere(shard_id, offset, size)
        try:
            with tracing.sampled_stage("ec.read.shard",
                                       READ_STATS.add_stage, "shard",
                                       shard_id, size):
                data = shard.read_at(size, offset)
        except (OSError, EcError):
            pass
        else:
            if len(data) == size:
                return data
            if self.tail_reader is not None:
                return self._read_past_the_log(shard, data, offset, size)
        READ_STATS.local_fallback()
        with tracing.sampled_stage("ec.read.local_fallback", _no_counter,
                                   "local_fallback", shard_id, size):
            return self._read_elsewhere(shard_id, offset, size)

    def _read_past_the_log(self, shard: EcVolumeShard, data: bytes,
                           offset: int, size: int) -> bytes:
        """Inline volumes: the span runs past the shard log's durable
        extent, and the remainder lives in the partially-filled tail
        stripe (data still buffered, or parity not yet committed for
        the current row)."""
        shard_id = shard.shard_id
        rest = self.tail_reader(shard_id, offset + len(data),
                                size - len(data))
        if rest is not None:
            return data + rest
        # the flusher committed the row between the pread and the tail
        # lookup — the bytes are on disk now
        data = shard.read_at(size, offset)
        if len(data) == size:
            return data
        raise EcError(f"short read shard {shard_id} at {offset}+{size}")

    def _read_elsewhere(self, shard_id: int, offset: int,
                        size: int) -> bytes:
        """The ladder below the local shard."""
        if self.tail_reader is not None:
            data = self.tail_reader(shard_id, offset, size)
            if data is not None:
                return data
        if self.remote_reader is not None:
            try:
                data = self.remote_reader(shard_id, offset, size)
            except Exception:
                data = None  # unreachable holder: degrade, don't fail
            if data is not None and len(data) == size:
                return data
            # a truncated remote answer degrades to reconstruction too:
            # the holder is damaged, but >=10 survivors can still serve
        return self._recover_span(shard_id, offset, size)

    def recover_stats(self) -> dict:
        """This volume's recovered-block cache occupancy + the process'
        cumulative degraded-read stage stats."""
        out = RECOVER_STATS.snapshot()
        out["cache_blocks"] = len(self._recover_cache)
        out["cache_bytes"] = self._recover_cache.size_bytes
        return out

    # -- degraded reads -------------------------------------------------------
    def _recover_span(self, target_shard: int, offset: int,
                      size: int) -> bytes:
        """Serve a missing shard's span by reconstruction — the fast
        degraded-read path.  Recovery is block-aligned: the span's
        covering WEED_EC_RECOVER_BLOCK_KB blocks are recovered (not the
        exact span), cached in the bounded per-volume LRU, and served
        from cache for every later read that lands in them.  Concurrent
        misses on one block are single-flighted; misses on different
        blocks that picked the same survivors decode in one stacked GF
        mat-vec (recover.py).  With no local shard to size blocks
        against (shard_size unknown) the exact span becomes the unit —
        still coalesced and cached."""
        _tls.busy = 0.0
        with tracing.stage("ec.recover.serve", self._add_serve, "serve",
                           target_shard, size):
            cache_bytes, block, coalesce = recover_knobs()
            shard_size = self.shard_size
            # recovery units must be sub-shard-aligned so vector codes
            # (alpha > 1) see whole interleaved lane groups; the KB-sized
            # block knob is always a multiple of alpha already
            align = self.family.sub_shards
            if block <= 0 or shard_size <= 0:
                lo = (offset // align) * align
                end = -(-(offset + size) // align) * align
                spans = [(lo, end - lo)]
            else:
                lo = (offset // block) * block
                end = max(offset + size,
                          min(shard_size,
                              -(-(offset + size) // block) * block))
                end = -(-end // align) * align
                spans = [(s, min(block, end - s))
                         for s in range(lo, end, block)]
            parts = []
            for bstart, blen in spans:
                key = (target_shard, bstart, blen)
                parts.append(self._recover_cache.get_or_recover(
                    key, lambda bs=bstart, bl=blen: self._recover_block(
                        target_shard, bs, bl),
                    cache_bytes, coalesce))
            blob = parts[0] if len(parts) == 1 else b"".join(parts)
            out = blob[offset - spans[0][0]:offset - spans[0][0] + size]
            if len(out) != size:
                raise EcError(
                    f"recovered span short for shard {target_shard} at "
                    f"{offset}+{size}: got {len(out)}")
        # read_needle's count of the intervals it served through here
        tls = _tls
        tls.recovered = getattr(tls, "recovered", 0) + 1
        tls.recovered_bytes = getattr(tls, "recovered_bytes", 0) + size
        return out

    def _add_serve(self, stage: str, seconds: float):
        """ec.recover.serve's accumulator: the span measured the whole
        degraded read; the serve stage is that wall minus this thread's
        fetch+decode busy seconds."""
        RECOVER_STATS.add_stage(
            stage, max(0.0, seconds - getattr(_tls, "busy", 0.0)))

    def _recover_block(self, target_shard: int, offset: int,
                       size: int) -> bytes:
        """One block's survivor fan-out + decode (the single-flight
        leader's job): fetch >=10 survivor spans, then reconstruct ONLY
        the target row through the decode-plan cache and the span-decode
        batcher."""
        blk0 = time.perf_counter()
        token = self._slab_token
        try:
            with tracing.stage("ec.recover.fetch", RECOVER_STATS.add_stage,
                               "fetch", target_shard, size):
                survivors, inputs = self._fetch_survivors(
                    target_shard, offset, size)
            # what names the survivor spans stands for their bytes: a
            # sealed volume's shard files are never rewritten in place,
            # and a shard mounted or unmounted beside the fetch changed
            # the token.  An inline volume's shard bytes can still change
            # (the same offset reads zeros now and data later): then, as
            # after a changed token, no identity and a plain upload.
            ident = ((token, offset, size)
                     if self.tail_reader is None
                     and token == self._slab_token else None)
            out = self._recover_batcher.decode(
                survivors, target_shard, inputs, ident)
            return np.ascontiguousarray(out).tobytes()
        finally:
            _tls.busy = (getattr(_tls, "busy", 0.0)
                         + (time.perf_counter() - blk0))

    def _fetch_survivors(self, target_shard: int, offset: int,
                         size: int) -> tuple[tuple, np.ndarray]:
        """Collect exactly DATA_SHARDS_COUNT survivor spans for one
        recovery (recoverOneRemoteEcShardInterval, store_ec.go:328-382).

        Survivor fetches fan out in PARALLEL like the reference's
        per-shard goroutines: local shards are read synchronously (disk,
        cheap, first-10-wins), then the remaining remote candidates are
        requested at once on a SHARED pool and the first arrivals win —
        a degraded read during an outage costs ~one RPC round-trip, not
        ten serial ones.  Queued stragglers are cancelled; in-flight
        ones drain on the shared pool (remote_reader RPCs carry their
        own timeouts).  Returns (sorted survivor ids, (k, L) stack in
        that order) — the decode-plan cache key and its matching input;
        k is the volume's code family's data-shard count."""
        k = self.family.data_shards
        shards: dict[int, np.ndarray] = {}
        remote_candidates: list[int] = []
        for sid in range(TOTAL_SHARDS_COUNT):
            if sid == target_shard:
                continue
            shard = self.shards.get(sid)
            if shard is not None:
                if len(shards) >= k:
                    continue  # reconstruct needs exactly k survivors
                try:
                    data = shard.read_at(size, offset)
                except (OSError, EcError):
                    continue  # closed beside this read: not a survivor
                if len(data) != size and self.tail_reader is not None:
                    # inline volume: the span runs past the shard log's
                    # durable extent.  The tail stripe serves pending
                    # rows; past that a DATA shard's content is
                    # definitionally zero (parity rows are encoded over
                    # the zero-padded row), while a parity shard without
                    # tail coverage is simply not a survivor
                    rest = self.tail_reader(sid, offset + len(data),
                                            size - len(data))
                    if rest is None and sid < k:
                        rest = b"\x00" * (size - len(data))
                    if rest is not None:
                        data += rest
                if len(data) == size:
                    shards[sid] = np.frombuffer(data, dtype=np.uint8)
            elif self.remote_reader is not None:
                remote_candidates.append(sid)
        if len(shards) < k and remote_candidates:
            import concurrent.futures as cf

            from ...qos import classify as qos_classify
            from ...rpc.http_rpc import current_deadline, set_deadline

            # pool workers don't share this thread's locals: pin the
            # caller's propagated deadline and QoS context on each fetch
            # so survivor RPCs stay inside the budget the client handed
            # us and keep their class downstream
            dl = current_deadline()
            qctx = (qos_classify.current_class(),
                    qos_classify.current_tenant())

            def fetch(sid: int):
                prev = set_deadline(dl)
                prev_q = qos_classify.set_qos(*qctx)
                try:
                    return self.remote_reader(sid, offset, size)
                finally:
                    qos_classify.set_qos(*prev_q)
                    set_deadline(prev)

            pool = _recover_pool()
            futs = {pool.submit(fetch, sid): sid
                    for sid in remote_candidates}
            try:
                for fut in cf.as_completed(futs):
                    try:
                        data = fut.result()
                    except Exception:
                        data = None
                    if data is not None and len(data) == size:
                        shards[futs[fut]] = np.frombuffer(data,
                                                          dtype=np.uint8)
                        if len(shards) >= k:
                            break
            finally:
                for fut in futs:
                    fut.cancel()
        if len(shards) < k:
            raise EcError(
                f"need {k} shards to recover shard "
                f"{target_shard}, only {len(shards)} available")
        survivors = tuple(sorted(shards))[:k]
        return survivors, np.stack([shards[sid] for sid in survivors])

    def _decode_span(self, survivors: tuple, target: int,
                     inputs: np.ndarray,
                     idents: Optional[tuple]) -> np.ndarray:
        """The batcher's decode hook: one cached decode row applied to
        the (possibly multi-span) survivor stack.  An explicitly-pinned
        encoder backend decodes through reconstruct_one on that backend
        (RS volumes only — pinned backends speak the RS layout); the
        default rides the size-dispatched reconstruct_span with this
        volume's code family.  `idents`, the stack's members as
        `_recover_block` named them, is the device slab pool's resident
        key: consecutive decodes of the same survivor spans (another
        missing shard, or a block re-recovered after cache eviction)
        reuse the HBM-resident upload instead of re-crossing the link.
        None: a plain upload."""
        if self._encoder is not None \
                and self.family.name == "rs_vandermonde":
            shard_list: list[Optional[np.ndarray]] = \
                [None] * TOTAL_SHARDS_COUNT
            for i, sid in enumerate(survivors):
                shard_list[sid] = inputs[i]
            return self._encoder.reconstruct_one(shard_list, target)
        return codec_mod.reconstruct_span(
            survivors, inputs, target,
            self.family.data_shards, TOTAL_SHARDS_COUNT,
            slab_key=idents, family=self.family,
            add_stage=RECOVER_STATS.add_stage)

    # -- delete (ec_volume_delete.go) -----------------------------------------
    def delete_needle(self, needle_id: int):
        """Tombstone the .ecx entry in place + journal the id in .ecj."""
        with self._ecx_lock:
            pos = self._search_ecx(needle_id)
            if pos is None:
                return
            self._mark_ecx_deleted(pos)
        with self._ecj_lock:
            self._ecj.seek(0, 2)
            self._ecj.write(struct.pack(">Q", needle_id))
            self._ecj.flush()
            self.ecj_file_size += t.NEEDLE_ID_SIZE

    def _mark_ecx_deleted(self, pos: int):
        size_off = (pos * t.NEEDLE_MAP_ENTRY_SIZE
                    + t.NEEDLE_ID_SIZE + t.OFFSET_SIZE)
        os.pwrite(self._ecx.fileno(),
                  struct.pack(">i", t.TOMBSTONE_FILE_SIZE), size_off)

    # -- lifecycle ------------------------------------------------------------
    def close(self):
        for shard in self.shards.values():
            shard.close()
        self.shards.clear()
        self._recover_cache.clear()
        if self._ecx_map is not None:
            self._ecx_map.close()
            self._ecx_map = None
        if self._ecx:
            self._ecx.close()
            self._ecx = None
        if self._ecj:
            self._ecj.close()
            self._ecj = None

    def destroy(self):
        base = self.base_file_name()
        for shard in list(self.shards.values()):
            shard.destroy()
        self.shards.clear()
        self.close()
        for ext in (".ecx", ".ecj", ".vif"):
            try:
                os.remove(base + ext)
            except FileNotFoundError:
                pass


def rebuild_ecx_file(base_file_name: str):
    """Replay .ecj tombstones into .ecx then remove the journal
    (RebuildEcxFile, ec_volume_delete.go:53-98)."""
    if not os.path.exists(base_file_name + ".ecj"):
        return
    with open(base_file_name + ".ecx", "r+b") as ecx:
        ecx_size = os.path.getsize(base_file_name + ".ecx")
        n_entries = ecx_size // t.NEEDLE_MAP_ENTRY_SIZE

        with open(base_file_name + ".ecj", "rb") as ecj:
            while True:
                buf = ecj.read(t.NEEDLE_ID_SIZE)
                if len(buf) != t.NEEDLE_ID_SIZE:
                    break
                pos = search_sorted_index(
                    ecx.fileno(), n_entries, struct.unpack(">Q", buf)[0])
                if pos is not None:
                    size_off = (pos * t.NEEDLE_MAP_ENTRY_SIZE
                                + t.NEEDLE_ID_SIZE + t.OFFSET_SIZE)
                    os.pwrite(ecx.fileno(),
                              struct.pack(">i", t.TOMBSTONE_FILE_SIZE),
                              size_off)
    os.remove(base_file_name + ".ecj")
