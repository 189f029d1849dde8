"""Streaming batched EC encode: .dat files -> 14 shard files through the
sharded TPU encoder, with pipelined host I/O.

This is the production encode path (BASELINE configs 1 + 4).  The reference
encodes one volume at a time, feeding its CPU codec 256 KB-per-shard slices
inside a synchronous loop (/root/reference/weed/storage/erasure_coding/
ec_encoder.go:194-231).  Here the striped rows of MANY volumes are tiled
into (B, 10, L) uint8 batches and pushed through one jit-compiled
parity+CRC step (parallel/mesh.py) with a three-stage pipeline:

  read stage      — a coordinator thread and a few I/O workers fill
                    pooled host staging slots from the .dat files, a
                    batch at a time and in batch order, and write the
                    data-shard bytes to .ec00-.ec09 (data shards are a
                    pure re-interleaving of the .dat, no compute needed;
                    all-zero padding rows are skipped — the shard files
                    are ftruncate()d to final size, so their bytes are
                    already zero);
  main thread     — dispatches batch N+1 into the persistent jitted step
                    while earlier batches are still in flight (depth
                    WEED_EC_DEVICE_INFLIGHT), uploading through the
                    device slab pool (ops/device_pool.py): staging slots
                    and donated output slots are leased once and recycled,
                    so the steady state performs zero per-batch device
                    allocations.  On a mesh of N devices whole batches
                    are dealt to the devices in turn (_DeviceLane): one
                    contiguous upload, one one-device step and one
                    single-device copy back a batch, never an array
                    sharded over the mesh;
  completion thread — synchronizes finished batches, chains per-shard-file
                    rolling CRC32Cs, recycles slots, and hands parity to
  writer thread   — appends parity bytes to .ec10-.ec13.

Units from ALL volumes in the call pack into ONE fixed compiled shape
(tail batch padded, pad columns masked out of CRC and writes), so a
100-volume encode is one pipeline with at most a handful of compiled
shapes.  On TPU meshes the per-chunk CRC32C is computed on device, fused
with the parity matmul (BASELINE config 5); on CPU meshes parity runs as
a persistent batched SWAR step and CRCs use the ~30x-faster host crc32c
kernel, overlapped with the next batch's compute.  Whole-shard-file CRCs
are returned and persisted in the .vif sidecar for scrub tooling.
"""

from __future__ import annotations

import ctypes
import functools
import math
import os
import queue
import threading
import time
from collections import defaultdict, deque
from concurrent import futures
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .. import profiling, tracing
from ..qos import lanes as _lanes

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = 14

# per-dispatch target: B * 10 * L bytes of data-shard input
TARGET_BATCH_BYTES = 64 << 20
MAX_CHUNK_BYTES = 1 << 20
_SLOTS = 4   # host staging buffers in flight
_INFLIGHT = 3  # device dispatches queued before draining (hides dispatch
               # and transfer latency)


@dataclass
class _Unit:
    """One (volume, row, column-chunk): a (10, L) slice of work."""
    vol: int
    row_start: int     # byte offset of the row in the .dat
    shard_off: int     # byte offset of this chunk in each shard file
    col: int           # column offset within the row's blocks
    block_size: int
    real_rows: int = DATA_SHARDS  # rows with any .dat bytes; rows past
    #                               this are the format's zero padding


@dataclass
class _VolumePlan:
    base: str
    dat_size: int
    rows: list[tuple[int, int, int]] = field(default_factory=list)
    # (row_start_in_dat, shard_offset, block_size)


def _plan_volume(base: str, large_block: int, small_block: int) -> _VolumePlan:
    """Row plan mirroring WriteEcFiles striping (ec_encoder.go:57-59):
    large rows while > 10 large blocks remain, then small rows, zero-padded."""
    dat_size = os.path.getsize(base + ".dat")
    plan = _VolumePlan(base, dat_size)
    remaining = dat_size
    row_start = 0
    shard_off = 0
    while remaining > large_block * DATA_SHARDS:
        plan.rows.append((row_start, shard_off, large_block))
        row_start += large_block * DATA_SHARDS
        shard_off += large_block
        remaining -= large_block * DATA_SHARDS
    while remaining > 0:
        plan.rows.append((row_start, shard_off, small_block))
        row_start += small_block * DATA_SHARDS
        shard_off += small_block
        remaining -= small_block * DATA_SHARDS
    return plan


def _chunk_len(large_block: int, small_block: int) -> int:
    """Static column-chunk width L: divides every block size in the plan."""
    cand = min(small_block, MAX_CHUNK_BYTES)
    if large_block % cand == 0 and small_block % cand == 0:
        return cand
    return math.gcd(large_block, small_block)


def _make_units(plans: list[_VolumePlan], chunk: int) -> list[_Unit]:
    units = []
    for vi, plan in enumerate(plans):
        for row_start, shard_off, block in plan.rows:
            for col in range(0, block, chunk):
                # rows i with row_start + i*block + col < dat_size carry
                # real bytes; the rest are zero padding the device paths
                # can compact away (their shard bytes are ftruncate
                # zeros and their chunk CRC is crc32c_zeros(chunk))
                avail = plan.dat_size - row_start - col
                real = 0 if avail <= 0 else min(
                    DATA_SHARDS, -(-avail // block))
                units.append(_Unit(vi, row_start, shard_off + col, col,
                                   block, real))
    return units


# -- the write stage's shared plumbing --------------------------------------
# checked vectored writes, dirty-page writeback pacing, and the raw shard
# fd set.  Shared by all three consumers: the host pipeline's writer pool,
# the device pipeline's drain side, and the rebuild path.

_IOV_MAX = 1024       # kernel cap on iovecs per pwritev
_SFR_WRITE = 2        # SYNC_FILE_RANGE_WRITE
# bytes written to an fd before the pacer kicks writeback of the window
WRITE_FLUSH_BYTES = 32 << 20

_sfr_fn = None
_sfr_probed = False


def _sync_file_range():
    """ctypes handle to sync_file_range(2) — not exposed by the os
    module; None when the libc doesn't have it (non-Linux)."""
    global _sfr_fn, _sfr_probed
    if not _sfr_probed:
        _sfr_probed = True
        try:
            libc = ctypes.CDLL(None, use_errno=True)
            fn = libc.sync_file_range
            fn.argtypes = [ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
                           ctypes.c_uint]
            fn.restype = ctypes.c_int
            _sfr_fn = fn
        except (OSError, AttributeError):
            _sfr_fn = None
    return _sfr_fn


def _preadv_full(fd: int, view: memoryview, offset: int):
    """Positional read that fills `view` or raises OSError: a short read
    is continued from where the kernel stopped, and end of file before
    the planned bytes (a .dat truncated under the seal, a survivor under
    the rebuild) fails the job rather than sealing or rebuilding from
    zeros."""
    got = 0
    while got < view.nbytes:
        n = os.preadv(fd, [view[got:]], offset + got)
        if n <= 0:
            raise OSError(
                "preadv reached end of file: %d of %d bytes at offset %d "
                "(file shorter than planned)" % (got, view.nbytes, offset))
        got += n


def pwritev_full(fd: int, bufs, offset: int) -> int:
    """pwritev that writes every byte or raises OSError.  A short kernel
    write must fail the encode, not silently truncate a shard whose CRC
    was already computed from memory: partial progress is retried from
    where the kernel stopped; zero progress is a hard error."""
    iovs = [memoryview(b) for b in bufs]
    total = sum(v.nbytes for v in iovs)
    written = 0
    while written < total:
        n = os.pwritev(fd, iovs, offset + written)
        if n <= 0:
            raise OSError(
                "pwritev made no progress: %d of %d bytes at offset %d "
                "(shard would be truncated)" % (written, total, offset))
        written += n
        if written >= total:
            break
        while n >= iovs[0].nbytes:  # drop fully-written iovecs
            n -= iovs[0].nbytes
            iovs.pop(0)
        if n:
            iovs[0] = iovs[0][n:]
    return total


class WritebackPacer:
    """Paces dirty-page writeback for the shard writer stage: after
    every `WRITE_FLUSH_BYTES` written to an fd, kick the kernel's async
    writeback for the newly-written window (sync_file_range(WRITE)) so
    dirty pages drain continuously instead of accumulating until
    vm.dirty_ratio stalls every writer at once — the failure mode of the
    8.79 GiB scale run, whose write stage was 93.5% of wall time.

    Time spent flushing is accumulated in `flush_seconds` so callers can
    attribute it separately from the pwritev busy time."""

    def __init__(self):
        self._sfr = _sync_file_range()
        # 0 where the libc has no sync_file_range: nothing to pace with
        self.flush_bytes = WRITE_FLUSH_BYTES if self._sfr is not None else 0
        self._lock = threading.Lock()
        self._state: dict[int, list[int]] = {}  # fd -> [acc, cursor, hi]
        self.flush_seconds = 0.0
        self.flushes = 0

    def wrote(self, fd: int, offset: int, n: int):
        if self.flush_bytes <= 0 or n <= 0:
            return
        with self._lock:
            st = self._state.setdefault(fd, [0, 0, 0])
            st[0] += n
            end = offset + n
            if end > st[2]:
                st[2] = end
            if st[0] < self.flush_bytes:
                return
            st[0] = 0
            lo, hi = st[1], st[2]
            st[1] = hi
        if hi <= lo:
            return
        t0 = time.perf_counter()
        self._sfr(fd, lo, hi - lo, _SFR_WRITE)
        with self._lock:
            self.flush_seconds += time.perf_counter() - t0
            self.flushes += 1

    def forget(self, fds):
        """Drop per-fd state on close: fd numbers get recycled."""
        with self._lock:
            for fd in fds:
                self._state.pop(fd, None)


class _ShardFileSet:
    """The shard files one job writes for a volume (all 14 for a seal,
    the missing ones for a rebuild) as raw O_WRONLY fds (no
    BufferedWriter copy, no seek-flush churn — profiling showed buffered
    seek+write was the #1 cost of the old host stage) with rolling
    per-file CRC32C.  pwritev is positional and thread-safe, so reader,
    writer-pool and drain threads can all write concurrently.  Files are
    ftruncate()d to their final size up front: extending i_size a
    megabyte at a time measurably slows tmpfs/ext4 writes (~3x on the
    profiled box).  Every write goes through the checked pwritev (full
    length or OSError) and reports to the writeback pacer."""

    def __init__(self, base: str, to_ext, shard_size: int = 0,
                 pacer: Optional[WritebackPacer] = None,
                 shards=range(TOTAL_SHARDS)):
        self.fds: dict[int, int] = {}
        self.crcs = [0] * TOTAL_SHARDS
        self.pacer = pacer
        try:
            for i in shards:
                self.fds[i] = os.open(
                    base + to_ext(i),
                    os.O_CREAT | os.O_TRUNC | os.O_WRONLY, 0o644)
                if shard_size:
                    os.ftruncate(self.fds[i], shard_size)
        except BaseException:
            self.close()
            raise

    def write(self, shard: int, bufs, offset: int) -> int:
        fd = self.fds[shard]
        n = pwritev_full(fd, bufs, offset)
        if self.pacer is not None:
            self.pacer.wrote(fd, offset, n)
        return n

    def close(self):
        if self.pacer is not None:
            self.pacer.forget(self.fds.values())
        for fd in self.fds.values():
            os.close(fd)


def encode_volumes(bases: list[str], large_block: Optional[int] = None,
                   small_block: Optional[int] = None,
                   mesh=None, batch_units: Optional[int] = None,
                   host_codec=None,
                   stage_stats: Optional[dict] = None) -> dict[str, list[int]]:
    """Encode every `base` (.dat) into 14 shard files via the batched
    pipeline.  Returns {base: [crc32c of each shard file] * 14}.

    Volumes are batched together: chunks from different volumes ride the
    same device dispatch, which is what makes the 100-volume HBM-resident
    configuration (BASELINE config 4) one pipeline rather than 100 encodes.

    host_codec: pass an encoder object (or True for the best host codec)
    to run the host pipeline — a reader thread filling staging slots and a
    pool of compute workers, each encoding a span through the fused
    native parity+CRC call (ops/codec.py encode_rows) and pwritev()ing
    its data+parity shard bytes on unbuffered fds.  This is the auto-selected
    fallback on link-capped machines: unlike the reference's synchronous
    loop (ec_encoder.go:194-231) it overlaps file I/O with compute and
    fans the codec out across cores, and it still produces the fused
    shard-file CRCs for the .vif.

    stage_stats: optional dict filled with per-stage busy seconds
    (read/encode+crc/write) and wall time — the pipeline's own answer to
    "which stage is the bottleneck" at any scale.
    """
    from ..storage.erasure_coding import (LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE,
                                          to_ext)

    large_block = large_block or LARGE_BLOCK_SIZE
    small_block = small_block or SMALL_BLOCK_SIZE
    plans = [_plan_volume(b, large_block, small_block) for b in bases]
    chunk = _chunk_len(large_block, small_block)
    units = _make_units(plans, chunk)

    if not units:
        out = {}
        for vi, p in enumerate(plans):
            _ShardFileSet(p.base, to_ext).close()
            out[p.base] = [0] * TOTAL_SHARDS
        return out
    # the seal's root span, current on this thread while either pipeline
    # runs: their worker threads install it too, so a sampled seal shows
    # its per-batch stages, and the per-stage totals hang under it
    root = tracing.start("ec.encode_volumes",
                         tags={"volumes": len(plans), "units": len(units)})
    prev = tracing.swap(root)
    try:
        if host_codec:
            return _encode_units_host(plans, units, chunk, host_codec,
                                      stage_stats)
        pacer = WritebackPacer()
        writers = {vi: _ShardFileSet(
                       p.base, to_ext,
                       (p.rows[-1][1] + p.rows[-1][2]) if p.rows else 0,
                       pacer)
                   for vi, p in enumerate(plans)}
        return _encode_units_device(plans, units, chunk, writers, mesh,
                                    batch_units, stage_stats)
    except BaseException as e:
        root.status = f"error: {type(e).__name__}"
        raise
    finally:
        tracing.restore(prev)
        root.finish()


def _put(stop: threading.Event, q: "queue.Queue", item) -> bool:
    """`q.put(item)` that gives up once the job has stopped: False then,
    and the item was not queued."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.5)
            return True
        except queue.Full:
            continue
    return False


def _get(stop: threading.Event, q: "queue.Queue"):
    """`q.get()` that gives up once the job has stopped: None then."""
    while not stop.is_set():
        try:
            return q.get(timeout=0.5)
        except queue.Empty:
            continue
    return None


class _ReadStage:
    """The read stage of both streaming pipelines (the seal's and the
    rebuild's): one coordinator thread and `read_workers` I/O workers
    that fill pooled staging slots a batch at a time and in batch order,
    ahead of the thread that uploads them.

    `batches` yields, a batch, `(steps, meanwhile, item)`.  The
    coordinator takes a free slot, hands the deque `steps` to the
    workers (each takes steps off it until it is empty and runs the
    client's `step(buf, step, split)`: one row read into its place in
    the slot by a positional call outside the GIL), runs
    `meanwhile(buf)` if there is one, waits for all of them and only
    then puts `(slot, *item)` on `ready`: batches reach the dispatch
    loop, the CRC chaining and the writer in order, while the rows
    inside a batch overlap.  The rows are independent (each its own
    chunk of the slot and its own range of a file), and one worker runs
    the same loop.  The client gives a slot back through `free_slots`
    once its batch no longer needs it.  `read_workers` follows the cores
    the process may use (`_read_workers`); tests pass it to the
    constructor.

    Seconds, through `add_time`: `read` = the stage's wall, one block a
    batch around the fan-out (the `<span>.read` stage); `read_slot_wait`
    = the coordinator blocked on a free slot (`<span>.stage_wait`: the
    stages behind set the pace); `read_worker_busy` = thread-seconds the
    workers spent in their loops, so it may exceed `read`:
    `read_worker_busy / read` is how many workers the stage kept busy
    (1 = the pool bought nothing, N = perfect overlap).  A step may
    split its own time further into `split` (key -> seconds, bare clock
    readings: a span per 1 MiB row would cost more than it tells); the
    worker adds those up once a batch.

    A failing step stops the other workers and the coordinator; the
    error is in `errors`, `stop` is set, and `get` then returns None to
    whoever waits on `ready`."""

    def __init__(self, span: str, batches, step, slots, add_time,
                 read_workers: Optional[int] = None):
        self.batches, self.step, self.add_time = batches, step, add_time
        self._read_span = span + ".read"
        self._wait_span = span + ".stage_wait"
        # the job's span (the seal's, the rebuild request's), installed
        # on the coordinator
        self.root = tracing.current()
        self.read_workers = read_workers or _read_workers()
        threads = span.replace(".", "-")    # ec-encode-read, ec-rebuild-read
        self._workers = futures.ThreadPoolExecutor(
            self.read_workers, thread_name_prefix=threads + "-read")
        self.free_slots: "queue.Queue" = queue.Queue()
        for ls in slots:
            self.free_slots.put(ls)
        self.ready: "queue.Queue" = queue.Queue(maxsize=len(slots))
        self.errors: list[BaseException] = []
        self.stop = threading.Event()
        self._coordinator = threading.Thread(
            target=self._coordinate, daemon=True, name=threads + "-reader")

    def put(self, q, item) -> bool:
        return _put(self.stop, q, item)

    def get(self, q):
        return _get(self.stop, q)

    def _run_steps(self, buf: np.ndarray, steps: deque):
        """One I/O worker's share of a batch."""
        split: dict = defaultdict(float)
        step = self.step
        t_in = time.perf_counter()
        try:
            while not self.stop.is_set():
                try:
                    item = steps.popleft()
                except IndexError:
                    break
                step(buf, item, split)
        except BaseException as e:  # fails the job, stops the others
            self.errors.append(e)
            self.stop.set()
        self.add_time("read_worker_busy", time.perf_counter() - t_in)
        for key, seconds in split.items():
            self.add_time(key, seconds)

    def _coordinate(self):
        tracing.swap(self.root)
        try:
            for n, (steps, meanwhile, item) in enumerate(self.batches):
                with tracing.stage(self._wait_span, self.add_time,
                                   "read_slot_wait", n):
                    slot = self.get(self.free_slots)
                if slot is None:
                    return
                buf = slot.payload
                with tracing.stage(self._read_span, self.add_time, "read",
                                   n, len(steps) * buf.shape[-1]):
                    jobs = [self._workers.submit(self._run_steps, buf, steps)
                            for _ in range(self.read_workers)]
                    if meanwhile is not None:
                        meanwhile(buf)
                    for job in jobs:
                        job.result()
                if not self.put(self.ready, (slot, *item)):
                    return      # also when a stop cut the batch short
            self.put(self.ready, None)
        except BaseException as e:  # propagate to the pipeline's thread
            self.errors.append(e)
            self.stop.set()
        finally:
            tracing.restore(None)

    def start(self):
        self._coordinator.start()

    def close(self):
        """Stop and join the coordinator and the workers.  Slots, files
        and leases are the client's to release, after this."""
        self.stop.set()
        if self._coordinator.is_alive():
            self._coordinator.join(timeout=30)
        self._workers.shutdown(wait=True)   # they saw the stop


class _WriteStage:
    """The write-behind stage of every pipeline here (the seal's, the
    rebuild's, the host pipeline's): a bounded queue and `writers`
    threads that run the client's `write(item)` on what `put` hands
    them, in the order it was put (one writer) or each its own share in
    that order (a pool), so that the thread that computes never waits
    for a checked pwritev unless the queue is full.

    It shares the job's one `errors` list and one `stop` event with the
    read stage and the pipeline's own threads: a write that fails is in
    `errors`, `stop` is set, readers, dispatch and completion end, and
    `put` returns False from then on.

    Seconds, through `add_time`: `write` = a writer inside `write(item)`
    (the `<span>.write` stage, one block an item, with `put`'s `nbytes`);
    what the pipeline's thread loses to a full queue is the client's to
    time around `put` and `close` (the rebuild's `write_wait`)."""

    def __init__(self, span: str, write, add_time, errors: list,
                 stop: threading.Event, depth: int, writers: int = 1):
        self.write, self.add_time = write, add_time
        self.errors, self.stop = errors, stop
        self._span = span + ".write"
        self.root = tracing.current()   # the job's span, as _ReadStage's
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        name = span.replace(".", "-") + "-writer"   # ec-encode-writer
        self._threads = [threading.Thread(target=self._run, daemon=True,
                                          name=name)
                         for _ in range(writers)]

    def put(self, item, nbytes: int = -1) -> bool:
        return _put(self.stop, self.q, (item, nbytes))

    def take_if(self, joins):
        """Without waiting: the next queued item if `joins(item)` holds,
        else None, and the item stays where it is.  For a client whose
        `write` can serve the item behind its own in the same call."""
        q = self.q
        with q.mutex:
            head = q.queue[0] if q.queue else None
            if head is None or not joins(head[0]):
                return None
            q.queue.popleft()
            q.not_full.notify()
        return head[0]

    def _run(self):
        tracing.swap(self.root)
        try:
            n = 0
            while True:
                entry = _get(self.stop, self.q)
                if entry is None:
                    return
                item, nbytes = entry
                with tracing.stage(self._span, self.add_time, "write", n,
                                   nbytes):
                    self.write(item)
                n += 1
        except BaseException as e:  # fails the job, stops the others
            self.errors.append(e)
            self.stop.set()
        finally:
            tracing.restore(None)

    def start(self):
        for t in self._threads:
            t.start()

    def close(self):
        """Let the writers finish what is queued (nothing, once the job
        has stopped) and join them.  Files are the client's to close,
        after this."""
        for _ in self._threads:
            _put(self.stop, self.q, None)
        for t in self._threads:
            if t.is_alive():
                t.join(timeout=600)
            if t.is_alive():    # its files are about to be closed
                self.errors.append(TimeoutError(
                    f"{t.name} still writing after 600 s"))
                self.stop.set()


class _PipelineIO:
    """Shared reader/writer scaffolding of the streaming encode
    pipeline: pooled staging slots, backpressure queues, the read stage
    (fills slots and writes data shards), the write stage (one thread
    that appends parity shards), and the torn-shutdown sequencing.  The
    device compute stages differ only in what happens between `ready`
    and `writes.put`.

    The read stage is a `_ReadStage`, which the rebuild composes too.
    The seal hands it, a batch, the (k, unit, row) steps of the rows
    that hold .dat bytes, and as a worker's step: read the row's .dat
    bytes into its place in the slot (preadv), write them to its data
    shard (pwritev), both positional and both outside the GIL.

    Staging slots are leased from the device slab pool so repeated
    encodes with the same geometry reuse the same buffers.  Two layouts:

      "bk" — (B, 10, L): the TPU words step's and the XLA step's input
             layout; every unit's 10 rows are zero-padded to the format
             (device CRC covers all 14 shards).
      "kb" — (10, B, L): the pooled CPU parity step's layout — slicing
             [:k_max] off axis 0 compacts away trailing all-zero rows
             as one contiguous view, and each shard row stays contiguous
             for readinto/pwritev/host-CRC.

    Either way a worker is handed a contiguous row; the read stage
    skips zero-padding rows' shard writes (the files are ftruncate zeros
    already) and trims partial tail rows to their real bytes; `ready`
    items carry the batch's compacted row count k_max ("bk" always
    reports the full 10)."""

    def __init__(self, plans, units, chunk, writers, b, layout, pool,
                 n_slots=_SLOTS, read_workers: Optional[int] = None):
        self.plans, self.units, self.chunk = plans, units, chunk
        self.writers, self.b = writers, b
        self.layout = layout
        self.pool = pool
        # the seal's span (encode_volumes'), installed on worker threads
        self.root = tracing.current()
        self.n_batches = (len(units) + b - 1) // b
        # raw fds: the workers read positionally, sharing no file offset
        self.dats = [os.open(p.base + ".dat", os.O_RDONLY) for p in plans]
        # busy seconds per stage.  read, read_worker_busy and
        # read_slot_wait are the read stage's (_ReadStage); read_dat and
        # read_data_write split its workers' seconds into their two
        # calls; dispatch = upload + step call (h2d is its upload);
        # encode_crc = the completion thread's per-batch block (d2h_wait
        # = blocked in the copy back, crc_host = the host work after it)
        self.timers = {"read": 0.0, "read_worker_busy": 0.0,
                       "read_dat": 0.0, "read_data_write": 0.0,
                       "read_slot_wait": 0.0, "dispatch": 0.0, "h2d": 0.0,
                       "encode_crc": 0.0, "d2h_wait": 0.0, "crc_host": 0.0,
                       "write": 0.0}
        self.tlock = threading.Lock()
        shape = (b, DATA_SHARDS, chunk) if layout == "bk" \
            else (DATA_SHARDS, b, chunk)
        key = ("ec-stage", layout, shape)
        nbytes = b * DATA_SHARDS * chunk
        self._slot_leases = [
            pool.lease(key, lambda: np.zeros(shape, dtype=np.uint8), nbytes)
            for _ in range(n_slots)]
        self.reads = _ReadStage("ec.encode", self._batches(),
                                self._read_row, self._slot_leases,
                                self.add_time, read_workers)
        self.read_workers = self.reads.read_workers
        self.free_slots, self.ready = self.reads.free_slots, self.reads.ready
        # one stop and one list of errors for every thread of the seal
        self.errors, self.stop = self.reads.errors, self.reads.stop
        self.put, self.get = self.reads.put, self.reads.get
        self.writes = _WriteStage("ec.encode", self._write_parity,
                                  self.add_time, self.errors, self.stop,
                                  depth=n_slots)

    def add_time(self, key: str, seconds: float):
        """The stage accumulator handed to tracing.stage()."""
        with self.tlock:
            self.timers[key] = self.timers.get(key, 0.0) + seconds

    def _read_row(self, buf: np.ndarray, step, split: dict):
        """A worker's step: the real .dat bytes of row `i` of unit `k`
        into their place in the staging slot (zero past them), and on to
        data shard `i`."""
        k, u, i = step
        t = time.perf_counter()
        row = buf[i, k] if self.layout == "kb" else buf[k, i]
        start = u.row_start + i * u.block_size + u.col
        real = min(self.chunk, self.plans[u.vol].dat_size - start)
        _preadv_full(self.dats[u.vol], memoryview(row)[:real], start)
        if real < self.chunk:
            row[real:] = 0
        t1 = time.perf_counter()
        split["read_dat"] += t1 - t
        self.writers[u.vol].write(i, [row[:real]], u.shard_off)
        split["read_data_write"] += time.perf_counter() - t1

    def _zero_padding(self, batch, k_max: int, buf: np.ndarray):
        """Padding rows up to the compacted height are zeroed while the
        workers read: they feed the parity math but neither files nor
        CRCs (files are ftruncate zeros, CRC is the cached zeros CRC)."""
        for k, u in enumerate(batch):
            for i in range(u.real_rows, k_max):
                if self.layout == "kb":
                    buf[i, k].fill(0)
                else:
                    buf[k, i].fill(0)

    def _batches(self):
        for n in range(self.n_batches):
            batch = self.units[n * self.b:(n + 1) * self.b]
            if self.layout == "kb":
                k_max = max(u.real_rows for u in batch)
            else:
                k_max = DATA_SHARDS
            steps = deque((k, u, i) for k, u in enumerate(batch)
                          for i in range(u.real_rows))
            yield (steps,
                   functools.partial(self._zero_padding, batch, k_max),
                   (batch, k_max))

    def _write_parity(self, item):
        """The write stage's client: a batch's parity rows on to the
        parity shards."""
        parity, batch = item
        for k, u in enumerate(batch):
            if u.real_rows == 0:
                continue  # parity of all-zero rows is zero: already on
                #           disk via ftruncate
            w = self.writers[u.vol]
            for i in range(PARITY_SHARDS):
                w.write(DATA_SHARDS + i, [parity[k, i]], u.shard_off)

    def start(self):
        self.reads.start()
        self.writes.start()

    def finish(self):
        self.writes.close()
        self.reads.close()
        for fd in self.dats:
            os.close(fd)
        for w in self.writers.values():
            w.close()
        for ls in self._slot_leases:
            self.pool.release(ls)
        self._slot_leases = []

    def result(self) -> dict[str, list[int]]:
        if self.errors:
            raise self.errors[0]
        from ..stats import metrics as stats

        stats.EcEncodeBytesCounter.inc(
            sum(p.dat_size for p in self.plans))
        return {p.base: self.writers[vi].crcs
                for vi, p in enumerate(self.plans)}


def _read_workers() -> int:
    """I/O workers of the device pipeline's read stage: a third of the
    cores this process may use, at most four.  Observed, not set: the
    stage's gain flattens at four on the 13- and 30-core chip hosts
    (from there the completion thread is as long as the read stage;
    PERF.md, PR 27), and the other two thirds are the pipeline's four
    other threads and the server's.  A one- or two-core machine runs
    the same loop with one worker."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:      # not on Linux
        cores = os.cpu_count() or 1
    return max(1, min(4, cores // 3))


def _device_inflight() -> int:
    """WEED_EC_DEVICE_INFLIGHT: device dispatches in flight before the
    completion thread must drain one (default 3).  Depth hides dispatch
    and transfer latency — H2D, compute and D2H genuinely overlap: the
    staging slots (depth + 1 or more) are the double-buffered H2D ring,
    the donated output slots (depth + 1 over the mesh, at least one a
    device) the D2H drain rings."""
    try:
        return max(1, int(
            os.environ.get("WEED_EC_DEVICE_INFLIGHT", "") or _INFLIGHT))
    except ValueError:
        return _INFLIGHT


def _fused_crc_on(platform: str) -> bool:
    """WEED_EC_FUSED_CRC: whether the pooled parity step also computes
    every shard row's CRC32C on device ("1"/"0" force it; "auto" — the
    default — fuses off-CPU and keeps the host crc32c walk on CPU
    meshes, where the native kernel is ~30x the GF(2) bit-matmul CRC's
    rate).  With the fused path active the host CRC walk leaves the
    completion thread entirely — the pipeline's critical path is
    read/dispatch/write only."""
    raw = os.environ.get("WEED_EC_FUSED_CRC", "auto").strip().lower()
    if raw in ("1", "on", "true", "fused", "yes"):
        return True
    if raw in ("0", "off", "false", "host", "no"):
        return False
    return platform != "cpu"


@dataclass
class _DeviceLane:
    """One device of the seal's mesh: whole batches are dealt to the
    lanes in turn (batch n to lane n mod N), so everything a batch
    touches on a device is single-device: its upload, the step compiled
    for that device, the ring of donated output slots the step aliases
    its parity into (pooled path), and the copy back.  `batches` counts
    the batches it took in this seal."""
    device: object
    step: object
    out_ring: "queue.Queue" = field(default_factory=queue.Queue)
    batches: int = 0

    @property
    def label(self) -> str:
        """What the slab pool and the link counters account the lane
        under."""
        return str(self.device)


def _encode_units_device(plans, units, chunk, writers, mesh,
                         batch_units,
                         stage_stats: Optional[dict] = None
                         ) -> dict[str, list[int]]:
    import jax
    from jax.sharding import Mesh

    from ..ops import crc32c as crc_host
    from ..ops.crc_device import finalize
    from ..ops.device_pool import get_pool
    from .mesh import (aliases_host_memory, make_ec_mesh, make_parity_step,
                       make_sharded_encoder, words_capable)

    wall0 = time.perf_counter()
    if mesh is None:
        mesh = make_ec_mesh()  # WEED_EC_DEVICE_SHARD picks the width
    devices = list(mesh.devices.flat)
    n_dev = len(devices)
    dev0 = devices[0]
    platform = dev0.platform
    # Path selection: the single-TPU-device Pallas words step when it
    # can serve; otherwise the pooled persistent kb step whenever the
    # chunk packs into int32 words; the bk XLA step is the odd-chunk
    # fallback.  Whichever it is, a mesh of N devices is N lanes of the
    # one-device step: no batch is sharded.
    use_words = words_capable(mesh, chunk)
    pooled = (not use_words) and chunk % 4 == 0
    # Pooled-path CRC placement (WEED_EC_FUSED_CRC): fused — the parity
    # step also emits every shard row's raw CRC32C image from the same
    # HBM-resident words — or the host crc32c walk on the completion
    # thread (the CPU-mesh default: the native host kernel is ~30x the
    # GF(2) bit-matmul CRC's rate there).
    fused = pooled and _fused_crc_on(platform)
    host_crc = pooled and not fused

    if batch_units is None:
        batch_units = max(1, TARGET_BATCH_BYTES // (DATA_SHARDS * chunk))
    # `batch_units` is what one round of the deal holds; a batch is one
    # lane's share of it, one execution of the step.  ONE fixed compiled
    # shape for every batch in the call (the tail batch is shorter than
    # b; its pad columns are never read back)
    b = -(-min(batch_units, len(units)) // n_dev)

    depth = _device_inflight()
    pool = get_pool()

    if pooled:
        make_step = functools.partial(make_parity_step, fused_crc=fused)
        layout = "kb"
        backend = ("device-pooled-swar-fused-crc" if fused
                   else "device-pooled-swar")
        # numpy -> jax via dlpack is ZERO-copy on the CPU backend: the
        # staging slot IS the device buffer, so H2D costs nothing (the
        # slot is recycled only after the completion thread synchronized
        # the batch, so the aliased memory is never overwritten mid-read)
        zero_copy = n_dev == 1 and aliases_host_memory(dev0)
    else:
        # word-layout fast path: packed int32 views move host<->device
        # with no device bitcasts (the relayout costs 10x the kernel)
        make_step = functools.partial(make_sharded_encoder, words=use_words)
        layout = "bk"
        backend = "device-words" if use_words else "device-xla"
        zero_copy = False
    # the step builders cache what they build, and JAX the executable
    # of each device: a later seal finds its lanes' programs built
    lanes = [_DeviceLane(d, make_step(
                 Mesh(np.array([d]).reshape(1, 1), mesh.axis_names)))
             for d in devices]

    # the staging slots double as the H2D ring: the reader fills slot
    # N+1 while slot N's transfer/compute is in flight, at any depth,
    # and one more than the lanes so that every lane can hold a batch
    # while the next is read
    n_slots = max(_SLOTS, depth + 1, n_dev + 1)
    io = _PipelineIO(plans, units, chunk, writers, b, layout, pool,
                     n_slots=n_slots)
    timers = io.timers
    add_time = io.add_time
    root = io.root

    # donated output-slot rings (pooled path), one a lane: device slots
    # the persistent step aliases its parity into — the donation swap
    # means the steady state allocates nothing on device per batch; a
    # ring is also the D2H drain buffer (the completion thread copies
    # out of one slot while the next is still computing).  depth + 1
    # slots over the mesh, and at least one a lane.
    out_leases: list = []
    if pooled:
        oshape = (PARITY_SHARDS, b, chunk // 4)

        def _out_slot(device):
            return jax.device_put(np.zeros(oshape, dtype=np.int32), device)

        for lane in lanes:
            for _ in range(-(-(depth + 1) // n_dev)):
                ls = pool.lease(("ec-out", oshape),
                                functools.partial(_out_slot, lane.device),
                                PARITY_SHARDS * b * chunk, device=lane.label)
                out_leases.append(ls)
                lane.out_ring.put(ls)

    zcrc = crc_host.crc32c_zeros(chunk)
    # every lane may hold a batch before the completion thread must
    # drain one
    done_q: "queue.Queue" = queue.Queue(maxsize=max(depth, n_dev))
    k_shapes: set = set()
    kernel_lats: list = []  # host-timed dispatch->ready per batch

    def _complete(n, lane, slot, batch, out, crc_dev, t_disp, k_rows):
        """Synchronize batch n, which ran on `lane`: D2H, per-chunk CRCs
        chained into the rolling shard-file CRCs (FIFO order — CRC
        chaining is order-dependent — while the batches behind it upload
        and compute on the other lanes), slots recycled, parity handed
        to the writer."""
        buf = slot.payload
        t0 = time.perf_counter()
        if pooled:
            parity = None
            fin = None
            if out is not None:
                with tracing.stage("ec.encode.d2h_wait", add_time,
                                   "d2h_wait", n):
                    # copies out of the donated slot (required: the slot
                    # is re-donated for a later batch while the writer
                    # thread still holds this parity); blocks until
                    # compute done
                    parity32 = np.array(out.payload)
                    raw = np.asarray(crc_dev) if fused else None
                lat = time.perf_counter() - t_disp
                kernel_lats.append(lat)
                profiling.record_device_batch(lat, units=len(batch),
                                              k=k_rows, devices=n_dev)
            with tracing.stage("ec.encode.crc", add_time, "crc_host", n):
                if out is not None:
                    pool.note_d2h(parity32.nbytes, device=lane.label)
                    lane.out_ring.put(out)
                    parity = parity32.view(np.uint8).reshape(
                        PARITY_SHARDS, b, chunk)
                    if fused:
                        pool.note_d2h(raw.nbytes, device=lane.label)
                        fin = finalize(raw, chunk)  # (k_rows + 4, b)
                if fused:
                    # the device already CRC'd every row (padding rows
                    # were zeroed in staging, so their image equals the
                    # cached zeros CRC) — only the O(1)-per-chunk
                    # combines remain
                    for k, u in enumerate(batch):
                        w = writers[u.vol]
                        r = u.real_rows
                        for i in range(DATA_SHARDS):
                            c = int(fin[i, k]) if i < k_rows else zcrc
                            w.crcs[i] = crc_host.crc32c_combine(
                                w.crcs[i], c, chunk)
                        for j in range(PARITY_SHARDS):
                            c = int(fin[k_rows + j, k]) if r else zcrc
                            w.crcs[DATA_SHARDS + j] = \
                                crc_host.crc32c_combine(
                                    w.crcs[DATA_SHARDS + j], c, chunk)
                else:
                    t_crc = time.perf_counter()
                    for k, u in enumerate(batch):
                        w = writers[u.vol]
                        r = u.real_rows
                        for i in range(DATA_SHARDS):
                            c = crc_host.crc32c(buf[i, k]) if i < r \
                                else zcrc
                            w.crcs[i] = crc_host.crc32c_combine(
                                w.crcs[i], c, chunk)
                        for j in range(PARITY_SHARDS):
                            c = crc_host.crc32c(parity[j, k]) if r \
                                else zcrc
                            w.crcs[DATA_SHARDS + j] = \
                                crc_host.crc32c_combine(
                                    w.crcs[DATA_SHARDS + j], c, chunk)
                    # distinct timer: this key's absence from the stage
                    # stats is the proof the fused path took host CRC
                    # off the critical path
                    add_time("host_crc", time.perf_counter() - t_crc)
            add_time("encode_crc", time.perf_counter() - t0)
            io.free_slots.put(slot)
            if parity is not None:
                # (4, B, L) -> writer's [k][i] indexing as a free view
                io.writes.put((parity.transpose(1, 0, 2), batch))
        else:
            parity_dev, crc_dev = out
            with tracing.stage("ec.encode.d2h_wait", add_time, "d2h_wait",
                               n):
                # blocks until compute done; file writes need a
                # contiguous buffer
                parity = np.ascontiguousarray(np.asarray(parity_dev))
                raw = np.asarray(crc_dev)
            lat = time.perf_counter() - t_disp
            kernel_lats.append(lat)
            profiling.record_device_batch(lat, units=len(batch), k=k_rows,
                                          devices=n_dev)
            with tracing.stage("ec.encode.crc", add_time, "crc_host", n):
                pool.note_d2h(parity.nbytes + raw.nbytes, device=lane.label)
                if use_words:  # packed int32 parity words -> bytes
                    parity = parity.view(np.uint8).reshape(
                        parity.shape[0], PARITY_SHARDS, chunk)
                crcs = finalize(raw, chunk)
                io.free_slots.put(slot)  # device consumed the transfer
                for k, u in enumerate(batch):
                    w = writers[u.vol]
                    for s in range(TOTAL_SHARDS):
                        w.crcs[s] = crc_host.crc32c_combine(
                            w.crcs[s], int(crcs[k, s]), chunk)
            add_time("encode_crc", time.perf_counter() - t0)
            io.writes.put((parity, batch))

    def _completion():
        tracing.swap(root)
        try:
            n = 0
            while True:
                item = io.get(done_q)
                if item is None:
                    return
                _complete(n, *item)
                n += 1
        except BaseException as e:
            io.errors.append(e)
            io.stop.set()
        finally:
            tracing.restore(None)

    ct = threading.Thread(target=_completion, daemon=True)
    io.start()
    ct.start()
    try:
        n = -1
        while not io.stop.is_set():
            item = io.get(io.ready)
            if item is None:
                break
            n += 1
            slot, batch, k_max = item
            buf = slot.payload
            lane = lanes[n % n_dev]     # whole batches, dealt in turn
            lane.batches += 1
            # background device lane: bulk encode yields to in-flight
            # foreground (degraded-read recover) decodes per batch
            lane_wait = _lanes.LANES.background_checkpoint()
            if lane_wait:
                add_time("lane_wait", lane_wait)
            t0 = time.perf_counter()
            crc_dev = None
            with tracing.stage("ec.encode.dispatch", add_time, "dispatch",
                               n, buf.nbytes):
                if pooled:
                    out = None
                    if k_max > 0:
                        k_shapes.add(k_max)
                        words = buf.view(np.int32)[:k_max]
                        with tracing.stage("ec.encode.h2d", add_time,
                                           "h2d", n, words.nbytes):
                            if zero_copy:
                                din = jax.dlpack.from_dlpack(words)
                            else:
                                # one contiguous array onto one device
                                din = jax.device_put(words, lane.device)
                                pool.note_h2d(words.nbytes,
                                              device=lane.label)
                        # backpressure: the lane's ring is empty until
                        # the completion thread has drained a batch of it
                        out = io.get(lane.out_ring)
                        if out is None:
                            break
                        # donation swap: the step aliases its result into
                        # the slot's buffer; the old handle is dead
                        if fused:
                            out.payload, crc_dev = lane.step(din,
                                                             out.payload)
                        else:
                            out.payload = lane.step(din, out.payload)
                else:
                    with tracing.stage("ec.encode.h2d", add_time, "h2d",
                                       n, buf.nbytes):
                        # packed int32 words for the words step
                        din = jax.device_put(
                            buf.view(np.int32) if use_words else buf,
                            lane.device)
                        pool.note_h2d(buf.nbytes, device=lane.label)
                    out = lane.step(din)
            if not io.put(done_q,
                          (lane, slot, batch, out, crc_dev, t0, k_max)):
                break
        io.put(done_q, None)
        ct.join(timeout=600)
    except BaseException:
        io.stop.set()
        raise
    finally:
        if ct.is_alive():
            io.stop.set()
            ct.join(timeout=30)
        io.finish()
        for ls in out_leases:
            pool.release(ls)
    result = io.result()

    wall = time.perf_counter() - wall0
    if stage_stats is not None:
        stage_stats.update({k: round(v, 3) for k, v in timers.items()})
        stage_stats["wall"] = round(wall, 3)
        stage_stats["backend"] = backend
        stage_stats["batches"] = io.n_batches
        # one round of the deal: `devices` executions of b units each
        stage_stats["batch_units"] = b * n_dev
        stage_stats["device_batches"] = [lane.batches for lane in lanes]
        stage_stats["k_shapes"] = sorted(k_shapes)
        stage_stats["inflight"] = depth
        stage_stats["staging_slots"] = n_slots
        stage_stats["read_workers"] = io.read_workers
        stage_stats["zero_copy_h2d"] = zero_copy
        stage_stats["devices"] = n_dev
        stage_stats["platform"] = platform
        stage_stats["device_kind"] = dev0.device_kind
        stage_stats["crc_path"] = "host" if host_crc else "fused-device"
        for k in ("read", "dispatch", "encode_crc", "write"):
            stage_stats[f"{k}_frac"] = (
                round(timers[k] / wall, 3) if wall > 0 else 0.0)
        if kernel_lats:
            lats = sorted(kernel_lats)
            stage_stats["kernel"] = {
                "batches": len(lats),
                "dispatch_ready_p50_ms": round(
                    lats[len(lats) // 2] * 1e3, 3),
                "dispatch_ready_p95_ms": round(
                    lats[min(len(lats) - 1,
                             int(len(lats) * 0.95))] * 1e3, 3),
                "dispatch_ready_max_ms": round(lats[-1] * 1e3, 3),
            }
        stage_stats["pool"] = pool.snapshot()
    _publish_stage_seconds(timers, wall, root)
    return result


def _publish_stage_seconds(timers: dict, wall: float, root):
    """The end of either pipeline: the stage seconds that stage() added
    up become the `ec_encode_stage_seconds` gauge (the last seal's, with
    its wall) and one child span each of the seal's root, so a trace of
    the seal reads the same whichever pipeline ran it."""
    from ..stats import metrics as stats

    for k, v in timers.items():
        stats.EcEncodeStageSeconds.labels(k).set(round(v, 3))
    stats.EcEncodeStageSeconds.labels("wall").set(round(wall, 3))
    if root is not None:
        for k, v in timers.items():
            tracing.record_span("ec.encode." + k, v, parent=root,
                                tags={"total": 1})


# Host-pipeline work sizing: a span batches consecutive equal-block rows
# into one contiguous .dat read (the striped rows of ec_encoder.go:57-59
# are adjacent on disk, so R rows = ONE preadv of R*10*block bytes, and
# each shard's R blocks land adjacently in its file = ONE pwritev).
# 30 MB spans measured best: large enough to amortize syscalls, small
# enough that the span is still cache-warm when the fused kernel walks
# it (64 MB spans cost ~20% — the early rows evict before compute).
_HOST_SPAN_BYTES = 30 << 20    # target bytes of .dat per work item
_HOST_SPAN_MAX_BLOCK = 8 << 20  # rows above this get column-chunked
_HOST_COL_CHUNK = 4 << 20       # column width for large-block rows


@dataclass
class _HostWork:
    """One host-pipeline work item: either a contiguous span of `rows`
    equal-size striped rows ((rows, 10, length) straight out of the
    .dat), or one column chunk of a large row (10 strided preads)."""
    vol: int
    kind: str        # "span" | "col"
    dat_off: int     # span: contiguous byte start; col: row start
    shard_off: int
    length: int      # per-shard width L of one row (span) / chunk (col)
    rows: int        # span: R; col: 1
    block_size: int  # col: the row's block size (pread stride)
    col: int = 0     # col: byte offset of the chunk within the block


def _host_work_items(plans) -> list[_HostWork]:
    items: list[_HostWork] = []
    for vi, plan in enumerate(plans):
        pending: Optional[_HostWork] = None
        for row_start, shard_off, block in plan.rows:
            if block <= _HOST_SPAN_MAX_BLOCK:
                # IOV_MAX caps a pwritev at 1024 iovecs (one per row)
                rmax = max(1, min(
                    1024, _HOST_SPAN_BYTES // (DATA_SHARDS * block)))
                if (pending is not None
                        and pending.block_size == block
                        and pending.rows < rmax):
                    pending.rows += 1
                    continue
                if pending is not None:
                    items.append(pending)
                pending = _HostWork(vi, "span", row_start, shard_off,
                                    block, 1, block)
            else:
                if pending is not None:
                    items.append(pending)
                    pending = None
                for col in range(0, block, _HOST_COL_CHUNK):
                    width = min(_HOST_COL_CHUNK, block - col)
                    items.append(_HostWork(vi, "col", row_start,
                                           shard_off + col, width, 1,
                                           block, col))
        if pending is not None:
            items.append(pending)
    return items


def _encode_units_host(plans, units, chunk, host_codec,
                       stage_stats=None) -> dict[str, list[int]]:
    """The host encode path as a true three-stage pipeline.  Work items
    (multi-row spans / column chunks) flow

      read    — a reader thread fills staging slots with contiguous
                preadv()s of the .dat;
      encode  — a pool of compute workers (WEED_EC_HOST_WORKERS, default
                one per *available* core, each releasing the GIL inside
                the fused native parity+CRC kernel) encodes into pooled
                parity slots;
      write   — a dedicated writer pool (`_WriteStage`) drains a bounded
                hand-off queue, coalescing adjacent spans into one
                pwritev per shard file and pacing dirty-page writeback
                (WritebackPacer) so scale runs don't stall on a full
                dirty-page budget.

    Compute workers hand (data, parity, crcs) to the writer stage and
    immediately pull the next item instead of blocking on 14 synchronous
    pwritev calls — at 300-volume scale the write stage was 93.5% of
    wall time while the codec sat idle.  Parity slots are pooled rather
    than thread-local because with write-behind a slot outlives its
    compute call until the writer stage releases it (each worker
    effectively double-buffers).

    On a single-core host everything runs inline in the calling thread —
    profiling showed reader/worker threads on one core cost ~3x in GIL
    convoying around every ctypes/syscall boundary — byte- and
    CRC-identical either way.

    stage_stats (optional dict) gets per-stage busy seconds + fractions
    (read / encode_crc / write / flush): the pipeline's own answer to
    "which stage is the bottleneck"."""
    import time as _t
    from concurrent.futures import ThreadPoolExecutor

    from ..ops import codec as codec_mod
    from ..ops import crc32c as crc_host
    from ..storage.erasure_coding import to_ext

    enc = host_codec if hasattr(host_codec, "_apply") \
        else codec_mod.new_host_encoder(DATA_SHARDS, PARITY_SHARDS)
    parity_matrix = np.ascontiguousarray(
        np.asarray(enc.matrix[DATA_SHARDS:], dtype=np.uint8))
    fused = hasattr(enc, "encode_rows")

    nworkers = int(os.environ.get("WEED_EC_HOST_WORKERS", "0") or 0)
    if nworkers <= 0:
        from ..util.platform import available_cpu_count

        # affinity-aware: an affinity-restricted box must not over-spawn
        # workers onto cores it cannot use
        nworkers = max(1, min(16, available_cpu_count()))

    write_behind = nworkers > 1
    nwriters = max(1, min(4, nworkers // 2)) if write_behind else 0

    items = _host_work_items(plans)
    slot_bytes = max(i.rows * DATA_SHARDS * i.length for i in items)
    parity_bytes = max(i.rows * PARITY_SHARDS * i.length for i in items)
    # pooled parity slots (not thread-local: see docstring); sized so
    # compute never starves while the writer pool holds slots in flight
    n_pslots = 1 if nworkers == 1 else nworkers + 2 * nwriters + 2
    parity_free: "queue.Queue[np.ndarray]" = queue.Queue()
    for _ in range(n_pslots):
        parity_free.put(np.empty(parity_bytes, dtype=np.uint8))

    stop = threading.Event()
    errors: list[BaseException] = []
    pacer = WritebackPacer()
    dat_fds = [os.open(p.base + ".dat", os.O_RDONLY) for p in plans]
    vols = {vi: _ShardFileSet(
                p.base, to_ext,
                (p.rows[-1][1] + p.rows[-1][2]) if p.rows else 0,
                pacer)
            for vi, p in enumerate(plans)}
    timers = {"read": 0.0, "encode_crc": 0.0, "write": 0.0, "flush": 0.0}
    tlock = threading.Lock()
    root = tracing.current()  # the seal's span, for the worker threads

    def add_time(key: str, seconds: float):
        with tlock:
            timers[key] += seconds

    def read_item(w: _HostWork, flat: np.ndarray) -> np.ndarray:
        """Fill (and return) the item's (rows, 10, length) view of the
        flat slot buffer, zero-padding past the .dat's EOF."""
        dat_size = plans[w.vol].dat_size
        fd = dat_fds[w.vol]
        nbytes = w.rows * DATA_SHARDS * w.length
        view = flat[:nbytes].reshape(w.rows, DATA_SHARDS, w.length)
        if w.kind == "span":
            span = view.reshape(-1)
            want = min(nbytes, max(0, dat_size - w.dat_off))
            got = 0
            while got < want:
                n = os.preadv(fd, [span[got:want]], w.dat_off + got)
                if n == 0:
                    break
                got += n
            if got < nbytes:
                span[got:] = 0
        else:
            row = view[0]
            for i in range(DATA_SHARDS):
                # shard i's chunk inside the large striped row
                start = w.dat_off + i * w.block_size + w.col
                want = min(w.length, max(0, dat_size - start))
                got = 0
                while got < want:
                    n = os.preadv(fd, [row[i, got:want]], start + got)
                    if n == 0:
                        break
                    got += n
                if got < w.length:
                    row[i, got:] = 0
        return view

    def encode_item(w: _HostWork, data: np.ndarray):
        """Encode stage: parity+CRC into a pooled parity slot.  The slot
        travels with the item to the write stage, which releases it."""
        prev = tracing.swap(root)  # a pool thread: hand it the seal's span
        try:
            with tracing.stage("ec.encode.crc", add_time, "encode_crc",
                               w.rows, data.nbytes):
                pbuf = _get(stop, parity_free)
                if pbuf is None:    # an error elsewhere must not wedge us
                    raise RuntimeError("encode pipeline stopped")
                need = w.rows * PARITY_SHARDS * w.length
                parity = pbuf[:need].reshape(w.rows, PARITY_SHARDS,
                                             w.length)
                if fused:
                    crcs = enc.encode_rows(parity_matrix, data, parity)
                else:
                    crcs = [0] * TOTAL_SHARDS
                    for r in range(w.rows):
                        parity[r] = enc._apply(parity_matrix, data[r])
                        for i in range(DATA_SHARDS):
                            crcs[i] = crc_host.crc32c(data[r, i], crcs[i])
                        for i in range(PARITY_SHARDS):
                            crcs[DATA_SHARDS + i] = crc_host.crc32c(
                                parity[r, i], crcs[DATA_SHARDS + i])
        finally:
            tracing.restore(prev)
        return pbuf, parity, crcs

    def write_spans(group):
        """The data+parity shard spans of adjacent items (w, data,
        parity): ONE pwritev per shard file."""
        v = vols[group[0][0].vol]
        for s in range(TOTAL_SHARDS):
            j = s if s < DATA_SHARDS else s - DATA_SHARDS
            v.write(s, [(data if s < DATA_SHARDS else parity)[r, j]
                        for (w, data, parity) in group
                        for r in range(w.rows)],
                    group[0][0].shard_off)

    def combine(w: _HostWork, crcs: list[int]):
        v = vols[w.vol]
        for s in range(TOTAL_SHARDS):
            v.crcs[s] = crc_host.crc32c_combine(
                v.crcs[s], crcs[s], w.rows * w.length)

    wall0 = _t.perf_counter()
    try:
        if nworkers == 1:
            flat = np.empty(slot_bytes, dtype=np.uint8)
            for w in items:
                with tracing.stage("ec.encode.read", add_time, "read",
                                   w.rows):
                    data = read_item(w, flat)
                pbuf, parity, crcs = encode_item(w, data)
                with tracing.stage("ec.encode.write", add_time, "write",
                                   w.rows, data.nbytes + parity.nbytes):
                    write_spans([(w, data, parity)])
                parity_free.put(pbuf)
                combine(w, crcs)
        else:
            n_slots = max(_SLOTS, nworkers + 2 * nwriters + 2)
            free_slots: "queue.Queue[np.ndarray]" = queue.Queue()
            for _ in range(n_slots):
                free_slots.put(np.empty(slot_bytes, dtype=np.uint8))
            ready: "queue.Queue" = queue.Queue(maxsize=n_slots)

            def reader():
                tracing.swap(root)
                try:
                    for w in items:
                        flat = _get(stop, free_slots)
                        if flat is None:
                            return
                        with tracing.stage("ec.encode.read", add_time,
                                           "read", w.rows):
                            data = read_item(w, flat)
                        if not _put(stop, ready, (flat, data, w)):
                            return
                    _put(stop, ready, None)
                except BaseException as e:
                    errors.append(e)
                    stop.set()
                finally:
                    tracing.restore(None)

            # the write stage's client.  Items arrive in stripe order
            # (the main loop combines and enqueues in submission order),
            # so a writer takes the adjacent spans queued behind its
            # item with it and writes them as one group
            _GROUP_MAX = 8  # spans per coalesced group

            def write_item(item):
                group = [item]
                rows = item[0].rows

                def joins(nxt) -> bool:
                    lw, nw = group[-1][0], nxt[0]
                    return (nw.vol == lw.vol
                            and nw.shard_off
                            == lw.shard_off + lw.rows * lw.length
                            and rows + nw.rows <= _IOV_MAX)

                while len(group) < _GROUP_MAX:
                    nxt = writes.take_if(joins)
                    if nxt is None:
                        break
                    group.append(nxt)
                    rows += nxt[0].rows
                write_spans([(w, data, parity)
                             for (w, _flat, data, parity, _pbuf) in group])
                for (_w, flat, _data, _parity, pbuf) in group:
                    free_slots.put(flat)
                    parity_free.put(pbuf)

            writes = _WriteStage("ec.encode", write_item, add_time, errors,
                                 stop, depth=2 * nwriters + 2,
                                 writers=nwriters)
            rt = threading.Thread(target=reader, daemon=True)
            rt.start()
            writes.start()
            pool = ThreadPoolExecutor(max_workers=nworkers)
            # keep up to nworkers+1 items in flight; combine in order
            # (per-file CRCs chain in stripe order, and in-order hand-off
            # is what lets the writer pool coalesce adjacent spans)
            pending: list = []
            try:
                done = False
                while not done and not stop.is_set():
                    item = _get(stop, ready)
                    if item is None:    # the end, or stopped
                        done = True
                    else:
                        flat, data, w = item
                        pending.append(
                            (w, flat, data,
                             pool.submit(encode_item, w, data)))
                    while pending and (len(pending) > nworkers or done):
                        w, flat, data, fut = pending.pop(0)
                        pbuf, parity, crcs = fut.result()
                        combine(w, crcs)
                        if not writes.put((w, flat, data, parity, pbuf)):
                            break
                writes.close()
                if errors:
                    raise errors[0]
            except BaseException:
                stop.set()
                if errors:  # the root cause, not a secondary unwind
                    raise errors[0] from None
                raise
            finally:
                stop.set()
                pool.shutdown(wait=True)
                rt.join(timeout=30)
                writes.close()
    finally:
        for fd in dat_fds:
            os.close(fd)
        for v in vols.values():
            v.close()

    wall = _t.perf_counter() - wall0
    # the pacer flushes inside timed write sections: attribute its time
    # to the flush stage, not double-counted under write
    timers["flush"] = pacer.flush_seconds
    timers["write"] = max(0.0, timers["write"] - pacer.flush_seconds)
    if stage_stats is not None:
        stage_stats.update({k: round(v, 3) for k, v in timers.items()})
        stage_stats["wall"] = round(wall, 3)
        stage_stats["backend"] = "host-pipeline"
        stage_stats["workers"] = nworkers
        stage_stats["writers"] = nwriters
        stage_stats["write_behind"] = write_behind
        stage_stats["fused"] = fused
        stage_stats["items"] = len(items)
        stage_stats["flushes"] = pacer.flushes
        for k in ("read", "encode_crc", "write", "flush"):
            stage_stats[f"{k}_frac"] = (
                round(timers[k] / wall, 3) if wall > 0 else 0.0)
    from ..stats import metrics as stats
    stats.EcEncodeBytesCounter.inc(sum(p.dat_size for p in plans))
    if pacer.flushes:
        stats.EcWritebackFlushCounter.inc(pacer.flushes)
    _publish_stage_seconds(timers, wall, root)
    return {p.base: vols[vi].crcs for vi, p in enumerate(plans)}


def rebuild_matrix(present: list[int], missing: list[int],
                   data_shards: int = DATA_SHARDS,
                   total_shards: int = TOTAL_SHARDS):
    """(survivor_ids, M) with M (len(missing) x data_shards) mapping the
    chosen survivors directly to the missing shards: data rows come from
    the inverted survivor submatrix, parity rows from encode-rows times
    that inverse (the one-matmul form of klauspost Reconstruct).  Row
    construction lives in ops.rs_numpy.decode_rows — the same cached
    decode plans the degraded-read path uses — so a rebuild right after
    an incident's reads pays zero extra inversions."""
    from ..ops.rs_numpy import decode_rows

    chosen = present[:data_shards]
    rows = decode_rows(data_shards, total_shards, chosen, tuple(missing))
    return chosen, np.array(rows, dtype=np.uint8, copy=True)


# the keys of a rebuild's stage seconds in `stage_stats`: one per
# `ec.rebuild.<key>` stage, but for the read stage's wait for a slot
# (`ec.rebuild.stage_wait` -> read_slot_wait) and its workers'
# thread-seconds (read_worker_busy: no span)
_REBUILD_STAGES = ("read", "read_worker_busy", "read_slot_wait",
                   "read_wait", "dispatch", "h2d", "d2h_wait", "crc",
                   "write_wait", "write")
_REBUILD_SLOTS = 3  # staging slots: one being filled, two in flight


def rebuild_shards(base: str, mesh=None,
                   batch_units: Optional[int] = None,
                   stage_stats: Optional[dict] = None) -> dict[int, int]:
    """Regenerate every missing .ecNN from survivors through the batched
    device pipeline (RebuildEcFiles, ec_encoder.go:233-287 — the
    reference loops 1 MB buffers through its CPU codec; here survivor
    chunks batch into (B, 10, L) device dispatches with fused CRC32C of
    the rebuilt shards).  Returns {shard_id: crc32c of the rebuilt file}.

    Three stages, each ahead of the next:

      read stage      — the encode pipeline's (`_ReadStage`: a coordinator
                        and a few I/O workers) fills pooled staging slots
                        from the ten survivor files, a batch at a time
                        and in batch order; a survivor that ends before
                        its planned bytes fails the rebuild;
      pipeline thread — (this one) uploads batch n and calls the step,
                        then drains batch n-1: waits for its step and
                        the copy back, gives the batch's slot back to
                        the read stage, chains the rebuilt files'
                        CRC32Cs and hands the rows to
      writer thread   — which pwritev()s them, paced.

    A rebuild that fails removes the shard files it created.

    stage_stats: optional dict filled with where the rebuild ran
    (backend, devices, platform, device_kind), its wall seconds, batch
    count and transfer bytes, and the seconds of each stage.  On the
    pipeline thread, disjoint: `read_wait` (blocked on a filled slot:
    the read stage sets the pace), `dispatch` (upload + step call) with
    `h2d` inside it, `d2h_wait` (step + copy back), `crc` and
    `write_wait` (blocked on the write stage, `_WriteStage`: its full
    queue, and the join at the end).  Overlapping them: the read stage's `read`,
    `read_slot_wait` and `read_worker_busy` over its `read_workers`
    workers (see `_ReadStage`), and on the writer thread `write`.
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..ops import crc32c as crc_host
    from ..ops.crc_device import finalize
    from ..ops.device_pool import get_pool
    from ..storage.erasure_coding import to_ext
    from .mesh import make_ec_mesh, make_sharded_apply

    present = [i for i in range(TOTAL_SHARDS)
               if os.path.exists(base + to_ext(i))]
    missing = [i for i in range(TOTAL_SHARDS) if i not in present]
    if not missing:
        return {}
    if len(present) < DATA_SHARDS:
        raise ValueError(
            f"too few shards to rebuild: {len(present)} < {DATA_SHARDS}")
    chosen, matrix = rebuild_matrix(present, missing)
    sizes = {os.path.getsize(base + to_ext(i)) for i in chosen}
    if len(sizes) != 1:
        raise ValueError(f"survivor shard sizes differ: {sorted(sizes)}")
    shard_size = sizes.pop()
    if shard_size == 0:
        for sid in missing:
            open(base + to_ext(sid), "wb").close()
        return {sid: 0 for sid in missing}

    chunk = min(MAX_CHUNK_BYTES, shard_size)
    offsets = list(range(0, shard_size, chunk))

    wall0 = time.perf_counter()
    if mesh is None:
        mesh = make_ec_mesh()
    n_data, n_block = mesh.devices.shape
    if chunk % n_block:
        mesh = Mesh(mesh.devices.reshape(-1, 1), mesh.axis_names)
        n_data, n_block = mesh.devices.shape
    if batch_units is None:
        batch_units = max(1, TARGET_BATCH_BYTES // (DATA_SHARDS * chunk))
    b = min(batch_units, len(offsets))
    b = max(n_data, ((b + n_data - 1) // n_data) * n_data)
    n_batches = -(-len(offsets) // b)

    step = make_sharded_apply(mesh, matrix)
    sharding = NamedSharding(mesh, P("data", None, "block"))
    pool = get_pool()
    dev_label = (str(mesh.devices.flat[0]) if mesh.devices.size == 1
                 else f"sharded:{mesh.devices.size}")
    # pooled staging buffers: a buffer is refilled only after its batch
    # drained (which implies the host->device transfer completed);
    # leased from the slab pool so consecutive rebuilds with the same
    # geometry reuse them instead of reallocating.  The lease carries
    # the mesh's placement label: a rebuild against one device set must
    # never be handed a slab staged for a different one.
    skey = ("rebuild-stage", (b, DATA_SHARDS, chunk))
    slots = [pool.lease(skey,
                        lambda: np.zeros((b, DATA_SHARDS, chunk),
                                         dtype=np.uint8),
                        b * DATA_SHARDS * chunk, device=dev_label)
             for _ in range(_REBUILD_SLOTS)]

    timers = dict.fromkeys(_REBUILD_STAGES, 0.0)
    tlock = threading.Lock()

    def add_time(key: str, seconds: float):
        """The stage accumulator handed to tracing.stage()."""
        with tlock:
            timers[key] += seconds

    # raw fds: the read stage's workers read positionally, sharing no
    # file offset
    in_fds: list[int] = []

    def survivor_batches():
        for start in range(0, len(offsets), b):
            batch_offs = offsets[start:start + b]
            yield (deque((k, i, off) for k, off in enumerate(batch_offs)
                         for i in range(DATA_SHARDS)),
                   None, (batch_offs,))

    def read_row(buf: np.ndarray, step, split: dict):
        """A worker's step: chunk `k` of survivor `i` into its row of
        the staging slot, zero past a short last chunk."""
        k, i, off = step
        width = min(chunk, shard_size - off)
        row = buf[k, i]
        _preadv_full(in_fds[i], memoryview(row)[:width], off)
        if width < chunk:
            row[width:] = 0

    def write_rows(item):
        """The write stage's client: a drained batch's rebuilt rows on
        to the missing shards' files."""
        batch_offs, out = item
        for k, off in enumerate(batch_offs):
            width = min(chunk, shard_size - off)
            for j, sid in enumerate(missing):
                files.write(sid, [out[k, j, :width]], off)

    reads = _ReadStage("ec.rebuild", survivor_batches(), read_row, slots,
                       add_time)
    files = writes = None
    done = False
    try:
        for i in chosen:
            in_fds.append(os.open(base + to_ext(i), os.O_RDONLY))
        reads.start()
        # write-behind: rebuilt batches are handed to a writer thread so
        # the next device dispatch isn't serialized behind checked
        # pwritevs; the pacer keeps large rebuilds from stalling on
        # dirty-page writeback.  One `errors` and one `stop` with the
        # read stage: a failed write ends the readers too
        files = _ShardFileSet(base, to_ext, shard_size, WritebackPacer(),
                              shards=missing)
        writes = _WriteStage("ec.rebuild", write_rows, add_time,
                             reads.errors, reads.stop, depth=2)
        writes.start()
        inflight: list = []

        def drain_one():
            n, slot, batch_offs, out_dev, crc_dev = inflight.pop(0)
            with tracing.stage("ec.rebuild.d2h_wait", add_time, "d2h_wait",
                               n):
                # blocks until the step is done, then copies back
                out = np.ascontiguousarray(np.asarray(out_dev))
                raw = np.asarray(crc_dev)
            reads.free_slots.put(slot)  # the step read it: refill it
            pool.note_d2h(out.nbytes, device=dev_label)
            with tracing.stage("ec.rebuild.crc", add_time, "crc", n):
                for k, off in enumerate(batch_offs):
                    width = min(chunk, shard_size - off)
                    fin = finalize(raw[k], chunk)
                    for j, sid in enumerate(missing):
                        # chunks are full except possibly the last; a
                        # short final chunk was zero-padded on device,
                        # and CRCs of zero-extended data un-extend via
                        # combine algebra
                        chunk_crc = int(fin[j]) if width == chunk else \
                            crc_host.crc32c(out[k, j, :width].tobytes())
                        files.crcs[sid] = crc_host.crc32c_combine(
                            files.crcs[sid], chunk_crc, width)
            with tracing.stage("ec.rebuild.write_wait", add_time,
                               "write_wait", n, out.nbytes):
                # `out` is fresh per drain: safe to hand off
                if not writes.put((batch_offs, out), out.nbytes):
                    raise reads.errors[0]

        for n in range(n_batches):
            with tracing.stage("ec.rebuild.read_wait", add_time,
                               "read_wait", n):
                item = reads.get(reads.ready)
            if item is None:    # stopped: by what is in `errors`
                raise reads.errors[0]
            slot, batch_offs = item
            buf = slot.payload
            with tracing.stage("ec.rebuild.dispatch", add_time, "dispatch",
                               n, buf.nbytes):
                with tracing.stage("ec.rebuild.h2d", add_time, "h2d",
                                   n, buf.nbytes):
                    dev = jax.device_put(buf, sharding)
                pool.note_h2d(buf.nbytes, device=dev_label)
                out_dev, crc_dev = step(dev)
            inflight.append((n, slot, batch_offs, out_dev, crc_dev))
            if len(inflight) >= 2:
                drain_one()
        while inflight:
            drain_one()
        done = True
    finally:
        if not done:
            reads.stop.set()    # the writer too: nothing left to write
        if writes is not None:
            with tracing.stage("ec.rebuild.write_wait", add_time,
                               "write_wait"):
                writes.close()
        reads.close()   # before its slots and files go
        for sl in slots:
            pool.release(sl)
        for fd in in_fds:
            os.close(fd)
        if files is not None:
            files.close()
        if not done or reads.errors:
            for sid in missing:     # they were missing: leave them so
                try:
                    os.unlink(base + to_ext(sid))
                except FileNotFoundError:
                    pass
    if reads.errors:
        raise reads.errors[0]
    if stage_stats is not None:
        dev0 = mesh.devices.flat[0]
        stage_stats.update({
            "backend": "device-apply-xla",
            "devices": mesh.devices.size,
            "device_shard": dev_label,
            "platform": dev0.platform,
            "device_kind": dev0.device_kind,
            "wall": round(time.perf_counter() - wall0, 6),
            **{k: round(v, 6) for k, v in timers.items()},
            "read_workers": reads.read_workers,
            "batches": n_batches,
            "batch_units": b,
            "missing": list(missing),
            "h2d_bytes": n_batches * b * DATA_SHARDS * chunk,
            "d2h_bytes": n_batches * b * len(missing) * chunk,
        })
    return {sid: files.crcs[sid] for sid in missing}
