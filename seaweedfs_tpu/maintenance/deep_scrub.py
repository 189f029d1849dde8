"""Device-batched deep scrub: re-encode and compare, not just re-hash.

The plain scrub (`/admin/ec/scrub`, storage.tools.verify_shard_files)
only re-hashes each local .ecNN file against the CRC the encode
pipeline recorded — it catches bitrot inside a file but cannot tell
whether the *parity still matches the data* (a stale or cross-wired
sidecar passes).  Deep scrub goes further:

 * every present shard file is streamed span-by-span (paced through
   the curator's BytePacer) and its rolling CRC32C is chained exactly
   like `shard_file_crc32c` — the basic bitrot check rides along for
   free on the same reads;
 * the ten data-shard spans are packed into `(10, B, W)` int32 batches
   — spans from *different volumes* share one compiled geometry — and
   pushed through the persistent `make_parity_step` SWAR kernel with
   the same DevicePool donated-output ring the encode path uses; the
   recomputed parity's chained CRCs are compared against the stored
   parity CRCs, proving data and parity agree end to end;
 * the host fallback (`deep_scrub_host`) walks the sorted .ecx and
   re-reads every live needle, verifying each needle's own CRC — the
   needle-level integrity walk for hosts without a device mesh.

Batching across volumes matters: scrub spans are small and plentiful,
and one fixed (k=10, B, W) shape means the kernel compiles once for
the whole sweep no matter how many volumes it covers."""

from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

from ..ops import crc32c as crc_host
from ..qos import lanes as _lanes
from ..storage.erasure_coding import (DATA_SHARDS_COUNT,
                                      PARITY_SHARDS_COUNT,
                                      TOTAL_SHARDS_COUNT, to_ext)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def span_bytes_default() -> int:
    """WEED_MAINT_SPAN_KB: deep-scrub span (device chunk) size."""
    return max(4096, _env_int("WEED_MAINT_SPAN_KB", 1024) << 10)


def _inflight() -> int:
    return max(1, _env_int("WEED_EC_DEVICE_INFLIGHT", 3))


@dataclass
class ScrubTarget:
    """One EC volume to deep-scrub.  `reader(shard, offset, size)`
    returns up to `size` bytes of that shard (local file or a peer's
    /admin/ec/shard_read) — short returns mean EOF, exceptions mean
    the shard is unreachable."""

    volume: int
    collection: str
    stored: list            # 14 recorded CRC32Cs from the .vif
    sizes: list             # per-shard byte length; -1 when absent
    reader: Callable[[int, int, int], bytes]
    close: Optional[Callable[[], None]] = None
    # runtime state
    chains: list = field(default_factory=list)
    computed: list = field(default_factory=list)
    recompute: bool = True
    unreadable: set = field(default_factory=set)
    bytes_read: int = 0

    def __post_init__(self):
        self.chains = [0] * TOTAL_SHARDS_COUNT
        self.computed = [0] * PARITY_SHARDS_COUNT
        # recompute needs every data shard; file-CRC still covers the rest
        self.recompute = all(
            self.sizes[i] >= 0 for i in range(DATA_SHARDS_COUNT))

    @property
    def shard_len(self) -> int:
        return max([s for s in self.sizes if s >= 0] or [0])


def local_target(base: str, volume: int = 0,
                 collection: str = "") -> ScrubTarget:
    """Build a ScrubTarget over local .ecNN files (the offline path:
    a scrub of shard files with no cluster around them)."""
    from ..storage.erasure_coding.encoder import load_volume_info

    info = load_volume_info(base) or {}
    stored = info.get("shard_crc32c")
    if not isinstance(stored, list) or len(stored) != TOTAL_SHARDS_COUNT:
        raise ValueError(f"{base}.vif has no shard_crc32c record")
    sizes = []
    for sid in range(TOTAL_SHARDS_COUNT):
        path = base + to_ext(sid)
        sizes.append(os.path.getsize(path)
                     if os.path.exists(path) else -1)
    fds: dict[int, int] = {}

    def reader(sid: int, offset: int, size: int) -> bytes:
        fd = fds.get(sid)
        if fd is None:
            fd = fds[sid] = os.open(base + to_ext(sid), os.O_RDONLY)
        return os.pread(fd, size, offset)

    def close():
        for fd in fds.values():
            os.close(fd)
        fds.clear()

    return ScrubTarget(volume=volume, collection=collection,
                       stored=list(stored), sizes=sizes,
                       reader=reader, close=close)


def _read_span(t: ScrubTarget, sid: int, off: int, chunk: int,
               throttle) -> bytes:
    """One paced span read, chained into the shard's rolling file CRC."""
    want = min(chunk, max(0, t.sizes[sid] - off))
    if want <= 0:
        return b""
    try:
        raw = t.reader(sid, off, want)
    except Exception:
        t.unreadable.add(sid)
        if sid < DATA_SHARDS_COUNT:
            t.recompute = False
        return b""
    if raw:
        if throttle is not None:
            throttle(len(raw))
        t.chains[sid] = crc_host.crc32c(raw, t.chains[sid])
        t.bytes_read += len(raw)
    return raw


def _verdict(t: ScrubTarget) -> dict:
    missing = [s for s in range(TOTAL_SHARDS_COUNT) if t.sizes[s] < 0]
    corrupt = [s for s in range(TOTAL_SHARDS_COUNT)
               if t.sizes[s] >= 0 and s not in t.unreadable
               and t.chains[s] != t.stored[s]]
    parity_mismatch = []
    if t.recompute and not any(s < DATA_SHARDS_COUNT for s in corrupt):
        # data is bit-identical to what was encoded, so a recompute
        # mismatch means the STORED parity record disagrees with the
        # data — the check the plain file CRC cannot make
        for j in range(PARITY_SHARDS_COUNT):
            sid = DATA_SHARDS_COUNT + j
            if t.computed[j] != t.stored[sid] and sid not in corrupt:
                parity_mismatch.append(sid)
    return {"volume": t.volume, "collection": t.collection,
            "corrupt": corrupt, "missing": missing,
            "unreadable": sorted(t.unreadable),
            "parity_mismatch": parity_mismatch,
            "recomputed": t.recompute,
            "bytes": t.bytes_read,
            "ok": not (corrupt or missing or t.unreadable
                       or parity_mismatch)}


def deep_scrub(targets: list, mesh=None,
               span_bytes: Optional[int] = None,
               batch_units: Optional[int] = None,
               throttle=None,
               stage_stats: Optional[dict] = None) -> dict:
    """Deep-scrub `targets`, batching recompute spans across volumes
    into one compiled device geometry.  Returns
    {"volumes": [per-target verdicts], "scrubbed_bytes", "corrupt"}."""
    import numpy as np

    wall0 = time.perf_counter()
    timers = {"read": 0.0, "dispatch": 0.0, "encode_crc": 0.0}

    chunk = span_bytes or span_bytes_default()
    max_len = max([t.shard_len for t in targets] or [0])
    # no point padding spans past the largest shard; keep words whole
    if max_len > 0:
        chunk = min(chunk, max_len + (-max_len) % 4)
    chunk = max(4096, chunk - chunk % 4)

    # units: (target_idx, offset) spans for recompute-capable targets;
    # file-CRC-only targets are streamed without device dispatch
    units: list[tuple[int, int]] = []
    for ti, t in enumerate(targets):
        if t.recompute and t.shard_len > 0:
            units.extend((ti, off)
                         for off in range(0, t.shard_len, chunk))

    backend = "host-crc32c"
    batches = 0
    b = 0
    depth = _inflight()
    pool_before = pool_after = None
    if units:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ..ops.device_pool import get_pool
        from ..parallel.mesh import (aliases_host_memory, make_ec_mesh,
                                     make_parity_step)

        if mesh is None:
            mesh = make_ec_mesh()
        n_data, n_block = mesh.devices.shape
        width = chunk // 4
        if width % n_block:
            mesh = Mesh(mesh.devices.reshape(-1, 1), mesh.axis_names)
            n_data, n_block = mesh.devices.shape
        if batch_units is None:
            # ~32 MB of data spans per dispatch: at the default 1 MB
            # span this packs 3 volumes' spans into one geometry, the
            # cross-volume batching that amortizes the compiled step
            batch_units = max(1, (32 << 20) // (DATA_SHARDS_COUNT * chunk))
        b = min(batch_units, len(units))
        b = max(n_data, ((b + n_data - 1) // n_data) * n_data)
        step = make_parity_step(mesh)
        backend = "device-pooled-swar"
        pool = get_pool()
        single = mesh.devices.size == 1
        dev0 = mesh.devices.flat[0]
        dev_label = (str(dev0) if single
                     else f"sharded:{mesh.devices.size}")
        sharding_kb = NamedSharding(mesh, P(None, "data", "block"))
        zero_copy = single and aliases_host_memory(dev0)
        pool_before = pool.snapshot()

        oshape = (PARITY_SHARDS_COUNT, b, width)

        def _out_factory():
            z = np.zeros(oshape, dtype=np.int32)
            return jax.device_put(z, dev0 if single else sharding_kb)

        okey = ("maint-out", mesh, oshape)
        out_leases = [pool.lease(okey, _out_factory,
                                 PARITY_SHARDS_COUNT * b * chunk,
                                 device=dev_label)
                      for _ in range(depth + 1)]
        out_ring = deque(out_leases)
        # staging ring: a buffer is refilled only after its batch has
        # been synchronized (dlpack aliases it as the device input)
        staging = [np.zeros((DATA_SHARDS_COUNT, b, chunk), dtype=np.uint8)
                   for _ in range(depth + 2)]
        free_bufs = deque(staging)
        pending: deque = deque()  # (out_lease, buf, metas, t_disp)

        def _complete():
            out, buf, metas, t_disp = pending.popleft()
            t0 = time.perf_counter()
            parity = np.asarray(out.payload)  # blocks until ready
            pool.note_d2h(parity.nbytes, device=dev_label)
            pbytes = parity.view(np.uint8).reshape(
                PARITY_SHARDS_COUNT, b, chunk)
            for k, (ti, off) in enumerate(metas):
                t = targets[ti]
                if not t.recompute:
                    continue  # went unreadable mid-sweep: chain invalid
                for j in range(PARITY_SHARDS_COUNT):
                    psize = t.sizes[DATA_SHARDS_COUNT + j]
                    if psize < 0:
                        psize = t.shard_len
                    real = min(chunk, max(0, psize - off))
                    if real > 0:
                        t.computed[j] = crc_host.crc32c(
                            pbytes[j, k, :real], t.computed[j])
            out_ring.append(out)
            free_bufs.append(buf)
            timers["encode_crc"] += time.perf_counter() - t0

        try:
            for start in range(0, len(units), b):
                metas = units[start:start + b]
                if len(pending) >= depth:
                    _complete()
                buf = free_bufs.popleft()
                t0 = time.perf_counter()
                buf.fill(0)
                for k, (ti, off) in enumerate(metas):
                    t = targets[ti]
                    for i in range(DATA_SHARDS_COUNT):
                        raw = _read_span(t, i, off, chunk, throttle)
                        if raw and t.recompute:
                            buf[i, k, :len(raw)] = np.frombuffer(
                                raw, dtype=np.uint8)
                    # parity spans ride along for the plain file-CRC
                    # chain (bitrot in a parity file is still bitrot)
                    for j in range(PARITY_SHARDS_COUNT):
                        _read_span(t, DATA_SHARDS_COUNT + j, off,
                                   chunk, throttle)
                t1 = time.perf_counter()
                timers["read"] += t1 - t0
                # background device lane: yield to in-flight foreground
                # (degraded-read recover) decodes before dispatching
                timers["lane_wait"] = timers.get("lane_wait", 0.0) \
                    + _lanes.LANES.background_checkpoint()
                words = buf.view(np.int32)
                if zero_copy:
                    din = jax.dlpack.from_dlpack(words)
                else:
                    din = jax.device_put(
                        words, dev0 if single else sharding_kb)
                    pool.note_h2d(words.nbytes, device=dev_label)
                out = out_ring.popleft()
                # donation swap: the step aliases its result into the
                # leased slot; the old handle is dead
                out.payload = step(din, out.payload)
                timers["dispatch"] += time.perf_counter() - t1
                pending.append((out, buf, metas, t1))
                batches += 1
            while pending:
                _complete()
        finally:
            for ls in out_leases:
                pool.release(ls)
        pool_after = pool.snapshot()

    # file-CRC-only sweep for targets with no recompute units
    t0 = time.perf_counter()
    for t in targets:
        if t.recompute and t.shard_len > 0:
            continue
        for sid in range(TOTAL_SHARDS_COUNT):
            off = 0
            while t.sizes[sid] >= 0 and off < t.sizes[sid]:
                raw = _read_span(t, sid, off, chunk, throttle)
                if not raw:
                    break
                off += len(raw)
    timers["read"] += time.perf_counter() - t0

    volumes = []
    for t in targets:
        volumes.append(_verdict(t))
        if t.close is not None:
            t.close()
    wall = time.perf_counter() - wall0
    if stage_stats is not None:
        stage_stats.update({k: round(v, 3) for k, v in timers.items()})
        stage_stats["wall"] = round(wall, 3)
        stage_stats["backend"] = backend
        stage_stats["batches"] = batches
        stage_stats["batch_units"] = b
        stage_stats["k_shapes"] = [DATA_SHARDS_COUNT] if units else []
        stage_stats["inflight"] = depth
        stage_stats["span_bytes"] = chunk
        for k in ("read", "dispatch", "encode_crc"):
            stage_stats[f"{k}_frac"] = (
                round(timers[k] / wall, 3) if wall > 0 else 0.0)
        if pool_before is not None and pool_after is not None:
            stage_stats["pool"] = {
                "allocs": pool_after.get("allocs", 0),
                "lease_hits": (pool_after.get("lease_hits", 0)
                               - pool_before.get("lease_hits", 0))}
    total = sum(v["bytes"] for v in volumes)
    from ..stats import metrics
    metrics.MaintScrubbedBytesCounter.inc(total)
    # a parity record that disagrees with the recompute is corruption
    # too (either the parity file or the record) — surface both kinds
    return {"volumes": volumes, "scrubbed_bytes": total,
            "corrupt": [{"volume": v["volume"],
                         "shards": sorted(set(v["corrupt"])
                                          | set(v["parity_mismatch"]))}
                        for v in volumes
                        if v["corrupt"] or v["parity_mismatch"]],
            "backend": backend}


def deep_scrub_host(directory: str, collection: str, vid: int,
                    throttle=None, needle_walk: bool = True) -> dict:
    """Host fallback: chunked+paced whole-file CRC verification plus a
    needle-level walk — every live needle in the sorted .ecx is
    re-read and its own CRC verified (Needle.read_bytes raises on
    mismatch), catching corruption the whole-file CRC localises only
    to a shard, at needle granularity."""
    from ..storage import types as t
    from ..storage.erasure_coding.ec_volume import EcVolume, EcVolumeShard
    from ..storage.erasure_coding.encoder import load_volume_info
    from ..storage.tools import verify_shard_files

    base = (os.path.join(directory, f"{collection}_{vid}") if collection
            else os.path.join(directory, str(vid)))
    if os.path.exists(base + ".scl"):
        # inline EC volume: shard logs have no whole-file CRC record;
        # the audit recomputes every committed stripe's parity + CRC
        # against the commit log and re-reads every live needle
        from ..storage.erasure_coding.inline import verify_inline_volume

        return verify_inline_volume(directory, collection, vid)
    info = load_volume_info(base) or {}
    stored = info.get("shard_crc32c")
    clean, corrupt, absent = verify_shard_files(base, stored,
                                                throttle=throttle)
    checked = bad = 0
    bad_needles: list[int] = []
    if needle_walk and os.path.exists(base + ".ecx"):
        ev = EcVolume(directory, collection, vid)
        try:
            for sid in range(TOTAL_SHARDS_COUNT):
                if os.path.exists(base + to_ext(sid)):
                    ev.add_shard(EcVolumeShard(directory, collection,
                                               vid, sid))
            n_entries = ev.ecx_file_size // t.NEEDLE_MAP_ENTRY_SIZE
            for pos in range(n_entries):
                nid, _, size = ev._read_ecx_entry(pos)
                if t.size_is_deleted(size):
                    continue
                checked += 1
                try:
                    ev.read_needle(nid)
                except Exception:
                    bad += 1
                    if len(bad_needles) < 64:
                        bad_needles.append(nid)
        finally:
            ev.close()
    return {"volume": vid, "collection": collection,
            "clean": clean, "corrupt": corrupt, "missing": absent,
            "needles_checked": checked, "needles_bad": bad,
            "bad_needles": bad_needles,
            "ok": not (corrupt or bad)}
