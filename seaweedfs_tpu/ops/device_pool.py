"""Device-memory slab pool for the EC pipeline (BASELINE config 4's
orchestration layer).

A dispatch layer that allocates fresh buffers per batch pays per-batch
`device_put` allocations, undonated outputs and synchronous drains on
every dispatch.  This module is the fix's memory half: every buffer the
dispatch path touches comes from a pool of pre-allocated, fixed-shape
slabs so the steady state performs ZERO per-batch allocations.

Two kinds of slab, one accounting domain:

  leases    — fixed-shape transfer/compute slots keyed by an opaque
              caller key (shape, dtype, device/mesh).  `lease()` hands
              out a free slab of the key or materializes one via the
              caller's factory (host staging buffers, donated device
              output rings); `release()` returns it for reuse.  Repeat
              encodes with the same geometry re-lease the same slabs.
  residents — ref-counted content slabs (`acquire_resident`): device
              uploads that outlive one call so repeated degraded reads /
              rebuilds against the same survivor set hit HBM instead of
              re-uploading over the link.  A resident with refs == 0
              stays cached until the byte cap evicts it (LRU).

`WEED_EC_DEVICE_POOL_MB` caps the total bytes the pool retains for
*idle* slabs (free leases + unreferenced residents); actively leased or
referenced slabs are never evicted, so the cap is a retention bound,
not an admission control.  The pool never imports jax itself — factories
own the allocation, the pool owns identity, reuse and accounting — so
it is equally happy pooling pinned host staging buffers.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Optional

DEFAULT_POOL_MB = 256


def _cap_bytes() -> int:
    """Retention cap, re-read per operation (tests and daemons flip the
    knob without re-importing)."""
    mb = os.environ.get("WEED_EC_DEVICE_POOL_MB", "")
    try:
        return int(float(mb) * (1 << 20)) if mb else DEFAULT_POOL_MB << 20
    except ValueError:
        return DEFAULT_POOL_MB << 20


class Lease:
    """One leased slab: `payload` is whatever the factory built (numpy
    staging buffer or jax device array).  Callers may swap `payload`
    while holding the lease (donation returns a new handle aliasing the
    same device memory); the swap travels back into the pool on
    release.  `device` is the placement label the slab was leased for —
    part of the free-list identity, so a slab leased for one device is
    never handed to a caller staging for another."""

    __slots__ = ("key", "payload", "nbytes", "device")

    def __init__(self, key, payload, nbytes: int, device=None):
        self.key = key
        self.payload = payload
        self.nbytes = nbytes
        self.device = device


class _Resident:
    __slots__ = ("key", "payload", "nbytes", "refs", "last_used")

    def __init__(self, key, payload, nbytes: int):
        self.key = key
        self.payload = payload
        self.nbytes = nbytes
        self.refs = 0
        self.last_used = 0.0


class DevicePool:
    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict[Any, list[Lease]] = {}   # key -> idle leases
        self._free_order: list[Lease] = []        # LRU over idle leases
        self._residents: dict[Any, _Resident] = {}
        self._leased_bytes = 0
        self._free_bytes = 0
        self._resident_bytes = 0
        self._leased_count = 0
        # counters (monotonic; mirrored into Prometheus vectors)
        self.allocs = 0
        self.lease_hits = 0
        self.resident_hits = 0
        self.resident_misses = 0
        self.evictions = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # per-device breakdowns (device label -> bytes): slab residency
        # from the lease accounting, link traffic from note_h2d/note_d2h
        self._dev_bytes: dict[str, int] = {}
        self._dev_h2d: dict[str, int] = {}
        self._dev_d2h: dict[str, int] = {}
        self._evictions_published = 0
        # HBM occupancy telemetry: peak bytes ever held, plus wall time
        # accrued while occupancy sat at >=95% of that peak (a pool
        # pinned at its watermark is the signal to raise
        # WEED_EC_DEVICE_POOL_MB or shrink the batch geometry)
        self._hwm_bytes = 0
        self._hwm_seconds = 0.0
        self._occ_ts = time.monotonic()
        self._occ_bytes = 0

    # -- transfer/compute slots ---------------------------------------

    @staticmethod
    def _dev_label(device) -> str:
        return "host" if device is None else str(device)

    def lease(self, key, factory: Callable[[], Any], nbytes: int,
              device=None) -> Lease:
        """A slab for `(key, device)`: a previously released one, else
        `factory()`.  The factory runs outside the lock (jax allocation
        can be slow and reentrant).  `device` is part of the free-list
        identity: two callers leasing the same geometry for different
        devices never alias slabs (a payload materialized on device A
        handed to a dispatch against device B would silently re-upload
        — or worse, compute against stale memory)."""
        bucket_key = (key, self._dev_label(device))
        with self._lock:
            bucket = self._free.get(bucket_key)
            if bucket:
                ls = bucket.pop()
                self._free_order.remove(ls)
                self._free_bytes -= ls.nbytes
                self._leased_bytes += ls.nbytes
                self._leased_count += 1
                self.lease_hits += 1
                self._publish()
                return ls
        payload = factory()
        ls = Lease(bucket_key, payload, nbytes, self._dev_label(device))
        with self._lock:
            self.allocs += 1
            self._leased_bytes += nbytes
            self._dev_bytes[ls.device] = \
                self._dev_bytes.get(ls.device, 0) + nbytes
            self._leased_count += 1
            self._publish()
        return ls

    def release(self, lease: Lease):
        with self._lock:
            self._leased_bytes -= lease.nbytes
            self._leased_count -= 1
            self._free.setdefault(lease.key, []).append(lease)
            self._free_order.append(lease)
            self._free_bytes += lease.nbytes
            self._evict_locked()
            self._publish()

    def discard(self, lease: Lease):
        """Release without retaining (the slab's geometry won't recur)."""
        with self._lock:
            self._leased_bytes -= lease.nbytes
            self._leased_count -= 1
            self._drop_dev_bytes_locked(lease)
            self._publish()

    def _drop_dev_bytes_locked(self, lease: Lease):
        dev = getattr(lease, "device", None) or "host"
        left = self._dev_bytes.get(dev, 0) - lease.nbytes
        if left > 0:
            self._dev_bytes[dev] = left
        else:
            self._dev_bytes.pop(dev, None)

    # -- ref-counted resident content slabs ---------------------------

    def acquire_resident(self, key, factory: Callable[[], Any],
                         nbytes: int) -> Any:
        """The device-resident payload for `key`, uploading via
        `factory()` on miss.  Pairs with `release_resident`; the slab
        survives refs == 0 (that is the point — the NEXT degraded read
        against the same survivor set skips the upload) until the byte
        cap evicts it."""
        with self._lock:
            res = self._residents.get(key)
            if res is not None:
                res.refs += 1
                res.last_used = time.monotonic()
                self.resident_hits += 1
                self._publish()
                return res.payload
        payload = factory()
        with self._lock:
            res = self._residents.get(key)
            if res is None:  # single writer wins; duplicates discarded
                res = _Resident(key, payload, nbytes)
                self._residents[key] = res
                self._resident_bytes += nbytes
                self.resident_misses += 1
                self.allocs += 1
            else:
                self.resident_hits += 1
            res.refs += 1
            res.last_used = time.monotonic()
            self._evict_locked()
            self._publish()
            return res.payload

    def release_resident(self, key):
        with self._lock:
            res = self._residents.get(key)
            if res is not None and res.refs > 0:
                res.refs -= 1
            self._publish()

    # -- eviction / accounting ----------------------------------------

    def _evict_locked(self):
        """Drop idle bytes (free leases first, then refs==0 residents,
        LRU) until under the cap."""
        cap = _cap_bytes()

        def idle():
            return self._free_bytes + sum(
                r.nbytes for r in self._residents.values() if r.refs == 0)

        while self._free_order and idle() > cap:
            ls = self._free_order.pop(0)
            self._free[ls.key].remove(ls)
            if not self._free[ls.key]:
                del self._free[ls.key]
            self._free_bytes -= ls.nbytes
            self._drop_dev_bytes_locked(ls)
            self.evictions += 1
        while idle() > cap:
            victims = sorted(
                (r for r in self._residents.values() if r.refs == 0),
                key=lambda r: r.last_used)
            if not victims:
                break
            v = victims[0]
            del self._residents[v.key]
            self._resident_bytes -= v.nbytes
            self.evictions += 1

    def note_h2d(self, nbytes: int, device=None):
        dev = self._dev_label(device)
        with self._lock:
            self.h2d_bytes += nbytes
            self._dev_h2d[dev] = self._dev_h2d.get(dev, 0) + nbytes
        from ..stats import metrics as stats
        stats.EcDeviceH2dBytesCounter.labels(dev).inc(nbytes)

    def note_d2h(self, nbytes: int, device=None):
        dev = self._dev_label(device)
        with self._lock:
            self.d2h_bytes += nbytes
            self._dev_d2h[dev] = self._dev_d2h.get(dev, 0) + nbytes
        from ..stats import metrics as stats
        stats.EcDeviceD2hBytesCounter.labels(dev).inc(nbytes)

    def _note_occupancy_locked(self):
        """Advance the watermark clock (lock held).  Time since the last
        byte mutation is attributed to the PREVIOUS occupancy level, so
        `hwm_seconds` is exact piecewise accounting, not sampling."""
        now = time.monotonic()
        if self._hwm_bytes > 0 and \
                self._occ_bytes >= 0.95 * self._hwm_bytes:
            self._hwm_seconds += now - self._occ_ts
        self._occ_ts = now
        self._occ_bytes = (self._free_bytes + self._leased_bytes
                           + self._resident_bytes)
        if self._occ_bytes > self._hwm_bytes:
            self._hwm_bytes = self._occ_bytes

    def _publish(self):
        """Mirror state into the Prometheus vectors (lock held: the
        registry's own primitives are lock-free enough)."""
        self._note_occupancy_locked()
        try:
            from ..stats import metrics as stats
        except Exception:  # pragma: no cover - import cycles at teardown
            return
        stats.DevicePoolHwmBytesGauge.set(self._hwm_bytes)
        stats.DevicePoolHwmSecondsGauge.set(self._hwm_seconds)
        for dev, nbytes in self._dev_bytes.items():
            stats.DevicePoolDeviceBytesGauge.labels(dev).set(nbytes)
        stats.DevicePoolSlotsGauge.labels("free").set(
            len(self._free_order))
        stats.DevicePoolSlotsGauge.labels("leased").set(self._leased_count)
        stats.DevicePoolSlotsGauge.labels("resident").set(
            len(self._residents))
        stats.DevicePoolBytesGauge.set(
            self._free_bytes + self._leased_bytes + self._resident_bytes)
        if self.evictions > self._evictions_published:
            stats.DevicePoolEvictionsCounter.inc(
                self.evictions - self._evictions_published)
            self._evictions_published = self.evictions

    def snapshot(self) -> dict:
        # the QoS device lanes gate dispatch INTO this pool's slots, so
        # their state belongs in the same observability snapshot
        from ..qos.lanes import LANES

        with self._lock:
            self._note_occupancy_locked()
            return {
                "hwm_bytes": self._hwm_bytes,
                "hwm_seconds": round(self._hwm_seconds, 3),
                "free_slots": len(self._free_order),
                "leased_slots": self._leased_count,
                "resident_slabs": len(self._residents),
                "bytes": self._free_bytes + self._leased_bytes
                + self._resident_bytes,
                "allocs": self.allocs,
                "lease_hits": self.lease_hits,
                "resident_hits": self.resident_hits,
                "resident_misses": self.resident_misses,
                "evictions": self.evictions,
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "devices": {
                    dev: {
                        "bytes": self._dev_bytes.get(dev, 0),
                        "h2d_bytes": self._dev_h2d.get(dev, 0),
                        "d2h_bytes": self._dev_d2h.get(dev, 0),
                    }
                    for dev in sorted(set(self._dev_bytes)
                                      | set(self._dev_h2d)
                                      | set(self._dev_d2h))
                },
                "lanes": LANES.snapshot(),
            }

    def clear(self):
        with self._lock:
            for ls in self._free_order:
                self._drop_dev_bytes_locked(ls)
            self._free.clear()
            self._free_order.clear()
            self._residents.clear()
            self._free_bytes = self._resident_bytes = 0
            self._publish()


_pool: Optional[DevicePool] = None
_pool_lock = threading.Lock()


def get_pool() -> DevicePool:
    global _pool
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                _pool = DevicePool()
    return _pool


def reset_pool():
    """Drop the process pool (tests; frees any retained device memory)."""
    global _pool
    with _pool_lock:
        _pool = None
