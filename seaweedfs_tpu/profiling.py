"""Continuous profiling: folded call stacks, device kernel timelines and
HBM occupancy telemetry.

Every perf round so far was steered by hand-rolled stage timers; the only
CPU profiler in the tree was the flat leaf-frame sampler in util/grace.py
(no call stacks, no on-demand access, no device visibility).  This module
is the always-on, queryable profiling layer:

  * host side — a sampling profiler over ``sys._current_frames()`` that
    keeps FULL folded call stacks (``frame.f_back`` walk, bounded
    stack-interning table), tagged with the sampled thread's name and
    the active RPC route from tracing's thread-local span context, so a
    profile slices per daemon, per thread pool and per route.  It runs
    always-on at a low ``WEED_PROF_HZ`` rate and serves on-demand bursts
    via ``GET /debug/pprof/profile?seconds=N&hz=M`` (collapsed-stack
    text — pipe straight into flamegraph.pl or speedscope) plus
    ``GET /debug/pprof/heap`` (tracemalloc allocation sites, armed on
    demand), mounted on every daemon exactly like ``/debug/traces``.
    The profiler measures its own duty cycle and exports it as the
    ``SeaweedFS_profiler_overhead_ratio`` gauge;
  * device side — host-timed dispatch->ready latency per batch from the
    EC device pipeline's completion FIFO, XLA cost analysis captured
    once per compiled geometry, and the device pool's HBM occupancy
    high-watermark, all queryable as a JSON timeline on
    ``GET /debug/pprof/device`` and exported as ``ec_kernel_*`` /
    ``device_pool_*`` metric families;
  * cluster side — ``merge_folded`` combines per-daemon profiles under
    per-daemon root frames into one cluster flamegraph (the engine
    behind ``weed.py profile``).

Knobs (env, read live like the WEED_TRACE_* family):
  WEED_PROF_HZ          always-on sampling rate (default 5; 0 disables)
  WEED_PROF_MAX_STACKS  interned-stack table cap (default 8192)
"""

from __future__ import annotations

import os
import sys
import threading
import time
import zlib
from collections import deque
from typing import Optional

from . import tracing
from .stats import metrics as _stats

_TRUNCATED = "(truncated)"
# What the always-on sampler's GIL gauge checksums each tick: zlib gives
# the GIL up for anything over 5 KiB, and 256 KiB keep it away for some
# 70 us, in which a thread that waits for the GIL takes it.  Probes of
# 1 and 4 MiB read the same within a third in three cells on the TPU
# hosts and cost four and sixteen times the CPU (PERF.md §6, PR 40).
_GIL_PROBE = bytes(256 << 10)
_MAX_DEPTH = 64


def prof_hz() -> float:
    return tracing._env_live(
        "WEED_PROF_HZ", b"WEED_PROF_HZ",
        lambda raw: max(0.0, float(raw)), 5.0)


def max_stacks() -> int:
    return tracing._env_live(
        "WEED_PROF_MAX_STACKS", b"WEED_PROF_MAX_STACKS", int, 8192)


# -- folded-stack engine ------------------------------------------------------

# frame labels are interned per (code object, line): the sampler walks
# the same hot frames thousands of times, so the format+basename cost is
# paid once per distinct frame, not per sample
_label_cache: dict = {}


def _frame_label(frame, leaf: bool) -> str:
    co = frame.f_code
    # leaf frames keep the sampled line (hot-line attribution, like the
    # old flat sampler); caller frames use the def line so one function
    # is ONE flamegraph frame no matter which call site is live.
    # f_lineno can be None when the target thread is mid-transition
    # (CPython computes it lazily from f_lasti) — fall back to the def
    # line rather than dropping the whole sample
    lineno = (frame.f_lineno if leaf else None) or co.co_firstlineno
    key = (co, lineno)
    label = _label_cache.get(key)
    if label is None:
        if len(_label_cache) > 4 * max_stacks():
            _label_cache.clear()
        label = "%s (%s:%d)" % (co.co_name,
                                os.path.basename(co.co_filename), lineno)
        label = label.replace(";", ":")  # ';' is the fold separator
        _label_cache[key] = label
    return label


def fold_stack(frame) -> str:
    """Root-first collapsed stack for one thread's current frame."""
    parts = []
    leaf = True
    while frame is not None and len(parts) < _MAX_DEPTH:
        parts.append(_frame_label(frame, leaf))
        leaf = False
        frame = frame.f_back
    parts.reverse()
    return ";".join(parts)


class StackSampler:
    """All-thread folded-stack sampling profiler.

    Samples ``sys._current_frames()`` on a timer like Go's pprof CPU
    profile; each sample's key is ``thread[;route];frame;frame;...`` in
    flamegraph.pl collapsed form.  ``publish=True`` (the always-on
    instance) mirrors per-route sample counts into the Prometheus
    registry.  The sampler measures its own busy time, so its duty
    cycle (``overhead_ratio``) is observable, not guessed.

    The always-on instance is also the process's GIL gauge: every tick
    it checksums 256 KiB in C (``zlib.crc32``), which gives the GIL up
    as a handler's ``pread``, ``send`` or device wait does, for long
    enough that a thread waiting for the GIL takes it, and records how
    much longer that took than the least it ever has (``gil_wait`` over
    its ``floor``): the wait to get the GIL back, which every thread of
    the process pays after every blocking call.  No timer is in the
    reading: a timed wait comes back a whole distribution late on an
    idle host (0.04 to 2.5 ms on the TPU hosts, PERF.md §6, PR 40), a
    checksum's time has a floor.  One observation a tick of a thread
    that holds the GIL for microseconds itself; late for the GIL or, on
    a host short of cores, for a core: a gauge of the process, not a
    measurement of any one request."""

    def __init__(self, hz: Optional[float] = None,
                 publish: bool = False, exclude=()):
        self.hz = hz  # None: follow WEED_PROF_HZ live
        self.samples: dict[str, int] = {}
        self.total = 0
        self.truncated = 0
        self.errors = 0
        self.route_samples: dict[str, int] = {}
        self.busy = 0.0
        self.started = 0.0
        self._publish = publish
        self._exclude = set(exclude)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._names: dict[int, str] = {}
        self._ticks = 0
        # the GIL gauge of the always-on instance: the least a probe
        # took, and count, sum, max of what each took longer than that
        self.gil_floor = float("inf")
        self.gil_count = 0
        self.gil_sum = 0.0
        self.gil_max = 0.0

    # -- lifecycle ----------------------------------------------------

    def start(self):
        self.started = time.perf_counter()
        self._thread = threading.Thread(
            target=self._loop, name="weed-prof", daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 2.0) -> bool:
        """Stop sampling; True when the sampler thread actually joined
        (False: it is still finishing one last tick — daemonized, so it
        cannot outlive the process, but the caller should say so)."""
        self._stop.set()
        t = self._thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    def _interval(self) -> float:
        hz = self.hz if self.hz is not None else prof_hz()
        return (1.0 / hz) if hz and hz > 0 else 0.0

    def _loop(self):
        me = threading.get_ident()
        while True:
            interval = self._interval()
            if interval <= 0:  # live-disabled: idle cheaply, stay alive
                if self._stop.wait(0.5):
                    return
                continue
            if self._stop.wait(interval):
                return
            t0 = time.perf_counter()
            if self._publish:
                zlib.crc32(_GIL_PROBE)
                wait = time.perf_counter() - t0
                if wait < self.gil_floor:
                    self.gil_floor = wait
                wait -= self.gil_floor
                self.gil_count += 1
                self.gil_sum += wait
                if wait > self.gil_max:
                    self.gil_max = wait
                _stats.ProfilerGilWaitHistogram.observe(wait)
                t0 += wait      # waited, not worked: not in `busy`
            try:
                self._sample_once(me)
            except Exception:
                # sampling races against every other thread's execution
                # state; one unreadable tick must not kill the always-on
                # sampler for the remaining process lifetime
                self.errors += 1
            self.busy += time.perf_counter() - t0

    # -- sampling -----------------------------------------------------

    def _sample_once(self, me: int):
        frames = sys._current_frames()
        names = self._names
        if any(tid not in names for tid in frames):
            names = self._names = {
                t.ident: t.name for t in threading.enumerate()}
        self._ticks += 1
        if self._ticks % 128 == 0:
            tracing.prune_thread_spans(frames.keys())
        cap = max_stacks()
        routes = []
        with self._lock:
            for tid, frame in frames.items():
                if tid == me or tid in self._exclude:
                    continue
                sp = tracing.span_for_thread(tid)
                route = (sp.route or "") if sp is not None else ""
                key = "%s;%s" % (names.get(tid) or "thread-%d" % tid,
                                 fold_stack(frame))
                if route:
                    thread, _, rest = key.partition(";")
                    key = "%s;%s;%s" % (thread, route, rest)
                    routes.append(route)
                    self.route_samples[route] = \
                        self.route_samples.get(route, 0) + 1
                if key not in self.samples and len(self.samples) >= cap:
                    self.truncated += 1
                    key = _TRUNCATED
                self.samples[key] = self.samples.get(key, 0) + 1
                self.total += 1
        if self._publish:
            for route in routes:
                _stats.ProfilerRouteSamplesCounter.labels(route).inc()

    # -- reporting ----------------------------------------------------

    def overhead_ratio(self) -> float:
        wall = time.perf_counter() - self.started if self.started else 0.0
        return (self.busy / wall) if wall > 0 else 0.0

    def folded(self, limit: int = 0) -> str:
        """Collapsed-stack text, hottest stacks first — feed directly to
        flamegraph.pl / speedscope."""
        with self._lock:
            items = sorted(self.samples.items(), key=lambda kv: -kv[1])
        if limit:
            items = items[:limit]
        return "".join("%s %d\n" % kv for kv in items)

    def snapshot(self) -> dict:
        with self._lock:
            out = {"samples": self.total, "stacks": len(self.samples),
                   "truncated": self.truncated, "errors": self.errors,
                   "overhead_ratio": round(self.overhead_ratio(), 6)}
        if self._publish:
            n = self.gil_count
            out["gil_wait"] = {
                "floor": round(self.gil_floor, 9) if n else 0.0,
                "count": n,
                "mean": round(self.gil_sum / n, 9) if n else 0.0,
                "max": round(self.gil_max, 9)}
        return out


# -- always-on process profiler ----------------------------------------------

_PROFILER: Optional[StackSampler] = None
_prof_lock = threading.Lock()


def ensure_started() -> Optional[StackSampler]:
    """Start the process-wide always-on sampler (idempotent; called by
    every daemon mount).  WEED_PROF_HZ is read live inside the loop, so
    0 parks the thread rather than preventing creation."""
    global _PROFILER
    if _PROFILER is None:
        with _prof_lock:
            if _PROFILER is None:
                prof = StackSampler(hz=None, publish=True)
                prof.start()
                _PROFILER = prof
    return _PROFILER


def profiler() -> Optional[StackSampler]:
    return _PROFILER


def overhead_ratio() -> float:
    prof = _PROFILER
    return prof.overhead_ratio() if prof is not None else 0.0


def stack_count() -> float:
    prof = _PROFILER
    return float(len(prof.samples)) if prof is not None else 0.0


def gil_probe_floor() -> float:
    prof = _PROFILER
    return prof.gil_floor if prof is not None and prof.gil_count else 0.0


def profile_burst(seconds: float, hz: float, exclude=()) -> str:
    """On-demand burst: a dedicated sampler for `seconds` at `hz`,
    returning collapsed stacks.  Runs beside the always-on sampler
    without disturbing its counters."""
    sampler = StackSampler(hz=hz, publish=False, exclude=exclude)
    sampler.start()
    time.sleep(seconds)
    sampler.stop()
    return sampler.folded()


# -- device kernel timeline ---------------------------------------------------

_tl_lock = threading.Lock()
_DEVICE_TIMELINE: "deque[dict]" = deque(maxlen=512)


def record_device_batch(latency_s: float, units: int = 0, k: int = 0,
                        devices: int = 1):
    """One EC device batch completed: host-observed dispatch->ready
    latency (rides the WEED_EC_DEVICE_INFLIGHT completion FIFO).
    `devices` is the width of the mesh the job runs over (a seal deals
    whole batches to its devices, a rebuild shards each) — the histogram
    is labeled by it, so a stall that only appears at a given mesh width
    shows up as its own latency series."""
    _stats.EcKernelDispatchHistogram.labels(str(devices)).observe(latency_s)
    with _tl_lock:
        _DEVICE_TIMELINE.append({
            "ts": round(time.time(), 3),
            "dispatch_ready_ms": round(latency_s * 1e3, 3),
            "units": units, "k": k, "devices": devices})


def device_timeline() -> dict:
    """The /debug/pprof/device payload: recent batch latencies (host
    clock, dispatch -> host copy, transfer included) and the device
    pool's occupancy snapshot."""
    from .ops import device_pool

    pool = device_pool._pool  # do NOT materialize a pool just to report
    with _tl_lock:
        timeline = list(_DEVICE_TIMELINE)
    return {"timeline": timeline,
            "pool": pool.snapshot() if pool is not None else {}}


def reset_device_telemetry():
    """Tests: drop the timeline."""
    with _tl_lock:
        _DEVICE_TIMELINE.clear()


# -- cluster merge ------------------------------------------------------------

def merge_folded(profiles: dict[str, str]) -> str:
    """Merge per-daemon collapsed-stack texts into one cluster profile:
    each daemon becomes a root frame, identical stacks sum."""
    merged: dict[str, int] = {}
    for daemon in sorted(profiles):
        for line in profiles[daemon].splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            stack, _, count = line.rpartition(" ")
            try:
                n = int(count)
            except ValueError:
                continue
            if not stack:
                continue
            key = "%s;%s" % (daemon, stack)
            merged[key] = merged.get(key, 0) + n
    return "".join("%s %d\n" % kv for kv in
                   sorted(merged.items(), key=lambda kv: -kv[1]))


# -- HTTP surface -------------------------------------------------------------

def _heap_text(req) -> str:
    import tracemalloc

    if req.param("stop") == "1":
        if tracemalloc.is_tracing():
            tracemalloc.stop()
        return "# tracemalloc disarmed\n"
    if not tracemalloc.is_tracing():
        # armed on demand: tracing allocations is too costly to leave on
        tracemalloc.start(12)
        return ("# tracemalloc armed (12 frames); re-fetch "
                "/debug/pprof/heap for allocation sites, "
                "?stop=1 to disarm\n")
    try:
        limit = int(req.param("limit") or 50)
    except ValueError:
        limit = 50
    snapshot = tracemalloc.take_snapshot()
    lines = ["# tracemalloc top allocation sites"]
    lines.extend(str(stat) for stat in
                 snapshot.statistics("lineno")[:limit])
    return "\n".join(lines) + "\n"


def pprof_handler(req):
    """RpcServer route for the /debug/pprof family.  Register with the
    bare prefix — longest-prefix matching routes profile/heap/device
    here, like traces_handler."""
    from .rpc.http_rpc import Response, RpcError

    rest = req.path[len("/debug/pprof"):].strip("/")
    if not rest:
        prof = _PROFILER
        return {
            "endpoints": ["/debug/pprof/profile?seconds=N&hz=M",
                          "/debug/pprof/heap", "/debug/pprof/device"],
            "always_on": prof.snapshot() if prof is not None else None,
            "hz": prof_hz(),
        }
    if rest == "profile":
        try:
            seconds = float(req.param("seconds") or 2.0)
        except ValueError:
            seconds = 2.0
        try:
            hz = float(req.param("hz") or 99.0)
        except ValueError:
            hz = 99.0
        seconds = max(0.0, min(seconds, 120.0))
        hz = max(1.0, min(hz, 1000.0))
        if seconds == 0:  # cumulative always-on profile, no wait
            prof = _PROFILER
            if prof is None:
                raise RpcError(
                    "always-on profiler not running; use ?seconds=N", 400)
            text = prof.folded()
        else:
            text = profile_burst(seconds, hz,
                                 exclude={threading.get_ident()})
        return Response(text.encode(),
                        content_type="text/plain; charset=utf-8")
    if rest == "heap":
        return Response(_heap_text(req).encode(),
                        content_type="text/plain; charset=utf-8")
    if rest == "device":
        return device_timeline()
    raise RpcError(f"unknown pprof endpoint {rest!r}", 404)


def mount(server):
    """Register /debug/pprof on an RpcServer and start the always-on
    sampler (every daemon front end calls this, like faults.mount)."""
    server.add("GET", "/debug/pprof", pprof_handler)
    ensure_started()
