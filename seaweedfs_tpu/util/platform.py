"""Small platform probes shared across modules.

Device detection answers from the CALLING process: `jax.devices()` is
asked here, once, and the answer is kept for the life of the process.
An accelerator belongs to one process at a time, so a probe run in a
child process would report the chip missing exactly when this process
holds it — and every device path would quietly run on the host.
"""

from __future__ import annotations

import os
import sys
import threading
import time

_lock = threading.Lock()
_cache: dict = {}  # {"device": dict | None} once jax.devices() was asked

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.
    Call before the first jit of a process (weed.py, the graft entry
    points).

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
    nothing is set in code.  Otherwise the cache lives at the fixed path
    <checkout>/.jax_cache — the path is part of the cache key, so it
    never carries a pid, a temp name or a time — published through the
    same variable so child daemons agree with their parent."""
    # every program caches, not only the slow ones: a fresh machine
    # otherwise recompiles dozens of sub-second XLA programs per process
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    placed_here = not path
    if placed_here:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before us: it read the env already
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(os.environ[
                              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
        if placed_here:
            jax.config.update("jax_compilation_cache_dir", path)
    return path


def process_age() -> float | None:
    """Seconds since the kernel started this process (Linux /proc);
    None where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def record_startup(**phases: float):
    """Start phases of this process, each recorded once, as the
    `volumeServer_startup_seconds{phase=}` gauge and one log line
    (import, load and listen come from `weed.py volume`; device_init
    from the first jax.devices(), whenever a device path first asks)."""
    from ..stats import metrics as stats
    from . import glog

    for phase, seconds in phases.items():
        stats.VolumeServerStartupSeconds.labels(phase).set(
            round(seconds, 6))
    glog.infof("start-up: %s", ", ".join(
        f"{phase} {seconds:.3f} s" for phase, seconds in phases.items()))


def available_cpu_count() -> int:
    """Cores THIS process may run on: the scheduling affinity mask when
    the platform exposes it (cgroup cpusets, taskset, k8s cpu-manager
    pins all shrink it below os.cpu_count()), else os.cpu_count().
    Worker-pool sizing must use this — spawning os.cpu_count() workers
    onto an affinity-restricted box just convoys them on the GIL."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def device_info() -> dict | None:
    """{"platform", "device_kind", "count"} as JAX reports them in THIS
    process (jax.devices()[0].platform / .device_kind, len(jax.devices())),
    or None when no backend is usable here.  A forked prefork worker
    (rpc/prefork.py) always gets None: it inherited the parent's runtime
    state without its threads and must never dispatch to the device the
    parent holds."""
    from ..rpc import prefork

    if prefork.is_worker():
        return None
    with _lock:
        if not _cache:
            try:
                t0 = time.perf_counter()
                import jax

                devs = jax.devices()
                _cache["device"] = {"platform": devs[0].platform,
                                    "device_kind": devs[0].device_kind,
                                    "count": len(devs)}
                record_startup(device_init=time.perf_counter() - t0)
            except (ImportError, RuntimeError) as e:
                from . import glog

                glog.errorf("jax backend failed to initialise (%s: %s); "
                            "device paths are off in this process",
                            type(e).__name__, e)
                _cache["device"] = None
        info = _cache["device"]
    return dict(info) if info else None


def device_asked() -> bool:
    """Whether this process's first `jax.devices()` has returned (with
    a device or with none): `device_info()` answers from its cache."""
    return bool(_cache)


def _probe() -> tuple[bool, str]:
    """(devices_ready, platform_name) of this process's default JAX
    backend — the one seam tests patch."""
    info = device_info()
    return (True, info["platform"]) if info else (False, "")


def jax_usable() -> bool:
    """True when this process's JAX backend enumerated its devices."""
    ready, _ = _probe()
    return ready


def on_tpu() -> bool:
    """True when this process's default JAX backend is a real TPU."""
    ready, platform = _probe()
    return ready and platform == "tpu"


# -- host<->device link throughput + encode-backend auto-selection -----------
#
# "Matching or beating" the host codec must hold on the hardware actually
# present: a fast kernel behind a slow host<->device link still loses
# disk->shards end to end.  The selection below predicts the batched
# pipeline's achievable rate from a measured link probe and picks the
# faster backend (BASELINE's -ec.backend contract: "tpu" forces the
# device path, None auto-selects).

_LINK_TTL_S = 600.0
_LINK_PROBE_BYTES = 4 << 20
_link_cache: dict = {}  # {"h2d": MB/s, "d2h": MB/s, "at": monotonic}
# fraction of bytes that must come BACK over the link per input byte
# (4 parity shards per 10 data shards)
_PARITY_RATIO = 0.4
# pipeline efficiency vs the raw link numbers (dispatch gaps, framing)
_LINK_EFFICIENCY = 0.85


def link_throughput(probe_bytes: int = _LINK_PROBE_BYTES,
                    ttl: float = _LINK_TTL_S) -> tuple[float, float]:
    """(h2d_MBps, d2h_MBps) of the host<->device link, EWMA-cached with a
    TTL.  Returns (0, 0) when no backend is usable."""
    with _lock:
        cached = dict(_link_cache)
    if cached and time.monotonic() - cached["at"] < ttl:
        return cached["h2d"], cached["d2h"]
    if not jax_usable():
        return 0.0, 0.0
    try:
        import jax
        import numpy as np

        buf = np.zeros(probe_bytes, dtype=np.uint8)
        dev = jax.device_put(buf)
        np.asarray(dev[:4])  # warm the path end to end
        t0 = time.monotonic()
        dev = jax.device_put(buf)
        np.asarray(dev[:4])
        h2d = probe_bytes / (1 << 20) / max(time.monotonic() - t0, 1e-6)
        t0 = time.monotonic()
        np.asarray(dev)
        d2h = probe_bytes / (1 << 20) / max(time.monotonic() - t0, 1e-6)
    except Exception:
        return 0.0, 0.0
    with _lock:
        if _link_cache:  # EWMA: smooth one-off outliers
            h2d = 0.5 * h2d + 0.5 * _link_cache["h2d"]
            d2h = 0.5 * d2h + 0.5 * _link_cache["d2h"]
        _link_cache.update(h2d=h2d, d2h=d2h, at=time.monotonic())
    return h2d, d2h


def predicted_batched_gibps() -> float:
    """Predicted disk->shards rate of the batched device pipeline in
    GiB/s: every input byte crosses the link up and 0.4 bytes of parity
    come back, with a fixed efficiency factor."""
    h2d, d2h = link_throughput()
    if h2d <= 0 or d2h <= 0:
        return 0.0
    mbps = _LINK_EFFICIENCY / (1.0 / h2d + _PARITY_RATIO / d2h)
    return mbps / 1024.0


_host_codec_cache: list = []


def host_codec_gibps() -> float:
    """Measured host EC codec kernel rate (GiB/s), derated to an e2e
    estimate; cached per process."""
    if _host_codec_cache:
        return _host_codec_cache[0]
    try:
        import numpy as np

        from ..ops import codec as codec_mod

        enc = codec_mod.new_host_encoder(10, 4)
        data = np.zeros((10, 4 << 20), dtype=np.uint8)
        matrix = np.asarray(enc.matrix[10:])
        enc._apply(matrix, data[:, :1 << 20])  # warm
        t0 = time.monotonic()
        enc._apply(matrix, data)
        dt = max(time.monotonic() - t0, 1e-6)
        kernel = data.nbytes / float(1 << 30) / dt
        # e2e is the smaller of the codec and the host pipeline's I/O
        # side: ~1.2 GiB/s of read+write per I/O-overlapping worker
        # (measured: single-core tmpfs page-allocation bound), scaling
        # with the worker fan-out on multi-core hosts
        workers = int(os.environ.get("WEED_EC_HOST_WORKERS", "0") or 0) \
            or max(1, min(16, available_cpu_count()))
        rate = min(kernel * 0.75, 1.2 * workers)
    except Exception:
        rate = 0.05  # pure-python/numpy fallback territory
    _host_codec_cache.append(rate)
    return rate


def prefer_batched_encode() -> bool:
    """True when the batched device pipeline is predicted to beat the
    synchronous host codec end to end on THIS machine's link."""
    ready, plat = _probe()
    if not ready:
        return False
    if plat != "tpu":
        # CPU/virtual-mesh backend: the "device" shares host memory, so
        # there is no link to lose on — keep the batched pipeline (the
        # surface the multi-chip dryrun and tests exercise)
        return True
    predicted = predicted_batched_gibps()
    host = host_codec_gibps()
    if predicted <= 0:
        return False
    if predicted < host:
        from . import glog

        glog.infof(
            "ec encode auto-backend: host codec (link-capped device "
            "path predicted %.3f GiB/s < host %.3f GiB/s)",
            predicted, host)
        return False
    return True
