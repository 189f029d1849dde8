"""Benchmark: RS(10,4) encode — kernel ceiling AND end-to-end paths.

Four measurements (BASELINE.md configs 1/4/5 + the kernel ceiling):

  * kernel        — slope-based device throughput of the parity kernel
                    alone (no CRC, no I/O): the ceiling.
  * hbm_fused     — slope-based throughput of the production batched step
                    (parity + fused per-shard CRC32C) on HBM-resident
                    (B, 10, L) batches: config 4/5's compute number.
  * e2e_disk      — wall-clock disk->shard-files throughput of the
                    streaming pipeline (parallel/batched_encode.py) on a
                    1 GiB volume: config 1.
  * e2e_batched   — same, many volumes through one pipeline: config 4.

Baseline: the native AVX2 nibble-shuffle codec in native/ec_native.cpp
(same algorithm class as klauspost/reedsolomon's SIMD kernels the
reference calls; BASELINE.md publishes no EC number so it is measured on
this machine), both as a raw kernel and end-to-end through the synchronous
host encode loop (the reference's architecture, ec_encoder.go:194-231).

Methodology for device kernels: each measurement jits a chain of K
serialised encodes (1-element data dependency between steps) and reports
the slope between two chain lengths, so per-call dispatch latency
cancels.  End-to-end numbers are wall-clock including file I/O and
host<->device transfer.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "GiB/s", "vs_baseline": N, ...}
value = hbm_fused (the HBM-resident batched parity+CRC step — the compute
number the host<->device link cannot distort); vs_baseline = value /
cpu_avx2_kernel (the closest CPU analogue: its kernel without CRC, i.e. a
baseline-favouring comparison).  The disk->shards wall-clock numbers and
the cpu end-to-end run are reported alongside as e2e_* / cpu_e2e_gibps
with the measured link bandwidth that caps them.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

GIB = float(1 << 30)


def bench_cpu_kernel(length: int = 64 << 20, reps: int = 3,
                     level: int = -1) -> float:
    """Native C++ encode GiB/s on (10, length) — kernel only.  level=1
    pins the AVX2 PSHUFB nibble-table kernel (the klauspost-classic
    algorithm the reference vendors — the apples-to-apples baseline);
    level=-1 is the best kernel on this machine (GFNI when present)."""
    from seaweedfs_tpu.ops.codec import NativeEncoder

    try:
        enc = NativeEncoder(10, 4, level=level)
    except RuntimeError:
        return 0.0
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(10, length), dtype=np.uint8)
    matrix = np.asarray(enc.matrix[10:])
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        enc._apply(matrix, data)
        dt = time.perf_counter() - t0
        best = max(best, data.nbytes / GIB / dt)
    return best


def _make_kernel(method: str, block: int | None):
    from seaweedfs_tpu.ops import gf256, rs_pallas
    from seaweedfs_tpu.ops.rs_jax import (_apply_mxu, _bit_matrix_cached,
                                          _matrix_key, apply_matrix_swar)

    matrix = gf256.parity_matrix(10, 14)
    if method == "mxu":
        bm = _bit_matrix_cached(*_matrix_key(matrix))
        return lambda x: _apply_mxu(bm, x)
    if method == "pallas":
        return lambda x: rs_pallas.apply_matrix_pallas(
            matrix, x, **({"block": block} if block else {}))
    if method == "swar":
        return lambda x: apply_matrix_swar(matrix, x)
    raise ValueError(method)


def _slope_time(make_chain, data, chains, reps) -> float:
    """Best per-step seconds via the two-chain-length slope method."""
    import time as _t

    times = {}
    for k in chains:
        f = make_chain(k)
        np.asarray(f(data))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = _t.perf_counter()
            np.asarray(f(data))
            best = min(best, _t.perf_counter() - t0)
        times[k] = best
    return (times[chains[1]] - times[chains[0]]) / (chains[1] - chains[0])


def bench_tpu_kernel(method: str, length: int, block: int | None = None,
                     chains: tuple[int, int] = (2, 10), reps: int = 3
                     ) -> float:
    """Slope-based device throughput in GiB/s for one kernel variant."""
    import jax
    import jax.numpy as jnp

    kernel = _make_kernel(method, block)

    @jax.jit
    def gen(key):
        return jax.random.randint(key, (10, length), 0, 256, dtype=jnp.uint8)

    data = gen(jax.random.PRNGKey(0))
    np.asarray(data[0, :8])  # force materialization

    def chain(k):
        @jax.jit
        def f(x):
            acc, out = x, None
            for _ in range(k):
                out = kernel(acc)
                acc = acc.at[0, 0].set(out[0, 0])  # serialising dependency
            return out[0, :8]
        return f

    per_encode = _slope_time(chain, data, chains, reps)
    if per_encode <= 0:
        return 0.0
    return (10 * length) / GIB / per_encode


def bench_hbm_fused(batch: int, length: int,
                    chains: tuple[int, int] = (16, 48), reps: int = 4,
                    variant: str = "xla") -> float:
    """Slope throughput of the production batched step (parity + fused
    CRC32C) on an HBM-resident (B, 10, L) batch.  variant: "xla" (the
    portable formulation, uint8 layout) or "pallas" (the fused word-
    layout kernel on packed int32 views — the production TPU step).
    Chains run under lax.scan (compile once per length) and both outputs
    feed the serialising dependency so neither pass is DCE'd."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_jax import _bit_matrix_cached, _matrix_key
    from seaweedfs_tpu.ops.rs_pallas import fused_encode_words
    from seaweedfs_tpu.parallel.mesh import batched_encode_step

    matrix = gf256.parity_matrix(10, 14)
    bm = jnp.asarray(_bit_matrix_cached(*_matrix_key(matrix)))
    if variant == "pallas":
        def stepfn(acc):  # acc: (B, 10, L//4) int32 word views
            out = fused_encode_words(matrix, acc, interpret=False)
            dep = out[0][0, 0, 0] ^ out[1][0, 0].astype(jnp.int32)
            return out, dep

        @jax.jit
        def gen(key):
            return jax.random.randint(key, (batch, 10, length // 4),
                                      -2**31, 2**31 - 1, dtype=jnp.int32)
    else:
        def stepfn(acc):
            out = batched_encode_step(bm, acc)
            dep = (out[0][0, 0, 0].astype(jnp.uint32)
                   ^ out[1][0, 0]).astype(jnp.uint8)
            return out, dep

        @jax.jit
        def gen(key):
            return jax.random.randint(key, (batch, 10, length), 0, 256,
                                      dtype=jnp.uint8)

    data = gen(jax.random.PRNGKey(1))
    np.asarray(data[0, 0, :8])

    def chain(k):
        def body(acc, _):
            out, dep = stepfn(acc)
            acc = acc.at[0, 0, 0].set(dep.astype(acc.dtype))
            return acc, out[1][0, 0]

        @jax.jit
        def f(x):
            _, tags = jax.lax.scan(body, x, None, length=k)
            return tags[-1]
        return f

    # timing jitter can push a two-point slope non-positive; retry until
    # a usable measurement lands
    for _ in range(3):
        per_step = _slope_time(chain, data, chains, reps)
        if per_step > 0:
            return (batch * 10 * length) / GIB / per_step
    return 0.0


def bench_rebuild_kernel(length: int, chains: tuple[int, int] = (8, 24),
                         reps: int = 3,
                         on_tpu: bool | None = None) -> float:
    """BASELINE config 3: device reconstruction throughput.  Hard
    direction: 4 DATA shards lost, rebuilt from 6 data + 4 parity
    survivors through the same bit-matmul kernel the encode uses, with
    the reconstruction matrix from rebuild_matrix (inverted survivor
    submatrix — the one-matmul form of klauspost Reconstruct).  Off-TPU
    the SWAR XLA apply serves (interpret-mode pallas is minutes/call)."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_pallas
    from seaweedfs_tpu.ops.rs_jax import apply_matrix_swar
    from seaweedfs_tpu.parallel.batched_encode import rebuild_matrix

    if on_tpu is None:
        from seaweedfs_tpu.util.platform import on_tpu as _on_tpu

        on_tpu = _on_tpu()
    present = [4, 5, 6, 7, 8, 9, 10, 11, 12, 13]  # data 0-3 lost
    _, matrix = rebuild_matrix(present, [0, 1, 2, 3])
    apply = (rs_pallas.apply_matrix_pallas if on_tpu
             else apply_matrix_swar)

    @jax.jit
    def gen(key):
        return jax.random.randint(key, (10, length), 0, 256,
                                  dtype=jnp.uint8)

    data = gen(jax.random.PRNGKey(2))
    np.asarray(data[0, :8])

    def chain(k):
        @jax.jit
        def f(x):
            acc, out = x, None
            for _ in range(k):
                out = apply(matrix, acc)
                acc = acc.at[0, 0].set(out[0, 0])
            return out[0, :8]
        return f

    per_step = _slope_time(chain, data, chains, reps)
    if per_step <= 0:
        return 0.0
    return (10 * length) / GIB / per_step


def _write_volume(base: str, n_bytes: int, seed: int = 0,
                  block: int = 16 << 20):
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        left = n_bytes
        while left > 0:
            n = min(block, left)
            f.write(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
            left -= n


def bench_e2e_disk(n_vols: int, vol_bytes: int, workdir: str,
                   warm: bool = True, mesh=None) -> float:
    """Wall-clock GiB/s of the streaming pipeline: .dat files -> 14 shard
    files each, including all file I/O and host<->device transfer."""
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes

    if warm:
        wbase = os.path.join(workdir, "warm")
        _write_volume(wbase, 60 << 20, seed=99)
        encode_volumes([wbase], mesh=mesh)  # compile at production shapes
        _cleanup(workdir, "warm")
    bases = []
    for i in range(n_vols):
        base = os.path.join(workdir, f"bvol{i}")
        _write_volume(base, vol_bytes, seed=i)
        bases.append(base)
    t0 = time.perf_counter()
    encode_volumes(bases, mesh=mesh)
    dt = time.perf_counter() - t0
    for i in range(n_vols):
        _cleanup(workdir, f"bvol{i}")
    return n_vols * vol_bytes / GIB / dt


def bench_e2e_default(vol_bytes: int, workdir: str
                      ) -> tuple[float, dict]:
    """Wall-clock GiB/s of the DEFAULT ec.encode path — write_ec_files
    with the link-throughput auto-selected backend — plus the host
    pipeline's per-stage busy fractions for the best run.  This is the
    number that must never lose to the host codec (e2e_vs_cpu_e2e >= 1).
    The selection probes (link + host codec) are warmed first: a daemon
    pays them once per TTL window, not per encode."""
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes
    from seaweedfs_tpu.storage.erasure_coding import encoder as ec_encoder
    from seaweedfs_tpu.util.platform import prefer_batched_encode

    batched = prefer_batched_encode()  # warm link/codec probes
    base = os.path.join(workdir, "defvol")
    _write_volume(base, vol_bytes, seed=11)
    best, stages = 0.0, {}
    for _ in range(3):
        st: dict = {}
        t0 = time.perf_counter()
        if batched:
            ec_encoder.write_ec_files(base)
        else:  # the host pipeline IS the default; capture its stages
            encode_volumes([base], host_codec=True, stage_stats=st)
        rate = vol_bytes / GIB / (time.perf_counter() - t0)
        if rate > best:
            best, stages = rate, st
    _cleanup(workdir, "defvol")
    return best, stages


def bench_e2e_scale(n_vols: int, vol_bytes: int, workdir: str
                    ) -> tuple[float, float, dict]:
    """BASELINE config-4 scale validation: >=100 volumes / >=8 GiB
    through ONE pipeline run — the host-codec compute stage drives the
    same reader/slots/CRC-combine machinery at full volume count and
    byte volume (a link-capped device run at this size proves only that
    the link is slow).  Returns
    (GiB/s, peak_rss_mb, per-stage busy stats) — the stage stats name
    the bottleneck at scale instead of leaving it to conjecture."""
    import resource

    from seaweedfs_tpu.parallel.batched_encode import encode_volumes

    bases = []
    for i in range(n_vols):
        base = os.path.join(workdir, f"svol{i}")
        _write_volume(base, vol_bytes, seed=1000 + i)
        bases.append(base)
    st: dict = {}
    t0 = time.perf_counter()
    encode_volumes(bases, host_codec=True, stage_stats=st)
    dt = time.perf_counter() - t0
    # realised write amplification of the seal-then-encode path: every
    # .dat byte is written once at ingest, read back at seal time, and
    # written again across 14 shard files — the floor inline EC removes
    logical = physical = 0
    for base in bases:
        logical += os.path.getsize(base + ".dat")
        for ext in [f".ec{j:02d}" for j in range(14)] + [".ecx", ".vif"]:
            if os.path.exists(base + ext):
                physical += os.path.getsize(base + ext)
    st["write_amp"] = (round((logical + physical) / logical, 3)
                       if logical else 0.0)
    for i in range(n_vols):
        _cleanup(workdir, f"svol{i}")
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    return n_vols * vol_bytes / GIB / dt, peak_rss_mb, st


# Child process of the device-scale curve: the XLA device count is
# fixed at backend init, so every mesh width needs its own interpreter.
# argv: n_devices workdir n_vols vol_bytes repo_root
_SCALE_CHILD = r"""
import json, os, sys, time
n, workdir = int(sys.argv[1]), sys.argv[2]
n_vols, vol_bytes = int(sys.argv[3]), int(sys.argv[4])
sys.path.insert(0, sys.argv[5])
import jax
from bench import GIB, _cleanup, _write_volume
from seaweedfs_tpu.parallel.batched_encode import encode_volumes
from seaweedfs_tpu.parallel.mesh import make_ec_mesh
mesh = make_ec_mesh(jax.devices("cpu"))
assert mesh.devices.size == n, (mesh.devices.shape, n)
wbases = []
for i in range(min(n_vols, 4)):
    b = os.path.join(workdir, "scw%d_%d" % (n, i))
    _write_volume(b, vol_bytes, seed=40 + i)
    wbases.append(b)
encode_volumes(wbases, mesh=mesh)  # warm the per-geometry compile
_cleanup(workdir, "scw%d_" % n)
bases = []
for i in range(n_vols):
    b = os.path.join(workdir, "scv%d_%d" % (n, i))
    _write_volume(b, vol_bytes, seed=i)
    bases.append(b)
st = {}
t0 = time.perf_counter()
encode_volumes(bases, mesh=mesh, stage_stats=st)
dt = time.perf_counter() - t0
_cleanup(workdir, "scv%d_" % n)
print(json.dumps({"gibps": n_vols * vol_bytes / GIB / dt,
                  "backend": st.get("backend"),
                  "crc_path": st.get("crc_path"),
                  "devices": st.get("devices")}))
"""


def bench_device_scale_curve(workdir: str, vol_bytes: int = 4 << 20,
                             n_vols: int = 16,
                             counts=(1, 2, 4)) -> dict:
    """Per-device-count scaling of the sharded dispatch path on the CPU
    harness: one subprocess per mesh width (1/2/4 virtual devices via
    --xla_force_host_platform_device_count), WEED_EC_DEVICE_SHARD pinned
    to the width so the shard_map partitioning is what is measured.
    Returns {"1": GiB/s, "2": ..., "4": ...} (None where a width
    failed)."""
    import re as _re
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    curve: dict = {}
    for n in counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = _re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                        env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
        env["WEED_EC_DEVICE_SHARD"] = str(n)
        try:
            out = subprocess.run(
                [sys.executable, "-c", _SCALE_CHILD, str(n), workdir,
                 str(n_vols), str(vol_bytes), root],
                env=env, cwd=root, capture_output=True, text=True,
                timeout=600, check=True)
            payload = json.loads(out.stdout.strip().splitlines()[-1])
            curve[str(n)] = round(payload["gibps"], 3)
        except Exception as e:  # one width failing shouldn't kill the run
            print(f"note: scale-curve width {n} failed: {e}",
                  file=sys.stderr)
            curve[str(n)] = None
    return curve


def bench_e2e_device_scale(n_vols: int, vol_bytes: int, workdir: str,
                           link_capped: bool) -> tuple[float, dict]:
    """100-volume count through the DEVICE-dispatch pipeline path:
    validates the slot/inflight/completion machinery at volume-count
    scale.  Runs on the real device when the link allows; on a CPU-device
    mesh when the link caps transfers (where a real-device run would
    only re-measure the slow link).  Returns (GiB/s, stage stats — the
    device pipeline's backend, per-stage busy fractions and slab-pool
    counters for this phase)."""
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes

    mesh = None
    if link_capped:
        import jax

        from seaweedfs_tpu.parallel.mesh import make_ec_mesh

        # the EC mesh (WEED_EC_DEVICE_SHARD): on a CPU harness "auto"
        # caps the shard width at the usable cores — virtual devices
        # beyond that only add partitioning overhead, and a 1-device
        # mesh restores the zero-copy dlpack H2D path
        mesh = make_ec_mesh(jax.devices("cpu"))
    # Warm at the MEASURED shape: the persistent parity step compiles per
    # (k, batch) geometry, and this phase's small volumes compact to a
    # shorter k than the 60 MB generic warm volume — warming there would
    # leave this shape's trace+compile inside the timed window.
    wbases = []
    for i in range(min(n_vols, 6)):
        wb = os.path.join(workdir, f"dwarm{i}")
        _write_volume(wb, vol_bytes, seed=500 + i)
        wbases.append(wb)
    encode_volumes(wbases, mesh=mesh)
    _cleanup(workdir, "dwarm")
    bases = []
    for i in range(n_vols):
        base = os.path.join(workdir, f"dvol{i}")
        _write_volume(base, vol_bytes, seed=i)
        bases.append(base)
    st: dict = {}
    t0 = time.perf_counter()
    encode_volumes(bases, mesh=mesh, stage_stats=st)
    dt = time.perf_counter() - t0
    _cleanup(workdir, "dvol")
    return n_vols * vol_bytes / GIB / dt, st


def bench_maintenance_deep_scrub(n_vols: int, vol_bytes: int,
                                 workdir: str,
                                 link_capped: bool) -> tuple[float, dict]:
    """Curator deep-scrub verification rate: spans from every volume's
    14 shard files re-encoded through the persistent device parity step
    and chained-CRC-checked against the .vif records, batching spans
    ACROSS volumes into one compiled geometry (maintenance/deep_scrub).
    Returns (GiB/s over shard bytes read, stage stats — backend, batch
    counts, per-stage busy fractions, slab-pool counters)."""
    from seaweedfs_tpu.maintenance.deep_scrub import (deep_scrub,
                                                      local_target)
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes
    from seaweedfs_tpu.storage.erasure_coding.encoder import \
        save_volume_info

    mesh = None
    if link_capped:
        import jax

        from seaweedfs_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(jax.devices("cpu"))
    bases = []
    for i in range(n_vols):
        base = os.path.join(workdir, f"scrubvol{i}")
        _write_volume(base, vol_bytes, seed=900 + i)
        bases.append(base)
    crc_map = encode_volumes(bases, mesh=mesh)
    for base in bases:
        save_volume_info(base, version=3,
                         extra={"shard_crc32c": crc_map[base]})
    # warm at the measured geometry: the parity step compiles per
    # (k, batch) shape, and batch size follows the unit count
    deep_scrub([local_target(b, i + 1) for i, b in enumerate(bases)],
               mesh=mesh)
    targets = [local_target(b, i + 1) for i, b in enumerate(bases)]
    st: dict = {}
    t0 = time.perf_counter()
    out = deep_scrub(targets, mesh=mesh, stage_stats=st)
    dt = time.perf_counter() - t0
    _cleanup(workdir, "scrubvol")
    if out["corrupt"]:
        raise RuntimeError(f"scrub flagged fresh volumes: {out}")
    return out["scrubbed_bytes"] / GIB / dt, st


def bench_cpu_e2e(vol_bytes: int, workdir: str, reps: int = 2) -> float:
    """The reference architecture end-to-end: synchronous per-row host loop
    with the AVX2 codec (ec_encoder.go:194-231 semantics)."""
    from seaweedfs_tpu.ops.codec import NativeEncoder
    from seaweedfs_tpu.storage.erasure_coding import encoder as ec_encoder

    try:
        enc = NativeEncoder(10, 4)
    except RuntimeError:
        return 0.0
    base = os.path.join(workdir, "cpuvol")
    _write_volume(base, vol_bytes, seed=7)
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        ec_encoder.write_ec_files(base, encoder=enc, batched=False)
        best = max(best, vol_bytes / GIB / (time.perf_counter() - t0))
    _cleanup(workdir, "cpuvol")
    return best


def _cleanup(workdir: str, prefix: str):
    for name in os.listdir(workdir):
        if name.startswith(prefix):
            os.unlink(os.path.join(workdir, name))


# Filled by _pick_workdir; reported in the result JSON so a slow e2e
# number can be traced to "the bench ran on spinning disk, not shm".
_WORKDIR_INFO: dict = {}


def _pick_workdir(need_bytes: int) -> str:
    for cand in ("/dev/shm", tempfile.gettempdir()):
        try:
            free = shutil.disk_usage(cand).free
        except OSError:
            continue
        if free > need_bytes * 2:
            _WORKDIR_INFO.update(
                {"dir": cand, "free_gb": round(free / GIB, 2),
                 "need_gb": round(need_bytes / GIB, 2)})
            return tempfile.mkdtemp(prefix="swbench", dir=cand)
    fallback = tempfile.mkdtemp(prefix="swbench")
    try:
        free = shutil.disk_usage(fallback).free
    except OSError:
        free = 0
    _WORKDIR_INFO.update(
        {"dir": os.path.dirname(fallback) or fallback, "cramped": True,
         "free_gb": round(free / GIB, 2),
         "need_gb": round(need_bytes / GIB, 2)})
    return fallback


def bench_inline_encode(n_vols: int = 2, vol_bytes: int = 24 << 20,
                        needle_bytes: int = 64 << 10, replicas: int = 3,
                        family: str = "rs_vandermonde") -> dict:
    """Inline write-path EC vs the legacy post-hoc pipeline on the same
    ingest volume.  The post-hoc arm reproduces what a replicated
    collection pays today: ``replicas`` copies of every .dat byte at
    ingest, then a seal-time read-back plus the 14-shard encode.  The
    inline arm streams the same bytes straight through the stripe
    accumulator — one durable pass, parity current at ack time.
    Reports GiB/s and realised write amplification for both arms."""
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes
    from seaweedfs_tpu.storage.erasure_coding.inline import InlineEcVolume
    from seaweedfs_tpu.storage.needle import Needle

    workdir = _pick_workdir(n_vols * vol_bytes * (replicas + 3))
    rng = np.random.default_rng(7)
    payloads = [rng.integers(0, 256, needle_bytes, dtype=np.uint8)
                .tobytes() for _ in range(8)]
    per_vol = max(1, vol_bytes // needle_bytes)
    out = {"volumes": n_vols, "needle_kb": needle_bytes >> 10,
           "replicas": replicas, "family": family}
    try:
        # -- inline arm: needles stream through the stripe writer ------------
        # Rates are taken per volume and the best volume reported: on a
        # loaded (or single-core) host the scheduler can steal an
        # arbitrary slice of any one volume's wall clock, and best-of-N
        # is the standard way to recover the intrinsic rate.
        # needle construction (payload copy + client checksum) is the
        # uploader's cost, identical in both arms — build outside the
        # timed windows so the rates compare the server write paths
        def _mint():
            out = []
            for i in range(per_vol):
                n = Needle.create(payloads[i % len(payloads)])
                n.id, n.cookie = i + 1, 0x1234
                out.append(n)
            return out

        logical = 0
        amps = []
        inline_rates = []
        dt_all = 0.0
        for v in range(n_vols):
            ev = InlineEcVolume(workdir, "bench", 9000 + v,
                                family=family, create=True)
            needles = _mint()
            t0 = time.perf_counter()
            for n in needles:
                ev.write_needle(n, check_cookie=False)
            ev.writer.drain(tail=True)
            dt = time.perf_counter() - t0
            dt_all += dt
            logical += ev.writer.logical_size
            amps.append(ev.writer.write_amp())
            inline_rates.append(ev.writer.logical_size / GIB / dt)
            ev.close()
        out["gib"] = round(logical / GIB, 3)
        out["inline_gibps"] = round(max(inline_rates), 3)
        out["inline_gibps_agg"] = round(logical / GIB / dt_all, 3)
        out["inline_write_amp"] = round(sum(amps) / len(amps), 3)

        # -- post-hoc arm: the same needle stream through the legacy
        # path — every needle lands in ``replicas`` .dat files at
        # ingest, then seal time reads one copy back and cuts the 14
        # shard files.  (A real cluster spreads the replica writes over
        # servers; the aggregate bytes moved are what this measures.)
        from seaweedfs_tpu.storage.volume import Volume

        bases = []
        posthoc_logical = 0
        posthoc_rates = []
        dt_all = 0.0
        for v in range(n_vols):
            needles = _mint()
            t0 = time.perf_counter()
            vols = [Volume(workdir, "ph", v * replicas + r + 1)
                    for r in range(replicas)]
            for n in needles:
                for vol in vols:
                    vol.write_needle(n, check_cookie=False)
                    # acked-write contract parity with the inline arm:
                    # the idx entry must reach the OS before the ack
                    # (the reference appends idx with a write syscall)
                    vol.nm.flush()
            base = vols[0].file_name()
            vol_logical = os.path.getsize(base + ".dat")
            posthoc_logical += vol_logical
            for vol in vols:
                vol.close()
            encode_volumes([base], host_codec=True)
            dt = time.perf_counter() - t0
            dt_all += dt
            posthoc_rates.append(vol_logical / GIB / dt)
            bases.append(base)
        physical = 0
        for base in bases:
            for ext in [f".ec{sid:02d}" for sid in range(14)] + [".ecx"]:
                if os.path.exists(base + ext):
                    physical += os.path.getsize(base + ext)
        for v in range(n_vols):
            for r in range(replicas):
                rb = os.path.join(workdir, f"ph_{v * replicas + r + 1}")
                for ext in (".dat", ".idx"):
                    if os.path.exists(rb + ext):
                        physical += os.path.getsize(rb + ext)
        out["posthoc_gibps"] = round(max(posthoc_rates), 3)
        out["posthoc_gibps_agg"] = round(posthoc_logical / GIB / dt_all, 3)
        out["posthoc_write_amp"] = round(physical / posthoc_logical, 3)
        out["inline_vs_posthoc"] = (
            round(out["inline_gibps"] / out["posthoc_gibps"], 3)
            if out["posthoc_gibps"] else 0.0)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return out


def bench_small_file(num_files: int) -> tuple[float, float, float]:
    """Small-file data plane (weed benchmark, 1 KB c=16) through the
    native engine's fast-path port — the reference README's headline
    load test (command/benchmark.go; README.md:342-391).  Returns
    (writes/s, framed reads/s, plain-HTTP reads/s); zeros when the
    native library is missing."""
    from seaweedfs_tpu.storage import native_engine

    if not native_engine.available():
        return 0.0, 0.0, 0.0
    import tempfile

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    workdir = tempfile.mkdtemp(prefix="swbench_sf_")
    master = MasterServer(port=0, pulse_seconds=1.0,
                          volume_size_limit_mb=1024)
    master.start()
    vs = VolumeServer([workdir], master.address, port=0,
                      pulse_seconds=1.0, max_volume_counts=[16],
                      enable_tcp=True)
    vs.start()
    vs.heartbeat_once()
    try:
        from seaweedfs_tpu.benchmark import _run_native

        w, r = _run_native(master.address, num_files, 1024, 16, 0, "000",
                           True, True, 1000, http_phase=True)
        write_rps = w.requests / w.seconds if w.seconds else 0.0
        read_rps = r.requests / r.seconds if r.seconds else 0.0
        return write_rps, read_rps, getattr(r, "http_rps", 0.0)
    finally:
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def bench_ec_degraded_read(num_files: int = 2000,
                           read_reqs: int = 10000
                           ) -> tuple[float, float, float, dict]:
    """Degraded EC reads: write 1 KB needles, ec.encode the volume, then
    KILL the shards holding the data (delete the files + unmount) and
    measure the reconstruct-path read rate — every read regenerates its
    span through the fast degraded-read path (ec_volume.py
    _recover_span: decode-plan cache + recovered-block LRU +
    single-flight; store_ec.go:328-382's
    recoverOneRemoteEcShardInterval).  This is the latency that matters
    mid-incident.  Also measures the NATIVE port's degraded reads (the
    engine reconstructs missing spans from 10 local survivors in C++).
    Returns (http_reads/s, http_p99_ms, native_reads/s, stage_stats);
    the Python HTTP path runs with or without the native engine — only
    the native column is zeroed when the library is missing."""
    from seaweedfs_tpu.storage import native_engine

    import tempfile

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.shell import commands as sh
    from seaweedfs_tpu.volume_server.server import VolumeServer

    workdir = tempfile.mkdtemp(prefix="swbench_deg_")
    master = MasterServer(port=0, pulse_seconds=1.0,
                          volume_size_limit_mb=1024)
    master.start()
    vs = VolumeServer([workdir], master.address, port=0,
                      pulse_seconds=1.0, max_volume_counts=[16],
                      enable_tcp=True)
    vs.start()
    vs.heartbeat_once()
    try:
        rng = np.random.default_rng(3)
        payload = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()

        from seaweedfs_tpu.rpc.http_rpc import RpcError

        def call_retry(url, path, *args, **kw):
            # earlier bench stages can leave the (shared) box briefly
            # catatonic; a transient connect timeout (RpcError 503
            # "cannot reach") must not kill the whole stage
            for attempt in range(3):
                try:
                    return call(url, path, *args, timeout=60, **kw)
                except RpcError as e:
                    if attempt == 2 or e.status != 503:
                        raise
                    time.sleep(1.0)

        fids = []
        vid = None
        for _ in range(num_files):
            a = call_retry(master.address, "/dir/assign")
            if vid is None:
                vid = int(a["fid"].split(",")[0])
            if int(a["fid"].split(",")[0]) != vid:
                continue  # keep one volume so the kill set is exact
            call_retry(a["url"], f"/{a['fid']}", raw=payload,
                       method="POST")
            fids.append(a["fid"])
        env = sh.CommandEnv(master.address)
        sh.ec_encode(env, vid)
        vs.heartbeat_once()
        # kill the data shards that hold the needles: num_files KB fits
        # in the first few 1 MB blocks, i.e. shards 0..ceil(MB)-1; kill
        # 4 so every read reconstructs from the 10 survivors
        kill = [0, 1, 2, 3]
        call_retry(vs.store.url, "/admin/ec/unmount",
                   {"volume": vid, "shard_ids": kill})
        call_retry(vs.store.url, "/admin/ec/delete_shards",
                   {"volume": vid, "shard_ids": kill})
        vs.heartbeat_once()
        # sanity: a read still answers the original bytes
        got = call_retry(vs.store.url, f"/{fids[0]}")
        assert got == payload, "degraded read returned wrong bytes"

        from seaweedfs_tpu.storage.erasure_coding.recover import \
            STATS as RECOVER_STATS

        RECOVER_STATS.reset()  # count only the timed phase

        import concurrent.futures as cf

        lat: list[float] = []
        lat_lock = __import__("threading").Lock()

        def one(i: int):
            fid = fids[i % len(fids)]
            t0 = time.perf_counter()
            try:
                call(vs.store.url, f"/{fid}")
            except RpcError as e:
                if e.status != 503:
                    raise
                call_retry(vs.store.url, f"/{fid}")
            dt = (time.perf_counter() - t0) * 1000.0
            with lat_lock:
                lat.append(dt)

        t0 = time.perf_counter()
        with cf.ThreadPoolExecutor(max_workers=16) as pool:
            list(pool.map(one, range(read_reqs)))
        secs = time.perf_counter() - t0
        lat.sort()
        p99 = lat[int(len(lat) * 0.99) - 1] if lat else 0.0
        stages = RECOVER_STATS.snapshot(wall=secs)

        # span-derived breakdown: re-run a short fully-sampled probe so
        # the timed storm above pays zero recorder cost, then read the
        # fetch/decode/serve split straight out of the trace recorder
        from seaweedfs_tpu import tracing
        tracing.RECORDER.reset()
        prev_sample = os.environ.get("WEED_TRACE_SAMPLE")
        os.environ["WEED_TRACE_SAMPLE"] = "1"
        try:
            with cf.ThreadPoolExecutor(max_workers=16) as pool:
                list(pool.map(one, range(min(500, read_reqs))))
        finally:
            if prev_sample is None:
                os.environ.pop("WEED_TRACE_SAMPLE", None)
            else:
                os.environ["WEED_TRACE_SAMPLE"] = prev_sample
        stages["trace_spans"] = tracing.RECORDER.aggregate("ec.recover.")
        tracing.RECORDER.reset()

        # native-port degraded reads: C++ reconstructs each span from
        # the 10 local survivors (zero GIL involvement)
        native_rps = 0.0
        if (native_engine.available()
                and getattr(vs, "_native_owner", False) and vs.tcp_port):
            nsecs, nerrs, _ = native_engine.bench(
                "127.0.0.1", vs.tcp_port, "R", fids,
                max(read_reqs, 20000), 0, 16)
            nreq = max(read_reqs, 20000)
            if nerrs > nreq * 0.01:
                print(f"note: native degraded read errors: {nerrs}",
                      file=sys.stderr)
            native_rps = (nreq - nerrs) / nsecs if nsecs else 0.0
        return read_reqs / secs, p99, native_rps, stages
    finally:
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def bench_ec_rebuild(data_bytes: int = 24 << 20) -> dict:
    """Repair-optimal rebuilds across the coding tier: encode the same
    volume with every registered code family, delete ONE data shard, run
    the family's planned rebuild, and report bytes-read-per-rebuilt-byte
    (read amplification) plus throughputs.  RS/Cauchy decode plans read
    k=10 full survivors (amp 10.0); pm_msr's projection repair reads
    1/alpha of d=8 helpers (amp 2.0) — the regenerating-code claim is
    the read_amp_vs_rs <= 0.6 line.  Rebuilt bytes are CRC-verified
    against the encode-time record, so the amp numbers only count when
    the repair is byte-exact."""
    import tempfile

    from seaweedfs_tpu.storage.erasure_coding import to_ext
    from seaweedfs_tpu.storage.erasure_coding.codes import (
        family_names, get_family)
    from seaweedfs_tpu.storage.erasure_coding.encoder import (
        rebuild_ec_files, write_ec_files)
    from seaweedfs_tpu.storage.tools import shard_file_crc32c

    workdir = tempfile.mkdtemp(prefix="swbench_ecrb_")
    rng = np.random.default_rng(0x5EA)
    payload = rng.integers(0, 256, data_bytes, dtype=np.uint8).tobytes()
    families: dict[str, dict] = {}
    lost = 0  # a data shard: the worst case for every family's planner
    try:
        for name in family_names():
            fam = get_family(name)
            base = os.path.join(workdir, name, "v1")
            os.makedirs(os.path.dirname(base), exist_ok=True)
            with open(base + ".dat", "wb") as f:
                f.write(payload)
            t0 = time.perf_counter()
            write_ec_files(base, family=fam,
                           large_block_size=1 << 20,
                           small_block_size=64 << 10)
            enc_s = time.perf_counter() - t0
            want = shard_file_crc32c(base + to_ext(lost))
            os.remove(base + to_ext(lost))
            stats: dict = {}
            t0 = time.perf_counter()
            crcs = rebuild_ec_files(base, family=fam, stats=stats)
            reb_s = time.perf_counter() - t0
            families[name] = {
                "plan": stats["plan"],
                "read_amp": stats["read_amp"],
                "read_mib": round(stats["read_bytes"] / (1 << 20), 2),
                "rebuilt_mib": round(stats["rebuilt_bytes"] / (1 << 20), 2),
                "rebuild_mib_s": round(
                    stats["rebuilt_bytes"] / reb_s / (1 << 20), 1)
                    if reb_s else 0.0,
                "encode_mib_s": round(
                    data_bytes / enc_s / (1 << 20), 1) if enc_s else 0.0,
                "crc_ok": crcs.get(lost) == want,
            }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    rs_amp = families.get("rs_vandermonde", {}).get("read_amp") or 0.0
    for r in families.values():
        r["read_amp_vs_rs"] = (round(r["read_amp"] / rs_amp, 3)
                               if rs_amp else 0.0)
    return {
        "metric": "ec_rebuild_read_amp",
        "unit": "bytes_read_per_rebuilt_byte",
        "data_mib": round(data_bytes / (1 << 20), 1),
        "lost_shard": lost,
        "families": families,
        "pm_msr_vs_rs_read_amp":
            families.get("pm_msr", {}).get("read_amp_vs_rs", 0.0),
    }


def bench_master_failover(warmup_acks: int = 25,
                          settle_acks: int = 25) -> dict:
    """Control-plane HA cost: write-unavailability window across a raft
    leader kill.  Three in-process masters replicate the control FSM; a
    writer assigns fids and stores 1 KB needles through whichever master
    answers, time-stamping every ack.  Mid-storm the leader is killed
    (server + raft stopped, no goodbye), and the window is the gap from
    the last ack before the kill to the first ack after re-election —
    the number a client actually experiences.  Reported in the bench
    JSON so every future PR sees the failover cost."""
    import socket
    import tempfile

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    socks = []
    for _ in range(3):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    addrs = [f"127.0.0.1:{p}" for p in ports]

    workdir = tempfile.mkdtemp(prefix="swbench_failover_")
    masters = []
    for i, p in enumerate(ports):
        d = os.path.join(workdir, f"m{i}")
        os.makedirs(d)
        m = MasterServer(port=p, peers=list(addrs), raft_dir=d,
                         raft_election_timeout=0.3, pulse_seconds=0.5,
                         volume_size_limit_mb=256)
        m.start()
        masters.append(m)
    vdir = os.path.join(workdir, "vol")
    os.makedirs(vdir)
    vs = VolumeServer([vdir], ",".join(addrs), port=0,
                      pulse_seconds=0.3, max_volume_counts=[8])
    vs.start()
    vs.heartbeat_once()

    payload = b"x" * 1024
    alive = list(masters)

    def write_once(timeout: float) -> bool:
        # one assign+store attempt through any answering master;
        # counts as an ack only when the needle is durably stored
        for m in alive:
            try:
                a = call(m.address, "/dir/assign", timeout=timeout)
                call(a["url"], f"/{a['fid']}", raw=payload,
                     method="POST", timeout=timeout)
                return True
            except RpcError:
                continue
        return False

    acks: list[float] = []

    def storm(target: int, deadline_s: float) -> None:
        deadline = time.monotonic() + deadline_s
        got = 0
        while got < target and time.monotonic() < deadline:
            if write_once(timeout=2):
                acks.append(time.monotonic())
                got += 1
            else:
                time.sleep(0.02)

    window = -1.0
    elections = 0
    try:
        storm(warmup_acks, deadline_s=30)
        leader = next((m for m in masters if m.raft.is_leader), None)
        if leader is not None and acks:
            pre_term = max(m.raft.term for m in masters)
            alive = [m for m in masters if m is not leader]
            last_before = acks[-1]
            leader.stop()
            storm(settle_acks, deadline_s=30)
            after = [t for t in acks if t > last_before]
            if after:
                window = after[0] - last_before
            elections = max(m.raft.term for m in alive) - pre_term
    finally:
        vs.stop()
        for m in alive:
            m.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "metric": "master_failover_unavailability",
        "unit": "seconds",
        "masters": len(masters),
        "election_timeout_s": 0.3,
        "acked_writes": len(acks),
        "terms_advanced": elections,
        "unavailability_window_s": round(window, 3),
    }


def bench_qos_isolation(num_files: int = 800, read_reqs: int = 3000,
                        scrub_vols: int = 3,
                        scrub_vol_bytes: int = 8 << 20) -> dict:
    """QoS foreground/background isolation: the degraded-read storm
    (bench_ec_degraded_read's incident path) measured once on an idle
    box and once while a device-batched deep scrub grinds in the same
    process.  The scrub's encode batches yield at their lane
    checkpoints whenever a recover decode holds the foreground lane
    (qos/lanes.py), so the with-scrub p99 should stay near the idle
    p99 while the scrub is visibly paced.  Returns fg rps/p99 for both
    runs, the concurrent scrub rate, and the lane counters
    (preemptions / background stall) accrued during the storm."""
    import tempfile
    import threading

    from seaweedfs_tpu.maintenance.deep_scrub import (deep_scrub,
                                                      local_target)
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes
    from seaweedfs_tpu.qos.lanes import LANES
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.shell import commands as sh
    from seaweedfs_tpu.storage.erasure_coding.encoder import \
        save_volume_info
    from seaweedfs_tpu.volume_server.server import VolumeServer

    workdir = tempfile.mkdtemp(prefix="swbench_qos_")
    # the recovered-block LRU would absorb the whole needle set after
    # one pass and idle the foreground lane; disable it so both storms
    # measure real decode work
    prev_cache = os.environ.get("WEED_EC_RECOVER_CACHE_MB")
    os.environ["WEED_EC_RECOVER_CACHE_MB"] = "0"
    master = MasterServer(port=0, pulse_seconds=1.0,
                          volume_size_limit_mb=1024)
    master.start()
    vs = VolumeServer([workdir], master.address, port=0,
                      pulse_seconds=1.0, max_volume_counts=[16],
                      enable_tcp=True)
    vs.start()
    vs.heartbeat_once()
    try:
        rng = np.random.default_rng(11)
        payload = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()

        def call_retry(url, path, *args, **kw):
            for attempt in range(3):
                try:
                    return call(url, path, *args, timeout=60, **kw)
                except RpcError as e:
                    if attempt == 2 or e.status != 503:
                        raise
                    time.sleep(1.0)

        fids = []
        vid = None
        for _ in range(num_files):
            a = call_retry(master.address, "/dir/assign")
            if vid is None:
                vid = int(a["fid"].split(",")[0])
            if int(a["fid"].split(",")[0]) != vid:
                continue
            call_retry(a["url"], f"/{a['fid']}", raw=payload,
                       method="POST")
            fids.append(a["fid"])
        env = sh.CommandEnv(master.address)
        sh.ec_encode(env, vid)
        vs.heartbeat_once()
        kill = [0, 1, 2, 3]
        call_retry(vs.store.url, "/admin/ec/unmount",
                   {"volume": vid, "shard_ids": kill})
        call_retry(vs.store.url, "/admin/ec/delete_shards",
                   {"volume": vid, "shard_ids": kill})
        vs.heartbeat_once()
        got = call_retry(vs.store.url, f"/{fids[0]}")
        assert got == payload, "degraded read returned wrong bytes"

        # background material: separate volumes the scrub loop chews on
        # while the storm runs; tiny spans/batches so the scrub takes
        # many lane checkpoints per pass instead of one long batch
        scrub_dir = os.path.join(workdir, "scrub")
        os.makedirs(scrub_dir, exist_ok=True)
        bases = []
        for i in range(scrub_vols):
            base = os.path.join(scrub_dir, f"qosvol{i}")
            _write_volume(base, scrub_vol_bytes, seed=1100 + i)
            bases.append(base)
        crc_map = encode_volumes(bases)
        for base in bases:
            save_volume_info(base, version=3,
                             extra={"shard_crc32c": crc_map[base]})
        targets = [local_target(b, i + 1) for i, b in enumerate(bases)]
        deep_scrub(targets, span_bytes=256 << 10, batch_units=4)  # warm

        import concurrent.futures as cf

        lat_lock = threading.Lock()

        def storm() -> tuple[float, float]:
            lat: list[float] = []

            def one(i: int):
                fid = fids[i % len(fids)]
                t0 = time.perf_counter()
                try:
                    call(vs.store.url, f"/{fid}")
                except RpcError as e:
                    if e.status != 503:
                        raise
                    call_retry(vs.store.url, f"/{fid}")
                dt = (time.perf_counter() - t0) * 1000.0
                with lat_lock:
                    lat.append(dt)

            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(max_workers=16) as pool:
                list(pool.map(one, range(read_reqs)))
            secs = time.perf_counter() - t0
            lat.sort()
            p99 = lat[int(len(lat) * 0.99) - 1] if lat else 0.0
            return read_reqs / secs, p99

        base_rps, base_p99 = storm()

        # concurrent run: scrub loops until the storm drains
        LANES.reset()
        stop = threading.Event()
        scrub_bytes = [0]
        scrub_secs = [0.0]

        def scrub_loop():
            while not stop.is_set():
                t0 = time.perf_counter()
                out = deep_scrub(targets, span_bytes=256 << 10,
                                 batch_units=4)
                scrub_secs[0] += time.perf_counter() - t0
                scrub_bytes[0] += out["scrubbed_bytes"]

        th = threading.Thread(target=scrub_loop, daemon=True)
        th.start()
        try:
            iso_rps, iso_p99 = storm()
        finally:
            stop.set()
            th.join(timeout=120)
        lanes = LANES.snapshot()
        scrub_gibps = (scrub_bytes[0] / GIB / scrub_secs[0]
                       if scrub_secs[0] else 0.0)
        return {
            "fg_rps": round(base_rps, 1),
            "fg_p99_ms": round(base_p99, 2),
            "fg_rps_with_scrub": round(iso_rps, 1),
            "fg_p99_ms_with_scrub": round(iso_p99, 2),
            "p99_ratio": (round(iso_p99 / base_p99, 2)
                          if base_p99 else 0.0),
            "scrub_gibps": round(scrub_gibps, 3),
            "scrub_passes_bytes": scrub_bytes[0],
            "lane_preemptions": lanes["preemptions"],
            "lane_bg_wait_seconds": lanes["background_wait_seconds"],
            "lane_bg_batches": lanes["background_batches"],
        }
    finally:
        if prev_cache is None:
            os.environ.pop("WEED_EC_RECOVER_CACHE_MB", None)
        else:
            os.environ["WEED_EC_RECOVER_CACHE_MB"] = prev_cache
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def _stage_fractions(spans: dict, roots: tuple) -> dict:
    """Render a RECORDER.aggregate() dict as per-stage fractions of the
    named root spans' total seconds (the gateway stage breakdown)."""
    total = sum(spans.get(r, {}).get("seconds", 0.0) for r in roots)
    out = {}
    for name, s in sorted(spans.items()):
        frac = (s["seconds"] / total) if total else 0.0
        out[name] = {"count": s["count"],
                     "seconds": round(s["seconds"], 4),
                     "fraction": round(frac, 3)}
    return out


def bench_s3_gateway(num_objects: int = 5000) -> dict:
    """Small-object data plane through the S3 gateway vs the filer's own
    HTTP API — the gateway's overhead is auth + XML + key mapping on top
    of the same save_bytes/read_bytes machinery (object bytes ride the
    filer's chunk paths, which use the native fast path when available).
    1 KB objects, keep-alive connections, 8 concurrent workers.
    The client is a hand-rolled HTTP/1.1 loop over raw sockets: client
    and daemons share one interpreter here, and http.client's
    email-parser header machinery costs as much GIL time per request as
    the entire gateway — the lean client measures the gateway, not the
    measurement.
    Returns {s3_put_rps, s3_get_rps, filer_put_rps, filer_get_rps}."""
    from seaweedfs_tpu.storage import native_engine  # noqa: F401

    import socket
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.filer.server import FilerServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.s3api.server import S3ApiServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    # earlier bench phases leave hundreds of thousands of live objects
    # (needle maps, filer entries); without a freeze every gen-2 GC pass
    # walks them all mid-request and the allocation-heavy gateway loop
    # triggers those passes constantly
    import gc
    gc.collect()
    gc.freeze()

    workdir = tempfile.mkdtemp(prefix="swbench_s3_")
    master = MasterServer(port=0, pulse_seconds=1.0,
                          volume_size_limit_mb=1024)
    master.start()
    vs = VolumeServer([workdir], master.address, port=0,
                      pulse_seconds=1.0, max_volume_counts=[16],
                      enable_tcp=True)
    vs.start()
    vs.heartbeat_once()
    filer = FilerServer(master.address, port=0)
    filer.start()
    s3 = S3ApiServer(filer, port=0)  # anonymous (no identities)
    s3.start()
    payload = b"s" * 1024
    out = {}
    try:
        def phase(address, method, path_of, nreq, body, workers=8):
            def worker(span):
                host, port = address.rsplit(":", 1)
                sock = socket.create_connection((host, int(port)),
                                                timeout=30)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                rfile = sock.makefile("rb", buffering=65536)
                head = f"{method} ".encode()
                tail = (f" HTTP/1.1\r\nHost: {host}\r\n"
                        f"Content-Length: {len(body or b'')}\r\n\r\n"
                        ).encode() + (body or b"")
                ok = 0
                readline = rfile.readline
                read = rfile.read
                for i in span:
                    sock.sendall(head + path_of(i).encode() + tail)
                    line = readline()
                    if not line:
                        break  # server dropped the connection
                    clen = 0
                    while True:
                        h = readline()
                        if h in (b"\r\n", b"\n", b""):
                            break
                        if h[:15].lower() == b"content-length:":
                            clen = int(h[15:])
                    if clen:
                        read(clen)
                    if line[9:12] in (b"200", b"201", b"204"):
                        ok += 1
                rfile.close()
                sock.close()
                return ok

            spans = [range(w, nreq, workers) for w in range(workers)]
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=workers) as pool:
                oks = sum(pool.map(worker, spans))
            secs = time.perf_counter() - t0
            if oks < nreq * 0.99:
                print(f"note: s3 bench {method} errors: {nreq - oks}",
                      file=sys.stderr)
            return oks / secs if secs else 0.0

        # bucket first
        phase(s3.address, "PUT", lambda i: "/bench", 1, b"")
        out["s3_put_rps"] = phase(
            s3.address, "PUT", lambda i: f"/bench/o{i}", num_objects,
            payload)
        out["s3_get_rps"] = phase(
            s3.address, "GET", lambda i: f"/bench/o{i}", num_objects,
            None)
        out["filer_put_rps"] = phase(
            filer.address, "PUT", lambda i: f"/bench2/o{i}", num_objects,
            payload)
        out["filer_get_rps"] = phase(
            filer.address, "GET", lambda i: f"/bench2/o{i}", num_objects,
            None)

        # span-derived stage breakdown (assign / upload / meta-save for
        # puts; lookup / fetch / read for gets): a short fully-sampled
        # probe with 8 KB bodies — past the inline limit, so the chunk
        # path and the fid lease are exercised — run AFTER the timed
        # phases, which therefore pay zero recorder cost
        from seaweedfs_tpu import tracing
        probe_payload = b"p" * 8192
        prev_sample = os.environ.get("WEED_TRACE_SAMPLE")
        os.environ["WEED_TRACE_SAMPLE"] = "1"
        try:
            tracing.RECORDER.reset()
            phase(filer.address, "PUT", lambda i: f"/probe/o{i}", 400,
                  probe_payload)
            put_spans = tracing.RECORDER.aggregate("filer.")
            tracing.RECORDER.reset()
            phase(filer.address, "GET", lambda i: f"/probe/o{i}", 400,
                  None)
            get_spans = tracing.RECORDER.aggregate("filer.")
            tracing.RECORDER.reset()
        finally:
            if prev_sample is None:
                os.environ.pop("WEED_TRACE_SAMPLE", None)
            else:
                os.environ["WEED_TRACE_SAMPLE"] = prev_sample
        out["gateway_stages"] = {
            "put": _stage_fractions(put_spans, ("filer.save",)),
            "get": _stage_fractions(
                get_spans,
                ("filer.lookup", "filer.read", "filer.stream")),
        }
        return out
    finally:
        s3.stop()
        filer.stop()
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)
        gc.unfreeze()


def bench_read_cache(num_objects: int = 3000, payload_bytes: int = 4096,
                     workers: int = 8) -> dict:
    """Cold vs warm GET storms through the unified read cache
    (cache/ package): a smallfile storm on the filer object-GET path
    (where a warm chunk-cache hit skips the internal filer->volume
    hop entirely), an S3 object-GET storm, and a direct volume-server
    needle storm, each run once with every cache tier cleared and once
    warm, with per-tier hit ratios from the cache's own accounting.
    4 KiB objects keep bodies past the filer inline limit so the chunk
    cache is actually on the path.  The direct needle storm is
    reported but not ratio-gated: the needle cache saves ~8 us of
    store work per request, which is real but small next to the
    ~100 us/request HTTP framing floor of the storm harness itself.
    Returns {smallfile_cold_rps, smallfile_warm_rps, warm_vs_cold,
    s3_get_cold_rps, s3_get_warm_rps, s3_warm_vs_cold,
    volume_get_cold_rps, volume_get_warm_rps, volume_warm_vs_cold,
    volume_cache, filer_cache}."""
    import socket
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.filer.server import FilerServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.s3api.server import S3ApiServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    import gc
    gc.collect()
    gc.freeze()

    workdir = tempfile.mkdtemp(prefix="swbench_rc_")
    master = MasterServer(port=0, pulse_seconds=1.0,
                          volume_size_limit_mb=1024)
    master.start()
    vs = VolumeServer([workdir], master.address, port=0,
                      pulse_seconds=1.0, max_volume_counts=[16],
                      enable_tcp=True)
    vs.start()
    vs.heartbeat_once()
    filer = FilerServer(master.address, port=0)
    filer.start()
    s3 = S3ApiServer(filer, port=0)
    s3.start()
    payload = b"r" * payload_bytes
    out: dict = {}
    try:
        def storm(address, method, path_of, nreq, body):
            def worker(span):
                host, port = address.rsplit(":", 1)
                sock = socket.create_connection((host, int(port)),
                                                timeout=30)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                rfile = sock.makefile("rb", buffering=65536)
                head = f"{method} ".encode()
                tail = (f" HTTP/1.1\r\nHost: {host}\r\n"
                        f"Content-Length: {len(body or b'')}\r\n\r\n"
                        ).encode() + (body or b"")
                ok = 0
                readline = rfile.readline
                read = rfile.read
                for i in span:
                    sock.sendall(head + path_of(i).encode() + tail)
                    line = readline()
                    if not line:
                        break
                    clen = 0
                    while True:
                        h = readline()
                        if h in (b"\r\n", b"\n", b""):
                            break
                        if h[:15].lower() == b"content-length:":
                            clen = int(h[15:])
                    if clen:
                        read(clen)
                    if line[9:12] in (b"200", b"201", b"204"):
                        ok += 1
                rfile.close()
                sock.close()
                return ok

            spans = [range(w, nreq, workers) for w in range(workers)]
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=workers) as pool:
                oks = sum(pool.map(worker, spans))
            secs = time.perf_counter() - t0
            if oks < nreq * 0.99:
                print(f"note: read-cache bench {method} errors: "
                      f"{nreq - oks}", file=sys.stderr)
            return oks / secs if secs else 0.0

        # -- smallfile storm on the filer object-GET path (gated) --------
        storm(filer.address, "PUT", lambda i: f"/rcache/f{i}",
              num_objects, payload)
        filer.chunk_cache.clear()
        vs.read_cache.clear()
        out["smallfile_cold_rps"] = storm(
            filer.address, "GET", lambda i: f"/rcache/f{i}", num_objects,
            None)
        out["smallfile_warm_rps"] = storm(
            filer.address, "GET", lambda i: f"/rcache/f{i}", num_objects,
            None)
        out["warm_vs_cold"] = (
            round(out["smallfile_warm_rps"] / out["smallfile_cold_rps"], 2)
            if out["smallfile_cold_rps"] else 0.0)

        # -- direct volume-server needle storm (reported, not gated) -----
        fids = []
        for _ in range(num_objects):
            a = call(master.address, "/dir/assign", timeout=10)
            fid = a["fid"]
            call(vs.address, f"/{fid}", raw=payload, method="POST",
                 timeout=10)
            fids.append(fid)
        vs.read_cache.clear()
        out["volume_get_cold_rps"] = storm(
            vs.address, "GET", lambda i: f"/{fids[i]}", num_objects, None)
        out["volume_get_warm_rps"] = storm(
            vs.address, "GET", lambda i: f"/{fids[i]}", num_objects, None)
        out["volume_warm_vs_cold"] = (
            round(out["volume_get_warm_rps"] / out["volume_get_cold_rps"],
                  2)
            if out["volume_get_cold_rps"] else 0.0)
        out["volume_cache"] = vs.read_cache.stats_snapshot()

        # -- S3 object-GET storm (filer chunk cache on the path) ---------
        storm(s3.address, "PUT", lambda i: "/rcache", 1, b"")
        storm(s3.address, "PUT", lambda i: f"/rcache/o{i}", num_objects,
              payload)
        filer.chunk_cache.clear()
        vs.read_cache.clear()
        out["s3_get_cold_rps"] = storm(
            s3.address, "GET", lambda i: f"/rcache/o{i}", num_objects,
            None)
        out["s3_get_warm_rps"] = storm(
            s3.address, "GET", lambda i: f"/rcache/o{i}", num_objects,
            None)
        out["s3_warm_vs_cold"] = (
            round(out["s3_get_warm_rps"] / out["s3_get_cold_rps"], 2)
            if out["s3_get_cold_rps"] else 0.0)
        out["filer_cache"] = filer.chunk_cache.stats_snapshot()
        return out
    finally:
        s3.stop()
        filer.stop()
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)
        gc.unfreeze()


def bench_small_file_secured(num_files: int) -> tuple[float, float]:
    """Small-file data plane under PRODUCTION configuration: JWT write
    signing + replication 001 — two volume servers (the second in a
    subprocess with its own native listener), every native write
    verified (HS256) and fanned out to the peer's fast-path port before
    acking (store_replicate.go:24-141).  Returns (writes/s, reads/s);
    zeros when unavailable.  Token lifetime is 3600 s so the up-front
    assign phase's tokens outlive the whole write phase."""
    from seaweedfs_tpu.storage import native_engine

    if not native_engine.available():
        return 0.0, 0.0
    import socket
    import struct
    import subprocess
    import tempfile

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.security import Guard
    from seaweedfs_tpu.security.jwt_auth import SigningKey, gen_write_jwt
    from seaweedfs_tpu.volume_server.server import VolumeServer

    key = "bench-secret"
    workdir = tempfile.mkdtemp(prefix="swbench_sec_")
    vs1_dir = os.path.join(workdir, "vs1")
    vs2_dir = os.path.join(workdir, "vs2")
    conf_dir = os.path.join(workdir, "conf")
    for d in (vs1_dir, vs2_dir, conf_dir):
        os.makedirs(d)
    with open(os.path.join(conf_dir, "security.toml"), "w") as f:
        f.write('[jwt.signing]\nkey = "%s"\n'
                'expires_after_seconds = 3600\n' % key)

    def guard():
        return Guard(signing_key=key, expires_after_seconds=3600)

    master = MasterServer(port=0, pulse_seconds=1.0,
                          volume_size_limit_mb=1024,
                          default_replication="001", guard=guard())
    master.start()
    vs = VolumeServer([vs1_dir], master.address, port=0,
                      pulse_seconds=1.0, max_volume_counts=[16],
                      enable_tcp=True, guard=guard())
    vs.start()
    vs.heartbeat_once()
    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "weed.py"), "volume",
         "-dir", vs2_dir, "-mserver", master.address, "-port", "0",
         "-tcp", "-pulseSeconds", "1"],
        cwd=conf_dir, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        env={**os.environ, "PYTHONPATH": repo})
    try:
        # wait for both servers to register (001 placement needs two)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                a = call(master.address, "/dir/assign?replication=001")
                if a.get("fid"):
                    break
            except Exception:
                pass
            time.sleep(0.5)

        signing = SigningKey(key, 3600)

        def probe_write(url: str, vid: int) -> bool:
            """One framed native write against url's fast path; True
            when the replicated write path is fully engaged (0)."""
            from seaweedfs_tpu.wdclient.volume_tcp_client import \
                VolumeTcpClient

            fid = f"{vid},deadbe{int(time.time()*1000)%0xFFFFFF:06x}"
            tok = gen_write_jwt(signing, fid)
            frame = f"W {fid} 5 {tok}\nprobe".encode()
            try:
                addr = VolumeTcpClient().tcp_address(url)
                host, port = addr.rsplit(":", 1)
                s = socket.create_connection((host, int(port)), timeout=5)
                try:
                    s.sendall(frame)
                    hdr = b""
                    while len(hdr) < 8:
                        c = s.recv(8 - len(hdr))
                        if not c:
                            return False
                        hdr += c
                    status, ln = struct.unpack(">II", hdr)
                    while ln > 0:
                        c = s.recv(ln)
                        if not c:
                            break
                        ln -= len(c)
                    return status == 0
                finally:
                    s.close()
            except OSError:
                return False

        def wait_replica_sets(by_server):
            """Until every assigned (url, vid) serves replicated writes
            natively (replica sets propagate on heartbeat cadence)."""
            pairs = {(url, int(fid.split(",")[0]))
                     for url, fids in by_server.items()
                     for fid in (f.split(" ")[0] for f in fids)}
            deadline = time.time() + 30
            pending = set(pairs)
            while pending and time.time() < deadline:
                vs.heartbeat_once()
                pending = {(url, vid) for url, vid in pending
                           if not probe_write(url, vid)}
                if pending:
                    time.sleep(1.0)

        from seaweedfs_tpu.benchmark import _run_native

        w, r = _run_native(master.address, num_files, 1024, 16, 0,
                           "001", True, True, 1000,
                           pre_phase_hook=wait_replica_sets)
        write_rps = w.requests / w.seconds if w.seconds else 0.0
        read_rps = r.requests / r.seconds if r.seconds else 0.0
        if w.errors > w.requests * 0.01:
            print(f"note: secured bench write errors: {w.errors}",
                  file=sys.stderr)
        return write_rps, read_rps
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def _cleanup_scale_workdirs():
    """Sweep leftover weed-scale-* workdirs: scale.up subprocess spawns
    make one per job, and a killed bench must not leak them."""
    import glob
    import tempfile

    base = os.environ.get("WEED_SCALE_DIR") or tempfile.gettempdir()
    for d in glob.glob(os.path.join(base, "weed-scale-*")):
        shutil.rmtree(d, ignore_errors=True)


def bench_cluster_scale(counts: tuple = (1, 4, 16),
                        num_objects: int = 300,
                        rate_rps: float = 400.0,
                        duration_s: float = 3.0) -> dict:
    """Throughput/latency scale curve over volume-server count: the
    same seeded zipfian replay (loadgen) runs closed-loop against a
    mini-cluster at each VS count, reporting rps and p99 per point.
    On the 1-core CI harness the absolute multipliers are meaningless
    (all servers share one core), so `gated` marks whether the host
    had >= 2 cores — the acceptance gate only applies when it did."""
    import tempfile

    from seaweedfs_tpu import loadgen
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc import policy as _policy
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    cores = len(os.sched_getaffinity(0))
    schedule = loadgen.build_schedule(
        duration_s=duration_s, rate_rps=rate_rps,
        n_objects=num_objects, write_ratio=0.0)
    payload = b"s" * 2048
    curve: dict = {}
    for n_servers in counts:
        _policy.reset_state()
        workdir = tempfile.mkdtemp(prefix="swbench_scale_")
        master = MasterServer(port=0, pulse_seconds=1.0,
                              volume_size_limit_mb=1024,
                              maintenance_interval=3600.0)
        master.start()
        servers = []
        try:
            for i in range(n_servers):
                d = os.path.join(workdir, f"vs{i}")
                os.makedirs(d)
                vs = VolumeServer([d], master.address, port=0,
                                  pulse_seconds=1.0,
                                  max_volume_counts=[16])
                vs.start()
                vs.heartbeat_once()
                servers.append(vs)
            urls: list = [None] * num_objects
            for i in range(num_objects):
                a = call(master.address, "/dir/assign", timeout=30)
                call(a["url"], f"/{a['fid']}", raw=payload,
                     method="POST", timeout=30)
                urls[i] = (a["url"], a["fid"])
            for vs in servers:
                vs.heartbeat_once()

            def send(req):
                url, fid = urls[req.obj % num_objects]
                try:
                    call(url, f"/{fid}", timeout=30)
                except RpcError as e:
                    if e.status != 503:
                        raise
                    time.sleep(0.05)
                    call(url, f"/{fid}", timeout=30)
                return True

            out = loadgen.replay(schedule, send, workers=8,
                                 open_loop=False)
            curve[str(n_servers)] = {
                "rps": out["rps"], "p99_ms": out["p99_ms"],
                "p50_ms": out["p50_ms"],
                "failures": out["failures"]}
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
            shutil.rmtree(workdir, ignore_errors=True)
    base = curve.get(str(counts[0]), {}).get("rps", 0.0)
    speedups = {f"speedup_{n}x": (round(curve[str(n)]["rps"] / base, 2)
                                  if base and str(n) in curve else 0.0)
                for n in counts[1:]}
    _cleanup_scale_workdirs()
    return {"counts": curve, **speedups,
            "requests": len(schedule),
            "seed": loadgen.load_seed(),
            "gated": cores >= 2, "host_cores": cores}


def bench_elasticity(num_objects: int = 150,
                     steady_reqs: int = 400,
                     recover_timeout: float = 45.0) -> dict:
    """Time-to-recover-p99 after a load spike: a 1-VS cluster serves a
    steady replay (baseline p99), then a storm drives admission-gate
    occupancy past WEED_SCALE_UP_OCC; the curator's autoscale detector
    enqueues scale.up, the worker spawns a second server through the
    in-process seam, the follow-up balance job re-shards volumes onto
    it, and the probe loop reports how long until windowed p99 drops
    back under 2x the steady baseline."""
    import tempfile
    import threading

    from seaweedfs_tpu import loadgen
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    overrides = {"WEED_SCALE": "1", "WEED_SCALE_UP_OCC": "0.3",
                 "WEED_SCALE_UP_RPS": "500",
                 "WEED_QOS_VS_LIMIT": "8"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    workdir = tempfile.mkdtemp(prefix="swbench_elastic_")
    master = MasterServer(port=0, pulse_seconds=0.5,
                          volume_size_limit_mb=1024,
                          maintenance_interval=3600.0)
    master.start()
    vs = VolumeServer([os.path.join(workdir, "vs0")], master.address,
                      port=0, pulse_seconds=0.5, max_volume_counts=[16])
    os.makedirs(os.path.join(workdir, "vs0"), exist_ok=True)
    spawned: list = []

    def spawn(job):
        d = os.path.join(workdir, f"spawn{len(spawned)}")
        os.makedirs(d, exist_ok=True)
        nv = VolumeServer([d], master.address, port=0,
                          pulse_seconds=0.5, max_volume_counts=[16])
        nv.start()
        nv.heartbeat_once()
        spawned.append(nv)
        return nv.store.url

    vs.spawn_volume_server = spawn
    payload = b"e" * 2048
    try:
        vs.start()
        vs.heartbeat_once()
        fids = []
        for _ in range(num_objects):
            a = call(master.address, "/dir/assign", timeout=30)
            call(a["url"], f"/{a['fid']}", raw=payload,
                 method="POST", timeout=30)
            fids.append(a["fid"])
        vs.heartbeat_once()
        locations: dict = {}
        loc_lock = threading.Lock()

        def lookup(fid: str, fresh: bool = False) -> str:
            vid = fid.split(",")[0]
            with loc_lock:
                if not fresh and vid in locations:
                    return locations[vid]
            looked = call(master.address,
                          f"/dir/lookup?volumeId={vid}", timeout=10)
            locs = looked.get("locations") or []
            url = locs[hash(fid) % len(locs)]["url"] if locs else ""
            with loc_lock:
                locations[vid] = url
            return url

        def get(fid: str):
            try:
                call(lookup(fid), f"/{fid}", timeout=30)
            except RpcError:
                call(lookup(fid, fresh=True), f"/{fid}", timeout=30)

        def probe(reqs: int, workers: int = 4) -> float:
            """Closed-loop GET storm; returns p99 seconds."""
            sched = [loadgen.Request(
                t=0.0, op="GET", obj=i, size=len(payload),
                tenant="bench", qos_class="interactive")
                for i in range(reqs)]
            out = loadgen.replay(
                sched, lambda r: (get(fids[r.obj % len(fids)]), True)[1],
                workers=workers, open_loop=False)
            return out["p99_ms"] / 1e3

        steady_p99 = probe(steady_reqs)
        bound = max(2.0 * steady_p99, steady_p99 + 0.25)

        storm_stop = threading.Event()

        def storm_loop():
            i = 0
            while not storm_stop.is_set():
                try:
                    get(fids[i % len(fids)])
                except Exception:
                    pass
                i += 1

        storm = [threading.Thread(target=storm_loop, daemon=True)
                 for _ in range(16)]
        t_spike = time.monotonic()
        for t in storm:
            t.start()
        spike_p99 = 0.0
        recover_seconds = -1.0
        scale_ticks = 0
        try:
            spike_p99 = probe(100, workers=2)
            deadline = time.monotonic() + recover_timeout
            while time.monotonic() < deadline:
                vs.heartbeat_once()
                for nv in spawned:
                    nv.heartbeat_once()
                master.curator.tick()
                vs.maintenance_worker.poll_once()
                scale_ticks += 1
                with loc_lock:
                    locations.clear()  # re-shard moves volumes
                p99 = probe(100, workers=2)
                if spawned and p99 <= bound:
                    recover_seconds = time.monotonic() - t_spike
                    break
        finally:
            storm_stop.set()
            for t in storm:
                t.join(timeout=5)
        return {"steady_p99_ms": round(steady_p99 * 1e3, 3),
                "spike_p99_ms": round(spike_p99 * 1e3, 3),
                "bound_ms": round(bound * 1e3, 3),
                "recover_seconds": round(recover_seconds, 2),
                "recovered": recover_seconds >= 0,
                "scaled_to": 1 + len(spawned),
                "control_ticks": scale_ticks}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for nv in spawned:
            nv.stop()
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)
        _cleanup_scale_workdirs()


def bench_topology_evolution(num_objects: int = 200,
                             probe_reqs: int = 200,
                             grow_timeout: float = 40.0,
                             split_timeout: float = 60.0) -> dict:
    """Online topology evolution under load: a 1-master / 2-shard filer
    cluster serves a steady metadata replay (baseline p99), then grows
    the control plane 1->3 masters (learner join, snapshot catch-up,
    voter promotion) and splits the filer map 2->8 shards (two-phase
    dual-write handover) while a background writer keeps inserting.
    Reports the wall time of each transition, read p99 at every
    topology, and the acked-write ledger — a lost acked write or a
    failed insert is the regression this phase exists to catch."""
    import tempfile
    import threading

    from seaweedfs_tpu import loadgen
    from seaweedfs_tpu.filer.entry import Entry
    from seaweedfs_tpu.filer.filer_store import ShardedSqliteStore
    from seaweedfs_tpu.filer.store_server import FilerStoreServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call

    overrides = {"WEED_FILER_SHARDS": "2",
                 "WEED_FILER_SHARD_LEASE": "2.0"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    workdir = tempfile.mkdtemp(prefix="swbench_topology_")
    d0 = os.path.join(workdir, "m0")
    os.makedirs(d0)
    m0 = MasterServer(port=0, pulse_seconds=0.5, raft_dir=d0,
                      raft_election_timeout=0.3,
                      maintenance_interval=3600.0)
    m0.start()
    stores = []
    for i in range(2):
        s = FilerStoreServer(
            port=0, store=ShardedSqliteStore(
                os.path.join(workdir, f"s{i}"), shard_count=2),
            masters=[m0.address])
        s.start()
        stores.append(s)
    new_masters: list = []

    def insert(path: str, timeout: float = 5.0) -> bool:
        for s in stores:
            try:
                call(s.address, "/store/insert",
                     payload=Entry(full_path=path).to_dict(),
                     method="POST", timeout=timeout)
                return True
            except RpcError:
                continue
        return False

    def readable(path: str) -> bool:
        for s in stores:
            try:
                call(s.address, "/store/find?path=" + path, timeout=5)
                return True
            except RpcError:
                continue
        return False

    def probe(paths: list, reqs: int) -> float:
        """Closed-loop metadata-read storm; returns p99 ms."""
        sched = [loadgen.Request(
            t=0.0, op="GET", obj=i, size=64,
            tenant="bench", qos_class="interactive")
            for i in range(reqs)]
        out = loadgen.replay(
            sched,
            lambda r: readable(paths[r.obj % len(paths)]),
            workers=4, open_loop=False)
        return out["p99_ms"]

    def wait_for(pred, timeout: float) -> float:
        """Poll until pred(); returns elapsed seconds or -1."""
        t0 = time.monotonic()
        deadline = t0 + timeout
        while time.monotonic() < deadline:
            if pred():
                return time.monotonic() - t0
            time.sleep(0.05)
        return -1.0

    acked: list = []
    failed = [0]
    stop = threading.Event()

    def writer_loop():
        i = 0
        while not stop.is_set():
            path = f"/evo{i}/obj"
            ok = False
            for _ in range(3):      # bounded retry: acked or failed
                if insert(path):
                    ok = True
                    break
                time.sleep(0.05)
            if ok:
                acked.append(path)
            else:
                failed[0] += 1
            i += 1
            time.sleep(0.01)

    grow_seconds = split_seconds = -1.0
    steady_p99 = grown_p99 = split_p99 = 0.0
    lost_acked = 0
    try:
        ok = wait_for(
            lambda: sum(len(s._held) for s in stores) == 2, 20.0)
        assert ok >= 0, "shard leases never converged"
        seeds = [f"/seed{i}/obj" for i in range(num_objects)]
        for p in seeds:
            insert(p, timeout=30.0)
        steady_p99 = probe(seeds, probe_reqs)

        writer = threading.Thread(target=writer_loop, daemon=True)
        writer.start()

        # -- grow the control plane 1 -> 3 (learner join) --------------
        for i in (1, 2):
            d = os.path.join(workdir, f"m{i}")
            os.makedirs(d)
            m = MasterServer(port=0, pulse_seconds=0.5, raft_dir=d,
                             peers=[m0.address], join=True,
                             raft_election_timeout=0.3,
                             maintenance_interval=3600.0)
            m.start()
            new_masters.append(m)
        grow_seconds = wait_for(
            lambda: all(m.address in m0.raft.voters
                        for m in new_masters), grow_timeout)
        grown_p99 = probe(seeds, probe_reqs)

        # -- split the filer map 2 -> 8 under the same write load ------
        call(m0.address, "/filer/shard_resize",
             payload={"op": "start", "to": 8}, method="POST",
             timeout=10)

        def split_done():
            r = call(m0.address, "/filer/shards", timeout=5)
            return r["slots"] == 8 and not r.get("resize")

        split_seconds = wait_for(split_done, split_timeout)
        wait_for(lambda: sum(len(s._held) for s in stores) == 8, 20.0)
        split_p99 = probe(seeds, probe_reqs)

        stop.set()
        writer.join(timeout=10)
        sample = acked[::max(1, len(acked) // 200)]
        lost_acked = sum(1 for p in sample if not readable(p))
    finally:
        stop.set()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for s in stores:
            s.stop()
        for m in new_masters:
            m.stop()
        m0.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "metric": "topology_evolution",
        "masters": 1 + len(new_masters),
        "shards_from": 2,
        "shards_to": 8,
        "grow_seconds": round(grow_seconds, 2),
        "split_seconds": round(split_seconds, 2),
        "steady_p99_ms": round(steady_p99, 3),
        "grown_p99_ms": round(grown_p99, 3),
        "split_p99_ms": round(split_p99, 3),
        "acked_writes": len(acked),
        "failed_writes": failed[0],
        "lost_acked": lost_acked,
    }


def bench_gateway_workers(counts: tuple = (1, 2, 4), num_files: int = 300,
                          read_reqs: int = 1500,
                          payload_bytes: int = 2048) -> dict:
    """smallfile_read_rps vs prefork gateway worker count.

    Each point starts a real `weed server` subprocess (prefork needs a
    fork + an SO_REUSEPORT bind on a concrete port, so the bench drives
    weed.py externally with WEED_HTTP_WORKERS set), writes `num_files`
    small objects through the volume gateway, then storms GETs with 8
    client threads and reports reads/s.  `gated` is True when the box
    has >= 2 usable cores — below that the workers time-slice one core
    and the curve measures the scheduler, not the sharding."""
    import signal as _signal
    import socket
    import subprocess
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.util.platform import available_cpu_count

    repo = os.path.dirname(os.path.abspath(__file__))

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    out: dict = {"counts": {}, "num_files": num_files,
                 "read_reqs": read_reqs, "cores": available_cpu_count()}
    out["gated"] = out["cores"] >= 2
    for workers in counts:
        workdir = tempfile.mkdtemp(prefix="swbench_gw_")
        mport, vport = free_port(), free_port()
        env = dict(os.environ, WEED_HTTP_WORKERS=str(workers),
                   JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(repo, "weed.py"), "server",
             "-ip", "127.0.0.1", "-dir", workdir,
             "-masterPort", str(mport), "-volumePort", str(vport)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, cwd=repo)
        master = f"127.0.0.1:{mport}"
        try:
            deadline = time.time() + 90
            while True:
                try:
                    st = call(master, "/dir/status", timeout=2)
                    if any(n.get("url")
                           for dc in st.get("datacenters", [])
                           for r in dc.get("racks", [])
                           for n in r.get("nodes", [])):
                        break
                except (RpcError, OSError):
                    pass
                if proc.poll() is not None or time.time() > deadline:
                    raise RuntimeError(
                        f"weed server ({workers}w) failed to come up")
                time.sleep(0.2)
            body = os.urandom(payload_bytes)
            fids = []
            for _ in range(num_files):
                a = call(master, "/dir/assign")
                call(a["url"], "/" + a["fid"], raw=body, method="POST")
                fids.append((a["url"], a["fid"]))

            def one(i: int) -> tuple:
                url, fid = fids[i % len(fids)]
                t = time.perf_counter()
                n = len(call(url, "/" + fid, parse=False))
                return n, time.perf_counter() - t

            with ThreadPoolExecutor(max_workers=8) as pool:
                list(pool.map(one, range(min(200, read_reqs))))  # warm
                t0 = time.perf_counter()
                results = list(pool.map(one, range(read_reqs)))
                elapsed = time.perf_counter() - t0
            if any(n != payload_bytes for n, _ in results):
                raise RuntimeError("short read during the GET storm")
            lat = sorted(t for _, t in results)
            out["counts"][str(workers)] = {
                "rps": round(read_reqs / elapsed, 1),
                "p50_ms": round(lat[len(lat) // 2] * 1000, 2),
                "p99_ms": round(lat[int(len(lat) * 0.99)
                                    if int(len(lat) * 0.99) < len(lat)
                                    else -1] * 1000, 2),
            }
        finally:
            if proc.poll() is None:
                proc.send_signal(_signal.SIGTERM)
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            shutil.rmtree(workdir, ignore_errors=True)
    c = out["counts"]
    if c.get("1") and c.get("2"):
        out["speedup_2x"] = round(c["2"]["rps"] / c["1"]["rps"], 2)
    return out


def bench_workload_analytics(num_objects: int = 400,
                             rate_rps: float = 800.0,
                             duration_s: float = 5.0,
                             num_parts: int = 3,
                             read_iters: int = 400) -> dict:
    """Workload-analytics accuracy + cost: the seeded zipfian replay
    (loadgen) is fed straight into WEED_HEAT_MAX_KEYS-bounded access
    recorders sharded across num_parts simulated daemons, merged the
    way the leader merges heartbeat summaries, and the sketch answers
    are checked against ground truth computed from the same schedule:
    every true head key must appear in the merged top-K, and
    per-tenant byte totals must land within 1%.  Recorder cost is the
    measured per-record() time expressed as a share of a real volume
    server's per-read service time — the <=2% gate perf_smoke
    enforces."""
    import tempfile

    from seaweedfs_tpu import loadgen
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.stats import access as access_mod
    from seaweedfs_tpu.volume_server.server import VolumeServer

    # cap the sketch well below the object count so the bench exercises
    # truncated Space-Saving merges, not exact counting
    saved = {k: os.environ.get(k) for k in ("WEED_HEAT",
                                            "WEED_HEAT_MAX_KEYS")}
    os.environ["WEED_HEAT"] = "1"
    os.environ["WEED_HEAT_MAX_KEYS"] = str(max(64, num_objects // 4))
    try:
        schedule = loadgen.build_schedule(
            duration_s=duration_s, rate_rps=rate_rps,
            n_objects=num_objects, n_tenants=32, write_ratio=0.0)
        recorders = [access_mod.AccessRecorder(node=f"bench{i}")
                     for i in range(num_parts)]
        true_reads: dict = {}
        tenant_bytes: dict = {}
        # time the second half only: steady state, not cold caches
        half = len(schedule) // 2
        t0 = 0.0
        for n, req in enumerate(schedule):
            if n == half:
                t0 = time.perf_counter()
            fid = f"7,{req.obj:08x}"
            recorders[n % num_parts].record(
                "read", collection="bench", tenant=req.tenant,
                volume=7, fid=fid, nbytes=req.size, latency_s=5e-4,
                qos_class=req.qos_class)
            true_reads[fid] = true_reads.get(fid, 0) + 1
            tenant_bytes[req.tenant] = (tenant_bytes.get(req.tenant, 0)
                                        + req.size)
        record_us = ((time.perf_counter() - t0)
                     / max(1, len(schedule) - half) * 1e6)

        agg = access_mod.UsageAggregator()
        for i, rec in enumerate(recorders):
            agg.ingest(f"bench{i}", rec.summary())
        usage = agg.usage(topk=20)
        sketch_top = [e["fid"] for e in usage["top_keys"]]
        true_top = [k for k, _ in sorted(true_reads.items(),
                                         key=lambda kv: (-kv[1], kv[0]))]
        head = true_top[:5]
        topk_hits = sum(1 for f in head if f in sketch_top)

        tenant_err = 0.0
        for name, truth in tenant_bytes.items():
            by_op = usage["tenants"].get(name, {}).get("bytes") or {}
            got = sum(by_op.values())
            tenant_err = max(tenant_err, abs(got - truth) / truth)
        sketch_bytes = sum(rec.memory_bytes() for rec in recorders)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # per-read service time on a live volume server (recorder on),
    # for the overhead ratio the perf_smoke gate enforces
    workdir = tempfile.mkdtemp(prefix="swbench_wa_")
    master = MasterServer(port=0, pulse_seconds=1.0,
                          maintenance_interval=3600.0)
    master.start()
    vs = VolumeServer([workdir], master.address, port=0,
                      pulse_seconds=1.0)
    vs.start()
    vs.heartbeat_once()
    try:
        payload = b"w" * 2048
        fids = []
        for _ in range(40):
            a = call(master.address, "/dir/assign", timeout=30)
            call(a["url"], f"/{a['fid']}", raw=payload, method="POST",
                 timeout=30)
            fids.append((a["url"], a["fid"]))
        for url, fid in fids:  # warm
            call(url, f"/{fid}", timeout=30)
        t0 = time.perf_counter()
        for i in range(read_iters):
            url, fid = fids[i % len(fids)]
            call(url, f"/{fid}", timeout=30)
        read_us = (time.perf_counter() - t0) / read_iters * 1e6
    finally:
        vs.stop()
        master.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    overhead_pct = record_us / read_us * 100.0 if read_us else 0.0
    return {
        "requests": len(schedule),
        "objects": num_objects,
        "parts": num_parts,
        "seed": loadgen.load_seed(),
        "topk_hits": topk_hits,
        "topk_expected": len(head),
        "topk_ok": topk_hits == len(head),
        "tenant_bytes_err_pct": round(tenant_err * 100.0, 4),
        "tenant_bytes_ok": tenant_err <= 0.01,
        "distinct_keys_est": usage["totals"]["distinct_keys"],
        "distinct_keys_true": len(true_reads),
        "sketch_bytes": sketch_bytes,
        "record_us": round(record_us, 3),
        "read_us": round(read_us, 1),
        "read_rps": round(1e6 / read_us, 1) if read_us else 0.0,
        "recorder_overhead_pct": round(overhead_pct, 3),
        "overhead_ok": overhead_pct <= 2.0,
    }


def main():
    from seaweedfs_tpu.util import platform as plat

    import jax

    # a device benchmark without the device is not a slower benchmark,
    # it is a different one: fail instead of benching the CPU backend
    if not plat.on_tpu():
        sys.exit("bench.py needs a TPU: JAX found none in this process")
    platform = jax.devices()[0].platform
    on_tpu = True

    cpu_kernel = bench_cpu_kernel(level=1)   # AVX2 PSHUFB baseline
    cpu_gfni = bench_cpu_kernel(level=-1)    # best host kernel (GFNI)

    # -- device kernel ceiling (no CRC) --------------------------------------
    # off-TPU the pallas kernels only run in interpret mode (a Python
    # grid emulation measured in minutes per call) — probe the XLA
    # formulations only there
    candidates: dict[str, float] = {}
    probe_len = (64 << 20) if on_tpu else (8 << 20)
    kernel_candidates = (
        (("pallas", 8192), ("pallas", 32768), ("mxu", None))
        if on_tpu else (("mxu", None), ("swar", None)))
    for method, block in kernel_candidates:
        name = f"{method}{block or ''}"
        try:
            for _ in range(3):
                value = bench_tpu_kernel(
                    method, probe_len, block=block, chains=(2, 6), reps=2)
                if value <= 500:  # > 500 GiB/s = jitter ate the slope
                    candidates[name] = value
                    break
        except Exception as e:
            print(f"note: {name} failed: {e}", file=sys.stderr)

    kernel, best_name = 0.0, "none"
    if candidates:
        best_name = max(candidates, key=candidates.get)
        method = "pallas" if best_name.startswith("pallas") else best_name
        block = (int(best_name[len("pallas"):])
                 if best_name.startswith("pallas") else None)
        length = (256 << 20) if on_tpu else (8 << 20)
        kernel = bench_tpu_kernel(method, length, block=block)

    # -- HBM-resident fused batched step (parity + CRC) ----------------------
    hbm_fused, hbm_variants = 0.0, {}
    b, length = (6, 1 << 20) if on_tpu else (6, 1 << 18)
    for variant in (("pallas", "xla") if on_tpu else ("xla",)):
        try:
            hbm_variants[variant] = bench_hbm_fused(b, length,
                                                    variant=variant)
        except Exception as e:
            print(f"note: hbm_fused[{variant}] failed: {e}",
                  file=sys.stderr)
    if hbm_variants:
        hbm_fused = max(hbm_variants.values())

    # -- host<->device link bandwidth (attributes the e2e gap) ---------------
    h2d_mbps = d2h_mbps = 0.0
    try:
        probe = np.zeros(32 << 20, dtype=np.uint8)
        dev = jax.device_put(probe)
        np.asarray(dev[:4])  # warm path
        t0 = time.perf_counter()
        dev = jax.device_put(probe)
        np.asarray(dev[:4])
        h2d_mbps = probe.nbytes / (1 << 20) / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(dev)
        d2h_mbps = probe.nbytes / (1 << 20) / (time.perf_counter() - t0)
    except Exception as e:
        print(f"note: link probe failed: {e}", file=sys.stderr)

    # -- device reconstruct (BASELINE config 3) ------------------------------
    rebuild_kernel = 0.0
    try:
        rebuild_kernel = bench_rebuild_kernel(
            (64 << 20) if on_tpu else (4 << 20), on_tpu=on_tpu)
    except Exception as e:
        print(f"note: rebuild kernel failed: {e}", file=sys.stderr)

    # -- end-to-end disk -> shards -------------------------------------------
    # size the device-path volumes to the measured link: a link in the
    # tens of MB/s would otherwise spend tens of minutes proving slow
    link_mbps = min(h2d_mbps, d2h_mbps) or 0.0
    link_capped = bool(on_tpu and link_mbps and link_mbps < 500)
    if link_capped:
        vol_bytes = 128 << 20
    else:
        vol_bytes = (512 << 20) if on_tpu else (64 << 20)
    n_dev = 3 if on_tpu else 2
    # config-4 scale validation: >=100 volumes / >=8 GiB through ONE
    # pipeline (CPU-device mesh when the device link is capped)
    scale_vols, scale_vol_bytes = (100, 90 << 20) if on_tpu else (12, 8 << 20)
    e2e_single = e2e_device = e2e_default = cpu_e2e = 0.0
    scale_rate, scale_rss, dev_scale_rate = 0.0, 0.0, 0.0
    default_stages: dict = {}
    scale_stages: dict = {}
    dev_scale_stages: dict = {}
    maint_scrub_rate = 0.0
    maint_scrub_stages: dict = {}
    workdir = _pick_workdir(
        max((n_dev + 1) * vol_bytes * 3, scale_vols * scale_vol_bytes * 3))
    # folded-stack sampler across the e2e encode phases: the bench JSON
    # carries a self-time top-frames breakdown so a rate regression
    # comes with its own attribution (not a separate profiling run)
    from seaweedfs_tpu import profiling as _profiling

    e2e_sampler = _profiling.StackSampler(hz=37.0)
    e2e_sampler.start()
    try:
        e2e_single = bench_e2e_disk(1, vol_bytes, workdir)
        e2e_device = bench_e2e_disk(n_dev, vol_bytes, workdir, warm=False)
        cpu_e2e = bench_cpu_e2e(vol_bytes, workdir)
        e2e_default, default_stages = bench_e2e_default(vol_bytes, workdir)
    except Exception as e:
        print(f"note: e2e failed: {e}", file=sys.stderr)
    try:
        scale_rate, scale_rss, scale_stages = bench_e2e_scale(
            scale_vols, scale_vol_bytes, workdir)
    except Exception as e:
        print(f"note: scale e2e failed: {e}", file=sys.stderr)
    try:
        # device-dispatch path at 100-volume COUNT (small volumes: the
        # capped-link/CPU-XLA rate only proves the link/backend is slow)
        dev_scale_rate, dev_scale_stages = bench_e2e_device_scale(
            scale_vols, 4 << 20, workdir, link_capped)
    except Exception as e:
        print(f"note: device scale e2e failed: {e}", file=sys.stderr)
    dev_scale_curve: dict = {}
    try:
        # per-mesh-width scaling of the sharded dispatch path (always on
        # the CPU harness — the curve isolates the shard_map scaling
        # from link and backend effects)
        dev_scale_curve = bench_device_scale_curve(workdir)
    except Exception as e:
        print(f"note: device scale curve failed: {e}", file=sys.stderr)
    try:
        maint_scrub_rate, maint_scrub_stages = \
            bench_maintenance_deep_scrub(
                8 if on_tpu else 4, 16 << 20, workdir, link_capped)
    except Exception as e:
        print(f"note: maintenance deep scrub failed: {e}",
              file=sys.stderr)
    finally:
        e2e_sampler.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    e2e_profile_top = e2e_sampler.top_frames(12)

    # -- inline write-path EC vs post-hoc seal-then-encode -------------------
    inline_ec_stats: dict = {}
    try:
        inline_ec_stats = bench_inline_encode()
    except Exception as e:
        print(f"note: inline encode bench failed: {e}", file=sys.stderr)

    # -- small-file data plane (the reference README's headline bench) ------
    # 1M x 1 KB c=16 published numbers: 15,708 writes/s / 47,019 reads/s
    # (reference README.md:342-391).  Scaled-down here to keep bench.py's
    # wall-clock bounded; rates are steady within ~10% of the 1M run.
    sf_write_rps = sf_read_rps = sf_http_read_rps = 0.0
    try:
        sf_write_rps, sf_read_rps, sf_http_read_rps = \
            bench_small_file(200_000)
    except Exception as e:
        print(f"note: small-file bench failed: {e}", file=sys.stderr)

    # policy state (breakers / retry budget / hedge rings) is process-
    # global and keyed by ephemeral addresses; a breaker opened by one
    # phase's teardown must not shed load in the next phase
    from seaweedfs_tpu.rpc import policy as _policy

    # -- small files under production config: JWT + replication 001 ----------
    sec_write_rps = sec_read_rps = 0.0
    try:
        _policy.reset_state()
        sec_write_rps, sec_read_rps = bench_small_file_secured(50_000)
    except Exception as e:
        print(f"note: secured small-file bench failed: {e}",
              file=sys.stderr)

    # -- degraded EC reads (4 shards dead, reconstruct per read) -------------
    deg_rps = deg_p99 = deg_native_rps = 0.0
    deg_stages: dict = {}
    deg_err = ""
    try:
        _policy.reset_state()
        deg_rps, deg_p99, deg_native_rps, deg_stages = \
            bench_ec_degraded_read()
        if deg_rps <= 0.0:
            deg_err = "bench returned 0 rps without raising"
    except Exception as e:
        deg_err = f"{type(e).__name__}: {e}"
        print(f"note: degraded-read bench failed: {e}", file=sys.stderr)

    # -- QoS isolation: fg degraded reads vs concurrent deep scrub ----------
    qos_iso: dict = {}
    try:
        _policy.reset_state()
        qos_iso = bench_qos_isolation()
    except Exception as e:
        print(f"note: qos isolation bench failed: {e}", file=sys.stderr)

    # -- coding-tier rebuild read amplification ------------------------------
    ec_rebuild_stats: dict = {}
    try:
        ec_rebuild_stats = bench_ec_rebuild()
    except Exception as e:
        print(f"note: ec rebuild bench failed: {e}", file=sys.stderr)

    # -- master leader-kill write-unavailability window ----------------------
    failover_stats: dict = {}
    try:
        _policy.reset_state()
        failover_stats = bench_master_failover()
    except Exception as e:
        print(f"note: master failover bench failed: {e}", file=sys.stderr)

    # -- S3 gateway vs filer data plane --------------------------------------
    s3_stats: dict = {}
    try:
        _policy.reset_state()
        s3_stats = bench_s3_gateway()
    except Exception as e:
        print(f"note: s3 bench failed: {e}", file=sys.stderr)

    # -- unified read cache: cold vs warm GET storms -------------------------
    read_cache_stats: dict = {}
    try:
        _policy.reset_state()
        read_cache_stats = bench_read_cache()
    except Exception as e:
        print(f"note: read cache bench failed: {e}", file=sys.stderr)

    # -- elasticity: rps/p99 scale curve + spike-recovery time ---------------
    cluster_scale_stats: dict = {}
    try:
        _policy.reset_state()
        cluster_scale_stats = bench_cluster_scale()
    except Exception as e:
        print(f"note: cluster scale bench failed: {e}", file=sys.stderr)
    elasticity_stats: dict = {}
    try:
        _policy.reset_state()
        elasticity_stats = bench_elasticity()
    except Exception as e:
        print(f"note: elasticity bench failed: {e}", file=sys.stderr)

    # -- online topology evolution: master growth + shard split --------------
    topology_stats: dict = {}
    try:
        _policy.reset_state()
        topology_stats = bench_topology_evolution()
    except Exception as e:
        print(f"note: topology evolution bench failed: {e}",
              file=sys.stderr)

    # -- prefork gateway worker scaling (smallfile read rps) -----------------
    gateway_workers_stats: dict = {}
    try:
        _policy.reset_state()
        gateway_workers_stats = bench_gateway_workers()
    except Exception as e:
        print(f"note: gateway workers bench failed: {e}", file=sys.stderr)

    # -- workload analytics: sketch accuracy + recorder overhead -------------
    workload_stats: dict = {}
    try:
        _policy.reset_state()
        workload_stats = bench_workload_analytics()
    except Exception as e:
        print(f"note: workload analytics bench failed: {e}",
              file=sys.stderr)

    vs_baseline = hbm_fused / cpu_kernel if cpu_kernel > 0 else 0.0
    from seaweedfs_tpu.util.platform import available_cpu_count

    print(json.dumps({
        "metric": "rs10_4_batched_encode_fused_throughput",
        "value": round(hbm_fused, 3),
        "unit": "GiB/s",
        "vs_baseline": round(vs_baseline, 3),
        "platform": platform,
        "kernel_gibps": round(kernel, 3),
        "kernel": best_name,
        "fused_vs_kernel": round(hbm_fused / kernel, 3) if kernel else 0,
        "rebuild_kernel_gibps": round(rebuild_kernel, 3),
        "cpu_avx2_kernel_gibps": round(cpu_kernel, 3),
        "cpu_gfni_kernel_gibps": round(cpu_gfni, 3),
        "kernel_vs_avx2": round(kernel / cpu_kernel, 3) if cpu_kernel else 0,
        "e2e_single_gibps": round(e2e_single, 3),
        "e2e_device_gibps": round(e2e_device, 3),
        "e2e_device_vols": n_dev,
        "e2e_batched_gibps": round(scale_rate, 3),
        "e2e_batched_vols": scale_vols,
        "e2e_vol_gib": round(scale_vol_bytes / GIB, 3),
        "e2e_batched_backend": scale_stages.get("backend",
                                                "host-pipeline"),
        "e2e_device_dispatch_100vol_gibps": round(dev_scale_rate, 3),
        "e2e_device_dispatch_backend": dev_scale_stages.get("backend", ""),
        "e2e_device_dispatch_stages": dev_scale_stages,
        "e2e_device_scale_curve": dev_scale_curve,
        "maintenance_deep_scrub_gibps": round(maint_scrub_rate, 3),
        "maintenance_deep_scrub_backend":
            maint_scrub_stages.get("backend", ""),
        "maintenance_deep_scrub_stages": maint_scrub_stages,
        "e2e_profile_top": e2e_profile_top,
        "workdir": dict(_WORKDIR_INFO),
        "scale_total_gib": round(scale_vols * scale_vol_bytes / GIB, 2),
        "scale_peak_rss_mb": round(scale_rss, 1),
        "cpu_e2e_gibps": round(cpu_e2e, 3),
        "e2e_default_gibps": round(e2e_default, 3),
        "e2e_vs_cpu_e2e": (round(e2e_default / cpu_e2e, 3)
                           if cpu_e2e > 0 else 0.0),
        "e2e_default_stages": default_stages,
        "e2e_scale_stages": scale_stages,
        "inline_ec": inline_ec_stats,
        # affinity-aware (sched_getaffinity): matches the worker count
        # the host pipeline will actually spawn on this box
        "host_cores": available_cpu_count(),
        "hbm_fused_variants": {k: round(v, 3)
                               for k, v in hbm_variants.items()},
        "link_h2d_mbps": round(h2d_mbps, 1),
        "link_d2h_mbps": round(d2h_mbps, 1),
        "smallfile_write_rps": round(sf_write_rps, 1),
        "smallfile_read_rps": round(sf_read_rps, 1),
        "smallfile_http_read_rps": round(sf_http_read_rps, 1),
        "smallfile_vs_ref_write": round(sf_write_rps / 15708.23, 2),
        "smallfile_vs_ref_read": round(sf_read_rps / 47019.38, 2),
        "smallfile_http_vs_ref_read": round(
            sf_http_read_rps / 47019.38, 2),
        "smallfile_jwt_repl001_write_rps": round(sec_write_rps, 1),
        "smallfile_jwt_repl001_read_rps": round(sec_read_rps, 1),
        "ec_degraded_read_rps": round(deg_rps, 1),
        "ec_degraded_read_p99_ms": round(deg_p99, 2),
        "ec_degraded_read_native_rps": round(deg_native_rps, 1),
        "ec_degraded_read_stages": deg_stages,
        "ec_degraded_read_error": deg_err,
        "qos_isolation": qos_iso,
        "ec_rebuild": ec_rebuild_stats,
        "master_failover": failover_stats,
        "s3_put_rps": round(s3_stats.get("s3_put_rps", 0.0), 1),
        "s3_get_rps": round(s3_stats.get("s3_get_rps", 0.0), 1),
        "filer_put_rps": round(s3_stats.get("filer_put_rps", 0.0), 1),
        "filer_get_rps": round(s3_stats.get("filer_get_rps", 0.0), 1),
        "s3_vs_filer_get": (
            round(s3_stats["s3_get_rps"] / s3_stats["filer_get_rps"], 2)
            if s3_stats.get("filer_get_rps") else 0.0),
        "gateway_stages": s3_stats.get("gateway_stages", {}),
        "read_cache": read_cache_stats,
        "cluster_scale": cluster_scale_stats,
        "elasticity": elasticity_stats,
        "topology_evolution": topology_stats,
        "gateway_workers": gateway_workers_stats,
        "workload_analytics": workload_stats,
        "smallfile_secured_vs_plain_write": (
            round(sec_write_rps / sf_write_rps, 2) if sf_write_rps
            else 0.0),
        "note": ("value = HBM-resident batched parity+CRC word-layout "
                 "step (BASELINE config 4/5); e2e_default is the "
                 "link-throughput auto-selected ec.encode path (must "
                 "never lose to cpu_e2e); e2e_single/e2e_device ride "
                 "the host<->device link measured as link_*_mbps; "
                 "e2e_batched validates the 100-volume pipeline at "
                 "scale on the backend named in e2e_batched_backend"),
        "probe": {k: round(v, 3) for k, v in candidates.items()},
    }))


def _flatten_metrics(d, prefix=""):
    """Numeric leaves of a bench result as {dotted.path: value}."""
    out = {}
    if isinstance(d, dict):
        for k, v in d.items():
            out.update(_flatten_metrics(v, f"{prefix}{k}."))
    elif isinstance(d, bool):
        pass
    elif isinstance(d, (int, float)):
        out[prefix[:-1]] = float(d)
    return out


_LOWER_IS_BETTER = ("p50", "p99", "latency", "_ms", "seconds",
                    "overhead", "write_amp", "failover_gap",
                    "sketch_bytes")
_TRACKED = ("rps", "gibps", "value", "throughput", "p50", "p99",
            "latency_ms", "failover_gap", "overhead_pct",
            "sketch_bytes")


def _metric_direction(path):
    """+1 higher-is-better, -1 lower-is-better, 0 untracked."""
    leaf = path.rsplit(".", 1)[-1]
    if not any(t in leaf for t in _TRACKED):
        return 0
    return -1 if any(t in leaf for t in _LOWER_IS_BETTER) else 1


def compare_results(prev: dict, curr: dict, threshold_pct: float):
    """Per-metric delta rows + the subset that regressed past the
    threshold.  Only tracked metrics (throughputs, rps, latencies) can
    fail the comparison; context fields are informational."""
    pv, cv = _flatten_metrics(prev), _flatten_metrics(curr)
    rows, regressions = [], []
    for path in sorted(set(pv) & set(cv)):
        a, b = pv[path], cv[path]
        direction = _metric_direction(path)
        if a == 0:
            delta_pct = 0.0 if b == 0 else float("inf")
        else:
            delta_pct = (b - a) / abs(a) * 100.0
        regressed = bool(direction) and (
            -direction * delta_pct > threshold_pct)
        rows.append((path, a, b, delta_pct, direction, regressed))
        if regressed:
            regressions.append(path)
    return rows, regressions


def cmd_compare(argv):
    """`bench.py --compare prev.json [curr.json]` — regression gate.

    Compares a previous run's JSON against the current one (second
    file, or stdin when omitted) and exits non-zero when any tracked
    metric regressed more than WEED_BENCH_REGRESS_PCT (default 20%)."""
    if not argv:
        sys.exit("usage: bench.py --compare prev.json [curr.json]")
    with open(argv[0]) as f:
        prev = json.load(f)
    if len(argv) > 1:
        with open(argv[1]) as f:
            curr = json.load(f)
    else:
        curr = json.load(sys.stdin)
    threshold = float(os.environ.get("WEED_BENCH_REGRESS_PCT", "")
                      or 20.0)
    rows, regressions = compare_results(prev, curr, threshold)
    if not rows:
        sys.exit("no common numeric metrics between the two results")
    print(f"{'metric':52s} {'prev':>12s} {'curr':>12s} {'delta':>9s}")
    for path, a, b, delta, direction, regressed in rows:
        flag = " REGRESSED" if regressed else ""
        arrow = {1: "^", -1: "v", 0: " "}[direction]
        print(f"{path:52s} {a:12.3f} {b:12.3f} {delta:+8.1f}%"
              f" {arrow}{flag}")
    if regressions:
        print(f"\n{len(regressions)} tracked metric(s) regressed more "
              f"than {threshold:g}%: {', '.join(regressions)}")
        sys.exit(1)
    print(f"\nno tracked metric regressed more than {threshold:g}%")


if __name__ == "__main__":
    from seaweedfs_tpu.util.platform import ensure_compile_cache

    ensure_compile_cache()  # before any phase jits
    # single-phase mode: `python bench.py ec_rebuild` runs one phase and
    # prints its JSON alone — the full suite stays the no-argument default
    _phases = {"ec_rebuild": bench_ec_rebuild,
               "e2e_inline_encode": bench_inline_encode,
               "master_failover": bench_master_failover,
               "read_cache": bench_read_cache,
               "cluster_scale": bench_cluster_scale,
               "elasticity": bench_elasticity,
               "topology_evolution": bench_topology_evolution,
               "gateway_workers": bench_gateway_workers,
               "workload_analytics": bench_workload_analytics,
               # alias: the curve IS the smallfile read-rps phase
               "smallfile_read_rps": bench_gateway_workers}
    if len(sys.argv) > 1:
        if sys.argv[1] in ("--list", "-l"):
            print("\n".join(sorted(_phases)))
            sys.exit(0)
        if sys.argv[1] == "--compare":
            cmd_compare(sys.argv[2:])
            sys.exit(0)
        if sys.argv[1] not in _phases:
            sys.exit(f"unknown bench phase {sys.argv[1]!r}; "
                     f"one of: {', '.join(sorted(_phases))}")
        # trailing key=value args are forwarded to the phase function
        # (ints when they parse as ints): bench.py e2e_inline_encode
        # n_vols=1 vol_bytes=8388608
        kwargs = {}
        for arg in sys.argv[2:]:
            key, _, val = arg.partition("=")
            kwargs[key] = int(val) if val.lstrip("-").isdigit() else val
        print(json.dumps(_phases[sys.argv[1]](**kwargs)))
    else:
        main()
