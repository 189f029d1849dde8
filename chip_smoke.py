#!/usr/bin/env python3
"""chip_smoke.py — the warm-EC path, end to end, on the chip.

Drives BASELINE configs 1-3 at their own size through the entry points a
user calls: `weed.py master` and `weed.py volume -ecBackend tpu` as child
processes, seeded objects uploaded over HTTP until one volume holds
~960 MiB, `weed.py shell -c ec.encode`, a degraded GET of every object
with one data shard deleted, three more shards deleted and
`weed.py shell -c ec.rebuild`, and a final GET of everything.

What decides the result:
  * a dict of fid -> (size, blake2b) kept here is the plain reference:
    every acknowledged write is read back byte-identical after encode,
    while degraded, and after rebuild;
  * parity is checked without the code under test: a seeded sample of
    stripe columns from the 14 shard files against ops/rs_numpy.py, and
    every shard file's CRC32C against the .vif record;
  * the volume server itself must report platform "tpu", encode backend
    "device-words", a device-served rebuild and recover (host->device
    byte counters grew by what each phase must upload, zero recover
    fallbacks).

The daemons run with their defaults except `WEED_EC_DEVICE_SHARD` (the
EC mesh is pinned to --devices chips whatever the host has) and
`WEED_MAINT=0` (the curator's automatic repair would race the script's own
`ec.rebuild`); both are listed under `assumed` in the result.

This script never imports JAX: the volume server is the one process on
the chip.  The last two lines of stdout are JSON: the run's summary
(phases, cold-run stage stats, compile cache; ends with `"claim": null`),
then the result line `{"ok": true, "device": {"platform", "kind",
"count"}}` with exactly those keys.  Neither is printed, and the exit code
is non-zero, unless every phase passed.

`--rehearse` runs the same flow at a few MiB on the CPU backend (the CPU
mesh encode/rebuild paths; recover runs on the host there) and labels its
output a rehearsal — it proves the flow, never the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import http.client
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

try:  # the checkout beside this script (sys.path[0]); none of it imports JAX
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.crc32c import crc32c
    from seaweedfs_tpu.ops.rs_numpy import gf_apply_matrix
    from seaweedfs_tpu.storage.erasure_coding import (
        DATA_SHARDS_COUNT as DATA_SHARDS, SMALL_BLOCK_SIZE as SHARD_BLOCK,
        TOTAL_SHARDS_COUNT as TOTAL_SHARDS, to_ext)
    from seaweedfs_tpu.util.platform import ensure_compile_cache
except ImportError as e:
    sys.exit(f"chip_smoke.py: no seaweedfs_tpu checkout beside this script "
             f"({e}) — nothing to run")

HERE = os.path.dirname(os.path.abspath(__file__))
COLLECTION = "smoke"
MIB = 1 << 20
LOST_FIRST = 0              # the data shard the degraded phase deletes
LOST_MORE = (3, 11, 13)     # then a second data shard and two parity shards

# (count, bytes) of large and small objects
FULL_SIZE = ((224, 4 * MIB), (2048, 32 << 10))     # 896 + 64 = 960 MiB
REHEARSAL_SIZE = ((5, 4 * MIB), (128, 32 << 10))   # 20 + 4 = 24 MiB

# Settings the daemons get that are not their defaults, and why.
ASSUMED_ENV = {
    # the curator (default on, 30 s tick) repairs a missing shard by
    # itself; left on it races the script's own ec.rebuild for the same
    # shard files and can end the degraded phase early
    "WEED_MAINT": "0",
}

T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def log(msg: str):
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


# -- tiny HTTP client (stdlib only: what any user of the store has) ----------

_tls = threading.local()


def request(addr: str, method: str, path: str, body: bytes | None = None,
            timeout: float = 120.0) -> tuple[int, bytes]:
    """One request over a per-thread keep-alive connection; a dropped
    idle connection is reopened once."""
    conns = _tls.__dict__.setdefault("conns", {})
    for attempt in (0, 1):
        conn = conns.get(addr)
        if conn is None:
            host, port = addr.rsplit(":", 1)
            conn = conns[addr] = http.client.HTTPConnection(
                host, int(port), timeout=timeout)
        try:
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (http.client.HTTPException, OSError):
            conn.close()
            conns.pop(addr, None)
            if attempt:
                raise
    raise AssertionError("unreachable")


def get_json(addr: str, path: str, method: str = "GET",
             payload: dict | None = None, timeout: float = 120.0) -> dict:
    body = None if payload is None else json.dumps(payload).encode()
    status, data = request(addr, method, path, body, timeout)
    check(status == 200, f"{method} {addr}{path} -> {status}: {data[:300]!r}")
    return json.loads(data)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- daemons ------------------------------------------------------------------

class Daemons:
    """The master and the volume server as child processes; stop() sends
    SIGTERM and reports whether every child exited."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.procs: list[tuple[str, subprocess.Popen]] = []

    def start(self, name: str, args: list[str], env: dict):
        logf = open(os.path.join(self.logdir, f"{name}.log"), "wb")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "weed.py"), *args],
            cwd=HERE, env=env, stdout=logf, stderr=subprocess.STDOUT)
        logf.close()
        self.procs.append((name, proc))

    def check_alive(self):
        for name, proc in self.procs:
            check(proc.poll() is None,
                  f"{name} exited early with code {proc.returncode}")

    def stop(self) -> list[str]:
        """SIGTERM every child; returns the names that had to be killed."""
        stubborn = []
        for _, proc in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc in reversed(self.procs):
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                stubborn.append(name)
                proc.kill()
                proc.wait(timeout=30)
        return stubborn

    def tails(self, lines: int = 40) -> str:
        out = []
        for name, _ in self.procs:
            path = os.path.join(self.logdir, f"{name}.log")
            try:
                with open(path, errors="replace") as f:
                    tail = f.readlines()[-lines:]
            except OSError:
                continue
            out.append(f"--- {name}.log (last {len(tail)} lines) ---\n"
                       + "".join(tail))
        return "\n".join(out)


def wait_until(what: str, fn, timeout: float, daemons: Daemons):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        daemons.check_alive()
        try:
            value = fn()
            if value:
                return value
        except (OSError, http.client.HTTPException, SmokeFailure) as e:
            last = e
        time.sleep(0.2)
    raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for {what}"
                       + (f" (last error: {last})" if last else ""))


def shell(master: str, line: str, timeout: float = 900.0) -> dict:
    """`weed.py shell -c <line>`: the printed JSON plan, or a failure (the
    shell prints `error: ...` and still exits 0)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "weed.py"), "shell",
         "-master", master, "-c", line],
        cwd=HERE, capture_output=True, text=True, timeout=timeout)
    out = proc.stdout.strip()
    check(proc.returncode == 0 and out.startswith("{"),
          f"shell `{line}` failed (exit {proc.returncode}): "
          f"{out[-600:]} {proc.stderr[-600:]}")
    return json.loads(out)


# -- what the volume server counts ---------------------------------------------

_H2D_RE = re.compile(
    r'^SeaweedFS_volumeServer_ec_device_h2d_bytes_total'
    r'\{device="((?:[^"\\]|\\.)*)"\} (\S+)$', re.M)


def device_h2d_bytes(vs: str) -> dict[str, int]:
    """Host->device bytes the volume server has staged, by device label
    ("host" staging excluded)."""
    status, body = request(vs, "GET", "/metrics")
    check(status == 200, f"/metrics -> {status}")
    return {label: int(float(v))
            for label, v in _H2D_RE.findall(body.decode())
            if label != "host"}


def h2d_growth(vs: str, before: dict[str, int]) -> dict[str, int]:
    now = device_h2d_bytes(vs)
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0) > 0}


def cache_entries(path: str) -> int:
    try:
        return sum(1 for _ in os.scandir(path))
    except OSError:
        return 0


# -- load ------------------------------------------------------------------------

def object_sizes(size_spec, seed: int) -> list[int]:
    sizes = [nbytes for count, nbytes in size_spec for _ in range(count)]
    random.Random(seed).shuffle(sizes)
    return sizes


def load(master: str, sizes: list[int], seed: int) -> tuple[dict, int]:
    """Upload seeded objects through /dir/assign + raw-body POST.  Returns
    ({fid: (size, blake2b, url)} of the ACKNOWLEDGED writes, volume id)."""
    rng = np.random.default_rng(seed)
    written: dict[str, tuple[int, bytes, str]] = {}
    lock = threading.Lock()
    slots = threading.Semaphore(16)  # bounds generated-but-unsent bytes

    def put(data: bytes):
        try:
            a = get_json(master, f"/dir/assign?collection={COLLECTION}")
            status, body = request(a["url"], "POST", "/" + a["fid"], data)
            check(status in (200, 201),
                  f"POST {a['fid']} -> {status}: {body[:200]!r}")
            digest = hashlib.blake2b(data, digest_size=16).digest()
            with lock:
                written[a["fid"]] = (len(data), digest, a["url"])
        finally:
            slots.release()

    with ThreadPoolExecutor(max_workers=8) as pool:
        futs = []
        for n in sizes:
            slots.acquire()
            futs.append(pool.submit(put, rng.bytes(n)))
        for f in futs:
            f.result()
    vids = {fid.split(",")[0] for fid in written}
    check(len(vids) == 1, f"objects landed on volumes {sorted(vids)}, "
                          "expected exactly one")
    return written, int(vids.pop())


def read_back(written: dict, phase: str, workers: int = 4) -> int:
    """GET every acknowledged write and compare with the reference dict."""
    def one(item):
        fid, (size, digest, url) = item
        status, body = request(url, "GET", "/" + fid)
        if status != 200:
            return f"{fid}: HTTP {status} {body[:120]!r}"
        if len(body) != size or hashlib.blake2b(
                body, digest_size=16).digest() != digest:
            return f"{fid}: {len(body)} bytes, content differs"
        return None

    with ThreadPoolExecutor(max_workers=workers) as pool:
        bad = [r for r in pool.map(one, sorted(written.items())) if r]
    check(not bad, f"{phase}: {len(bad)} of {len(written)} objects wrong; "
                   f"first: {bad[:3]}")
    return len(written)


# -- parity and CRC, without the code under test ----------------------------------

def check_parity_sample(base: str, seed: int, sample_bytes: int) -> int:
    """A seeded sample of stripe columns from the 14 shard files: the 4
    parity rows must equal ops/rs_numpy.py's encode of the 10 data rows.
    Returns the data bytes compared."""
    shard_size = os.path.getsize(base + to_ext(0))
    piece = 64 << 10
    n_pieces = min(shard_size // piece,
                   -(-sample_bytes // (DATA_SHARDS * piece)))
    offsets = sorted(random.Random(seed).sample(
        range(0, shard_size // piece), n_pieces))
    cols = np.empty((TOTAL_SHARDS, n_pieces * piece), dtype=np.uint8)
    for sid in range(TOTAL_SHARDS):
        with open(base + to_ext(sid), "rb") as f:
            for k, off in enumerate(offsets):
                f.seek(off * piece)
                got = f.readinto(memoryview(cols[sid, k * piece:
                                                 (k + 1) * piece]))
                check(got == piece, f"short read of shard {sid}")
    matrix = gf256.parity_matrix(DATA_SHARDS, TOTAL_SHARDS)
    expect = gf_apply_matrix(matrix, cols[:DATA_SHARDS])
    for j in range(TOTAL_SHARDS - DATA_SHARDS):
        check(np.array_equal(expect[j], cols[DATA_SHARDS + j]),
              f"parity shard {DATA_SHARDS + j} differs from rs_numpy on the "
              f"sampled columns")
    return DATA_SHARDS * n_pieces * piece


def check_shard_crcs(base: str) -> int:
    """Every shard file's CRC32C against the .vif record (host crc32c,
    not the device kernel that produced the record)."""
    with open(base + ".vif") as f:
        recorded = json.load(f).get("shard_crc32c")
    check(isinstance(recorded, list) and len(recorded) == TOTAL_SHARDS,
          f".vif carries no 14-entry shard_crc32c record: {recorded!r}")
    for sid in range(TOTAL_SHARDS):
        crc = 0
        with open(base + to_ext(sid), "rb") as f:
            while True:
                buf = f.read(32 * MIB)
                if not buf:
                    break
                crc = crc32c(buf, crc)
        check(crc == recorded[sid],
              f"shard {sid}: file CRC32C {crc:#010x} != .vif "
              f"{recorded[sid]:#010x}")
    return TOTAL_SHARDS


# -- the run ----------------------------------------------------------------------

def run(args, workdir: str, daemons: Daemons, phases: dict) -> dict:
    rehearse = args.rehearse
    size_spec = REHEARSAL_SIZE if rehearse else FULL_SIZE
    want_platform = "cpu" if rehearse else "tpu"

    @contextlib.contextmanager
    def phase(name: str):
        log(f"{name} ...")
        t0 = time.monotonic()
        ph = phases[name] = {"ok": False}
        try:
            yield ph
            ph["ok"] = True
        finally:
            ph["seconds"] = round(time.monotonic() - t0, 3)
            log(f"{name} {'ok' if ph['ok'] else 'FAILED'} "
                f"({ph['seconds']} s)")

    with phase("build_native"):
        # -B: prove the toolchain on THIS machine, not a copied .so
        cmd = ["make", "-B", "-C", os.path.join(HERE, "native")]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=600)
        check(proc.returncode == 0,
              f"`{' '.join(cmd)}` failed:\n{proc.stderr[-3000:]}")

    env = dict(os.environ)
    env["WEED_EC_DEVICE_SHARD"] = str(args.devices)
    env.update(ASSUMED_ENV)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    cache_dir = ensure_compile_cache()  # the rule the daemons follow
    cache_before = cache_entries(cache_dir)
    log(f"compile cache: {cache_dir} ({cache_before} entries before)")

    master = f"127.0.0.1:{free_port()}"
    vs_port = free_port()
    vs = f"127.0.0.1:{vs_port}"
    os.makedirs(os.path.join(workdir, "master"))
    os.makedirs(os.path.join(workdir, "vol"))
    with phase("start_daemons") as ph:
        daemons.start("master", [
            "master", "-port", master.rsplit(":", 1)[1],
            "-mdir", os.path.join(workdir, "master"),
            "-pulseSeconds", "1"], env)
        wait_until("master", lambda: get_json(master, "/cluster/status"),
                   60, daemons)
        daemons.start("volume", [
            "volume", "-port", str(vs_port), "-mserver", master,
            "-dir", os.path.join(workdir, "vol"), "-max", "4",
            "-pulseSeconds", "1", "-ecBackend", "tpu"], env)
        wait_until("volume server", lambda: get_json(vs, "/admin/status"),
                   60, daemons)
        # the device as the VOLUME SERVER's own JAX reports it — asked
        # before any data is loaded, so a run without the chip ends here
        device = get_json(vs, "/admin/ec/recover_stats",
                          timeout=300).get("device")
        ph["device"] = device
        check(device is not None, "the volume server found no JAX backend")
        log(f"volume server device: platform: {device['platform']}, "
            f"device_kind: {device['device_kind']}, "
            f"count: {device['count']}")
        check(device["platform"] == want_platform,
              f"volume server runs on platform {device['platform']!r}, "
              f"this run needs {want_platform!r}")
        check(device["count"] >= args.devices,
              f"--devices {args.devices} but JAX reports "
              f"{device['count']}")

    with phase("load") as ph:
        grown = get_json(
            master, f"/vol/grow?collection={COLLECTION}&count=1", "POST")
        check(grown.get("count") == 1, f"/vol/grow -> {grown}")
        sizes = object_sizes(size_spec, args.seed)
        written, vid = load(master, sizes, args.seed)
        base = os.path.join(workdir, "vol", f"{COLLECTION}_{vid}")
        dat_size = os.path.getsize(base + ".dat")
        ph.update(objects=len(written), payload_bytes=sum(sizes),
                  volume=vid, dat_bytes=dat_size)
        log(f"volume {vid}: {len(written)} objects, .dat {dat_size} bytes")

    with phase("ec_encode") as ph:
        h2d0 = device_h2d_bytes(vs)
        plan = shell(master, f"ec.encode {vid} -collection={COLLECTION}")
        gen = plan.get("generate") or {}
        ph.update(backend=gen.get("backend"), devices=gen.get("devices"),
                  device=gen.get("device"))
        want_backend = ("device-pooled-swar" if rehearse else
                        "device-words" if args.devices == 1 else
                        "device-pooled-swar-fused-crc")
        log(f"encode backend: {gen.get('backend')}, "
            f"devices: {gen.get('devices')}")
        check(gen.get("backend") == want_backend,
              f"encode ran as {gen.get('backend')!r}, "
              f"expected {want_backend!r}")
        check(gen.get("devices") == args.devices,
              f"encode used {gen.get('devices')} devices, "
              f"expected {args.devices}")
        check((gen.get("device") or {}).get("platform") == want_platform
              and gen["stage_stats"].get("platform") == want_platform,
              f"encode reports device {gen.get('device')}, mesh platform "
              f"{gen['stage_stats'].get('platform')}")
        grew = h2d_growth(vs, h2d0)
        ph["h2d_bytes"] = grew
        if not rehearse:  # the CPU mesh aliases host memory: no upload
            check(sum(grew.values()) >= dat_size,
                  f"encode uploaded {grew}, must be >= .dat {dat_size}")
        shard_size = os.path.getsize(base + to_ext(0))
        ph["shard_bytes"] = shard_size

    with phase("read_after_encode") as ph:
        ph["objects"] = read_back(written, "after encode")

    with phase("parity_vs_rs_numpy") as ph:
        ph["data_bytes_compared"] = check_parity_sample(
            base, args.seed, (2 if rehearse else 64) * MIB)
        ph["shard_crcs_vs_vif"] = check_shard_crcs(base)

    with phase("degraded_read") as ph:
        h2d0 = device_h2d_bytes(vs)
        before = get_json(vs, "/admin/ec/recover_stats")
        get_json(vs, "/admin/ec/delete_shards", "POST",
                 {"volume": vid, "collection": COLLECTION,
                  "shard_ids": [LOST_FIRST]})
        check(not os.path.exists(base + to_ext(LOST_FIRST)),
              "shard file survived delete_shards")
        # one GET at a time until a read needs the lost shard: the
        # first recover (JAX already up from the encode) is timed alone
        for fid in sorted(written):
            t_get = time.monotonic()
            read_back({fid: written[fid]}, "first degraded GET", workers=1)
            took = time.monotonic() - t_get
            if get_json(vs, "/admin/ec/recover_stats")["cache_misses"] \
                    > before["cache_misses"]:
                ph["first_recover_get_seconds"] = round(took, 3)
                break
        ph["objects"] = read_back(written, "degraded")
        after = get_json(vs, "/admin/ec/recover_stats")
        decodes = after["device_decodes"] - before["device_decodes"]
        fallbacks = after["device_fallbacks"] - before["device_fallbacks"]
        blocks = after["cache_misses"] - before["cache_misses"]
        grew = h2d_growth(vs, h2d0)
        ph.update(device_decodes=decodes, device_fallbacks=fallbacks,
                  recovered_blocks=blocks, h2d_bytes=grew,
                  recover_stats={k: after[k] for k in (
                      "fetch_seconds", "decode_seconds", "serve_seconds",
                      "spans", "batches", "recovered_bytes")})
        log(f"recover: {blocks} blocks, device decodes {decodes}, "
            f"device fallbacks {fallbacks}")
        check(fallbacks == 0, f"{fallbacks} recover device fallbacks")
        check(blocks > 0, "no block was recovered: reads never degraded")
        if rehearse:
            check(decodes == 0, "recover dispatched to a CPU 'device'")
        else:
            # every object was read, so every block of the lost shard
            # that holds data was recovered: 10 survivor spans each
            must = DATA_SHARDS * (shard_size - SHARD_BLOCK)
            check(decodes > 0, "no recover ran on the device")
            check(all(k.startswith("TPU") for k in grew),
                  f"recover uploads went to {sorted(grew)}")
            check(sum(grew.values()) >= must,
                  f"recover uploaded {grew}, must be >= {must}")

    with phase("ec_rebuild") as ph:
        h2d0 = device_h2d_bytes(vs)
        get_json(vs, "/admin/ec/delete_shards", "POST",
                 {"volume": vid, "collection": COLLECTION,
                  "shard_ids": list(LOST_MORE)})
        plan = shell(master, f"ec.rebuild {vid} -collection={COLLECTION}")
        rb = plan.get("rebuild") or {}
        lost = sorted((LOST_FIRST, *LOST_MORE))
        ph.update(backend=rb.get("backend"), devices=rb.get("devices"),
                  device=rb.get("device"),
                  rebuilt=rb.get("rebuilt_shard_ids"))
        log(f"rebuild backend: {rb.get('backend')}, "
            f"devices: {rb.get('devices')}, "
            f"rebuilt: {rb.get('rebuilt_shard_ids')}")
        check(rb.get("rebuilt_shard_ids") == lost,
              f"rebuilt {rb.get('rebuilt_shard_ids')}, lost {lost}")
        check(rb.get("backend") == "device-apply-xla",
              f"rebuild ran as {rb.get('backend')!r}")
        check(rb.get("devices") == args.devices,
              f"rebuild used {rb.get('devices')} devices")
        check((rb.get("device") or {}).get("platform") == want_platform
              and rb["stage_stats"].get("platform") == want_platform,
              f"rebuild reports device {rb.get('device')}, mesh platform "
              f"{rb['stage_stats'].get('platform')}")
        grew = h2d_growth(vs, h2d0)
        ph["h2d_bytes"] = grew
        check(sum(grew.values()) >= DATA_SHARDS * shard_size,
              f"rebuild uploaded {grew}, must be >= "
              f"{DATA_SHARDS * shard_size}")

    with phase("read_after_rebuild") as ph:
        ph["objects"] = read_back(written, "after rebuild")
        ph["shard_crcs_vs_vif"] = check_shard_crcs(base)

    cache_after = cache_entries(cache_dir)
    log(f"compile cache: {cache_dir} ({cache_after} entries after)")
    n_large, n_small = size_spec[0][0], size_spec[1][0]
    return {
        "ok": True,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["count"]},
        "mode": "rehearsal (CPU backend; proves the flow, not the chip)"
                if rehearse else "chip",
        "seed": args.seed,
        "ec_devices": args.devices,
        "volume": {"objects": len(written), "large": n_large,
                   "small": n_small, "dat_bytes": dat_size,
                   "shard_bytes": shard_size},
        "reduced": (["rehearsal: 24 MiB volume instead of 960 MiB"]
                    if rehearse else []),
        "assumed": {**ASSUMED_ENV,
                    "WEED_EC_DEVICE_SHARD": str(args.devices)},
        "phases": phases,
        # what the daemon returned for ONE COLD RUN (first compile and
        # first touch included) — stage stats, not rates or metrics
        "cold_run_stage_stats": {"ec_encode": gen.get("stage_stats"),
                                 "ec_rebuild": rb.get("stage_stats")},
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_after},
        "claim": None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--devices", type=int, default=1,
                    help="chips the volume server's EC mesh spans "
                         "(WEED_EC_DEVICE_SHARD)")
    ap.add_argument("--rehearse", action="store_true",
                    help="same flow at a few MiB on the CPU backend")
    ap.add_argument("--workdir", default="",
                    help="where the volume lives (default: a temp dir)")
    ap.add_argument("--logdir", default="",
                    help="keep the daemons' logs here")
    args = ap.parse_args(argv)

    workdir = tempfile.mkdtemp(prefix="chip_smoke_",
                               dir=args.workdir or None)
    logdir = args.logdir or workdir
    os.makedirs(logdir, exist_ok=True)
    daemons = Daemons(logdir)
    phases: dict[str, dict] = {}
    result = None
    failure = None
    try:
        result = run(args, workdir, daemons, phases)
    except (SmokeFailure, OSError, subprocess.SubprocessError,
            http.client.HTTPException, KeyError, ValueError) as e:
        failure = f"{type(e).__name__}: {e}"
    finally:
        stubborn = daemons.stop()
        if failure or stubborn:
            print(daemons.tails(), file=sys.stderr)
        shutil.rmtree(workdir, ignore_errors=True)
    if stubborn:
        failure = (failure or "") + \
            f" daemons ignored SIGTERM and were killed: {stubborn}"
    if failure:
        print(f"chip_smoke FAILED: {failure}\nphases: "
              f"{json.dumps(phases)}", file=sys.stderr)
        return 1
    # the summary (ends with "claim": null), then the contract's result
    # line: exactly "ok" and the device as the volume server's JAX saw it
    print(json.dumps(result))
    print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
