"""Gateway fast-path structural checks (perf_smoke).

These assert the SHAPE of the fast path rather than wall-clock numbers,
so they stay meaningful on loaded CI boxes: amortized fid leasing must
collapse per-chunk master assigns, and the streamed GET pipeline must
deliver the first byte without waiting for the tail chunks."""

import os
import socket
import threading
import time

import pytest

pytestmark = pytest.mark.perf_smoke


@pytest.fixture
def stack(tmp_path):
    from seaweedfs_tpu.filer.server import FilerServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    d = tmp_path / "vs0"
    d.mkdir()
    vs = VolumeServer([str(d)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    vs.heartbeat_once()
    filer = FilerServer(master.address, port=0, chunk_size=1024)
    filer.start()
    yield master, vs, filer
    filer.stop()
    vs.stop()
    master.stop()


def test_leased_assigns_amortize_across_chunks(stack, monkeypatch):
    """An 8-chunk PUT with WEED_FILER_ASSIGN_LEASE=8 costs at most two
    master assign calls (one count=8 batch + at most one low-water
    background refill) instead of eight count=1 round trips."""
    from seaweedfs_tpu.rpc.http_rpc import call

    monkeypatch.setenv("WEED_FILER_ASSIGN_LEASE", "8")
    master, vs, filer = stack
    assigns = []
    orig = filer._assign

    def counting_assign(*args, **kwargs):
        assigns.append(kwargs.get("count", 1))
        return orig(*args, **kwargs)

    monkeypatch.setattr(filer, "_assign", counting_assign)
    payload = bytes(range(256)) * 32  # 8192 bytes -> 8 chunks of 1024
    resp = call(filer.address, "/smoke/eight.bin", raw=payload,
                method="POST")
    assert resp["size"] == len(payload)
    entry = filer.filer.find_entry("/smoke/eight.bin")
    assert len(entry.chunks) == 8
    sync_assigns = list(assigns)  # async refill may land after this
    assert len(sync_assigns) <= 2, sync_assigns
    assert sync_assigns[0] == 8  # batched, not per-chunk
    assert call(filer.address, "/smoke/eight.bin") == payload


def test_streamed_get_first_byte_before_last_chunk(stack, monkeypatch):
    """With a prefetch window of 2, the reply's first body bytes arrive
    while the object's LAST chunk has not even been requested from the
    volume layer — first-byte latency is one chunk fetch, independent
    of object size."""
    from seaweedfs_tpu.rpc.http_rpc import call

    monkeypatch.setenv("WEED_FILER_PREFETCH_CHUNKS", "2")
    master, vs, filer = stack
    payload = bytes(range(256)) * 32  # 8 chunks
    call(filer.address, "/smoke/stream.bin", raw=payload, method="POST")
    entry = filer.filer.find_entry("/smoke/stream.bin")
    last_fid = max(entry.chunks, key=lambda c: c.offset).fid

    fetched = []
    release_last = threading.Event()
    orig_fetch = filer._fetch_chunk

    def gated_fetch(fid):
        fetched.append(fid)
        if fid == last_fid:
            # hold the tail chunk back until the client has seen the
            # first body bytes (bounded by a timeout, not forever)
            release_last.wait(10.0)
        return orig_fetch(fid)

    monkeypatch.setattr(filer, "_fetch_chunk", gated_fetch)
    host, port = filer.address.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=15)
    try:
        sock.sendall(b"GET /smoke/stream.bin HTTP/1.1\r\n"
                     b"Host: smoke\r\nConnection: close\r\n\r\n")
        rfile = sock.makefile("rb")
        status = rfile.readline()
        assert b"200" in status, status
        clen = 0
        while True:
            line = rfile.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                clen = int(line.split(b":", 1)[1])
        assert clen == len(payload)
        first = rfile.read(1024)  # first chunk's worth of body
        assert first == payload[:1024]
        # the tail chunk is outside the prefetch window: untouched
        assert last_fid not in fetched
        release_last.set()
        rest = rfile.read(clen - 1024)
        assert first + rest == payload
    finally:
        release_last.set()
        sock.close()


def test_profiler_samples_busy_threads_and_joins():
    """The always-on sampler at its default rate (WEED_PROF_HZ) against
    eight spinning threads — the worst case for stack walking, every
    thread busy with real frames: it ticks, every spinner shows up in
    the folded stacks, no tick errors, and `stop()` joins its thread.
    What the duty cycle costs on a chip host is not measured."""
    from seaweedfs_tpu import profiling

    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(i * i for i in range(2000))

    names = {f"spin-{i}" for i in range(8)}
    workers = [threading.Thread(target=spin, name=n) for n in names]
    for w in workers:
        w.start()
    sampler = profiling.StackSampler()  # default rate: WEED_PROF_HZ
    sampler.start()

    def seen():
        return {k.split(";", 1)[0] for k in list(sampler.samples)}

    try:
        deadline = time.monotonic() + 30  # a hang fails, not a slow box
        while not names <= seen() and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        stop.set()
        for w in workers:
            w.join()
    assert sampler.stop(timeout=30), "sampler thread failed to join"
    assert names <= seen(), seen()
    assert sampler.total >= len(names)
    assert sampler.errors == 0
    assert 0.0 <= sampler.overhead_ratio() <= 1.0  # a share, measured


def test_maintenance_scrub_paced_under_foreground_load(tmp_path):
    """Deep-scrub I/O runs under the maintenance token bucket, and a
    saturated front end halves (here: floor-clamps) its effective rate.
    Fake clock: asserts the sleep arithmetic — every scrubbed byte is
    debited and the injected delay is exactly bytes/effective_rate
    minus the one-burst credit — not wall-clock numbers."""
    import numpy as np

    from seaweedfs_tpu.maintenance.deep_scrub import (deep_scrub,
                                                      local_target)
    from seaweedfs_tpu.maintenance.pacer import BytePacer
    from seaweedfs_tpu.storage.erasure_coding import TOTAL_SHARDS_COUNT
    from seaweedfs_tpu.storage.erasure_coding.encoder import (
        save_volume_info, write_ec_files)

    base = os.path.join(str(tmp_path), "1")
    rng = np.random.default_rng(7)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes())
    crcs = write_ec_files(base, batched=True)
    save_volume_info(base, version=3, extra={"shard_crc32c": crcs})

    pacer = BytePacer(rate_bytes=float(1 << 20),
                      load_fn=lambda: 1.0,  # shedder saturated
                      floor_frac=0.5)
    clock = {"t": 0.0}
    slept = []
    pacer.now = lambda: clock["t"]

    def fake_sleep(s):
        slept.append(s)
        clock["t"] += s

    pacer.sleep = fake_sleep
    eff = pacer.effective_rate()
    assert eff == pytest.approx(0.5 * (1 << 20))  # floor, not zero

    out = deep_scrub([local_target(base, 1)], throttle=pacer.throttle)
    assert out["corrupt"] == [] and out["volumes"][0]["ok"]
    total = sum(os.path.getsize(base + f".ec{sid:02d}")
                for sid in range(TOTAL_SHARDS_COUNT))
    # every shard byte was debited through the bucket
    assert pacer.paced_bytes == out["scrubbed_bytes"] == total
    # injected delay is deterministic: bytes at the floored rate minus
    # the single burst_seconds credit the bucket starts with
    assert sum(slept) == pytest.approx(
        total / eff - pacer.burst_seconds, rel=1e-6)
    assert pacer.throttled_seconds == pytest.approx(sum(slept))


def test_device_scale_dispatch_smoke(tmp_path, monkeypatch):
    """Two `encode_volumes` of four 256 KiB volumes on a CPU-device
    mesh: the SHAPE of the pooled device pipeline — the pooled backend
    was selected, the compiled-shape set stays bounded (one fixed batch
    geometry, not one compile per volume), and the second run re-leases
    the first run's slabs instead of allocating."""
    import jax
    import numpy as np

    from seaweedfs_tpu.ops.device_pool import get_pool, reset_pool
    from seaweedfs_tpu.parallel.batched_encode import encode_volumes
    from seaweedfs_tpu.parallel.mesh import make_ec_mesh

    def volumes(prefix, seed):
        bases = []
        for i in range(4):
            base = str(tmp_path / f"{prefix}{i}")
            rng = np.random.default_rng(seed + i)
            with open(base + ".dat", "wb") as f:
                f.write(rng.integers(0, 256, 256 << 10,
                                     dtype=np.uint8).tobytes())
            bases.append(base)
        return bases

    # the default retention cap holds one run's slabs on the 8-device
    # mesh (9 staging slots of one unit and an output slot a device);
    # an evicted slab would be an honest new allocation
    monkeypatch.delenv("WEED_EC_DEVICE_POOL_MB", raising=False)
    reset_pool()
    mesh = make_ec_mesh(jax.devices("cpu"))
    encode_volumes(volumes("dwarm", 500), mesh=mesh)
    allocs_after_first = get_pool().snapshot()["allocs"]
    st: dict = {}
    encode_volumes(volumes("dvol", 0), mesh=mesh, stage_stats=st)
    assert st["backend"].startswith("device-pooled")
    assert st["batches"] >= 1
    # one fixed compiled geometry: k-compaction may retrace per distinct
    # k, but equal-size volumes must share ONE shape
    assert len(st["k_shapes"]) == 1
    assert st["inflight"] >= 1
    snap = get_pool().snapshot()
    # the first encode populated the pool; the second re-leased
    assert snap["lease_hits"] > 0, snap
    assert snap["evictions"] == 0, snap
    assert st["pool"]["allocs"] == snap["allocs"] == allocs_after_first, \
        "second run allocated fresh slabs"
    reset_pool()


def test_cluster_scale_curve_smoke(tmp_path):
    """The same seeded zipfian replay (loadgen), closed-loop against a
    mini-cluster of 1 and of 2 volume servers: the replay runs to its
    end at both points with zero failed reads and real latency
    percentiles."""
    from seaweedfs_tpu import loadgen
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc import policy
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    num_objects = 60
    schedule = loadgen.build_schedule(
        duration_s=1.5, rate_rps=150.0, n_objects=num_objects,
        write_ratio=0.0)
    assert len(schedule) > 100  # the Poisson schedule actually ran
    payload = b"s" * 2048
    for n_servers in (1, 2):
        policy.BREAKERS.reset()  # keyed by address; ports come back
        master = MasterServer(port=0, pulse_seconds=1.0,
                              volume_size_limit_mb=1024,
                              maintenance_interval=3600.0)
        master.start()
        servers = []
        try:
            for i in range(n_servers):
                d = tmp_path / f"n{n_servers}vs{i}"
                d.mkdir()
                vs = VolumeServer([str(d)], master.address, port=0,
                                  pulse_seconds=1.0,
                                  max_volume_counts=[16])
                vs.start()
                vs.heartbeat_once()
                servers.append(vs)
            urls = []
            for _ in range(num_objects):
                a = call(master.address, "/dir/assign", timeout=30)
                call(a["url"], f"/{a['fid']}", raw=payload,
                     method="POST", timeout=30)
                urls.append((a["url"], a["fid"]))

            def send(req):
                url, fid = urls[req.obj % num_objects]
                try:
                    body = call(url, f"/{fid}", timeout=30)
                except RpcError as e:
                    if e.status != 503:
                        raise
                    time.sleep(0.05)
                    body = call(url, f"/{fid}", timeout=30)
                return body == payload

            out = loadgen.replay(schedule, send, workers=8,
                                 open_loop=False)
        finally:
            for vs in servers:
                vs.stop()
            master.stop()
        assert out["requests"] == len(schedule), (n_servers, out)
        assert out["failures"] == 0, (n_servers, out)
        assert out["p99_ms"] >= out["p50_ms"] > 0, (n_servers, out)


def test_read_cache_warm_storm_takes_ram_hits(stack):
    """Two GET passes over the same chunked objects on the filer
    object-GET path, the first with every cache tier cleared: the
    second pass is served by the chunk cache's RAM tier, and the
    cache's own accounting shows it."""
    from seaweedfs_tpu.rpc.http_rpc import call

    master, vs, filer = stack
    payload = b"r" * 4096  # past the inline limit: 4 chunks of 1024
    paths = [f"/rcache/f{i}" for i in range(40)]
    for p in paths:
        call(filer.address, p, raw=payload, method="POST")
    filer.chunk_cache.clear()
    vs.read_cache.clear()
    for _ in range(2):
        for p in paths:
            assert call(filer.address, p) == payload
    fc = filer.chunk_cache.stats_snapshot()
    assert fc["tier_hits"]["ram"] > 0
    assert 0.0 < fc["hit_ratio"] <= 1.0
    assert set(fc["tier_hits"]) == {"hbm", "ram", "disk"}
    assert set(fc["fills"]) == {"admitted", "qos_bypass"}


def test_lint_dashboards_and_slo_rules():
    """`weed.py lint-dashboards` as a library call: every Grafana panel
    query and every active SLO rule must resolve against the metric
    registry — a renamed family must fail CI, not blank a panel."""
    from seaweedfs_tpu.stats import lint

    assert lint.run() == []


def test_health_scrape_round_touches_every_target(stack):
    """Dedicated scrape rounds against a live master+volume+filer
    stack: every target the master knows is scraped and up, each round
    is counted, and the plane accounts its own busy time.  What a round
    costs is a number for the chip host, not asserted here."""
    master, vs, filer = stack
    plane = master.health
    want = {master.address: "master", vs.address: "volume",
            filer.address: "filer"}
    deadline = time.monotonic() + 30  # the filer registers by itself
    while plane.targets() != want and time.monotonic() < deadline:
        time.sleep(0.02)
    targets = plane.targets()
    assert targets == want
    # the loop thread may also be scraping: count dedicated rounds
    rounds, before = 5, plane.rounds
    for _ in range(rounds):
        plane.scrape_round()
    assert plane.rounds >= before + rounds
    assert {a: plane._up.get(a) for a in targets} == \
        {a: 1 for a in targets}
    assert plane.busy_seconds > 0
