"""Driver `ycsb_filer`: YCSB's core workloads against a stand-alone filer.

N closed-loop callers, no think time, each drawing read or update by the
traffic file's weights and a record by YCSB's scrambled zipfian from its
own seeded stream.  A record is one file under the traffic's `folder`,
as the YCSB `seaweedfs` binding stores it: a read is `GET <folder>/<key>`;
an update is a `GET` and a `POST` of the same path, one field replaced
and the whole record written back, timed as one operation.  Every `GET`
body is handed to `reference_ycsb.Register`, outside the timed span: it
has to be, byte for byte, a version the register may answer with.

`Cluster` starts one master and one volume server.  The filer is this
driver's: `prepare` starts `weed.py filer` through `run.cluster.daemons`,
the harness's own child-process list, from the configuration's `gateways`
key, so that `Daemons.stop()`, `tails()` and `--keep-logs` cover it, and
`wait_until` fails the run as soon as the child exits (a parent's
`weed.py` does not know `-saveToFilerLimit`).

The load phase is not the run phase: `prepare` writes the loaded state
with the program's own writers while the cluster boots, as `volumes.py`
does for sealed volumes.  Needles go through `Volume.write_needle` into
`volumes` volumes of the default collection (worker processes, a volume
each), which the volume server then mounts and the master serves; entries
go through `SqliteStore.insert_entry` inside one transaction, into the
file the filer was started on and has not yet read.  A needle is what the filer's own upload would
have left (mime, time stamp), so the volume server counts a staged chunk
as it counts a served one.  Before a window opens, seeded reads and
updates through the served path are held to the reference: a deployment
that cannot serve its staged state fails there.

The harness scrapes the volume server only; the filer's `/metrics` is
scraped here, before and after the callers run, and kept in
`run.records["filer_prom"]` for the reader `prometheus_delta`.  Every run
logs the filer's stages a block from those two scrapes, traced or not.

The device's part is `put-get-open`'s: `device_touch.at_s` into every
window one `ec.encode` of a small volume of another collection (the
functions are `s3_mixed`'s).
"""

from __future__ import annotations

import hashlib
import http.client
import json
import multiprocessing
import os
import random
import sys
import threading
import time
from concurrent.futures import ProcessPoolExecutor, as_completed

import numpy as np

import reference
import reference_ycsb
import volumes
from cluster import (ROOT, BenchFailure, call, free_port, scrape,
                     wait_until)
from drivers.s3_mixed import _after, _make_touch_volume, _touch_device

KINDS = ("read", "update")
RECORD_MIME = "application/json"
# what the filer's chunk upload tells the volume server, so that a staged
# needle has the flags, and with them the size, of a served one
CHUNK_MIME = b"application/octet-stream"
FIRST_VID = 1
SLICE_S = 4


# -- the load phase ------------------------------------------------------------

def stage_volume(job: dict) -> list[tuple]:
    """One volume of the loaded state (a worker process's whole job):
    the records whose number is `slot` modulo `volumes`, each a needle
    whose id is the record's number + 1.  Returns a row a record:
    (record, fid, chunk etag, body md5, body bytes, needle size)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    records = reference_ycsb.Records(job["seed"], job["fieldcount"],
                                     job["fieldlength"])
    vid = FIRST_VID + job["slot"]
    cookies = random.Random(job["seed"] * 1009 + vid)
    vol = Volume(job["directory"], "", vid)
    rows = []
    try:
        for record in range(job["slot"], job["records"], job["volumes"]):
            body = records.loaded_body(record)
            n = Needle.create(body, mime=CHUNK_MIME,
                              last_modified=job["now"])
            n.id, n.cookie = record + 1, cookies.getrandbits(32)
            _, size, _ = vol.write_needle(n)
            rows.append((record, reference.fid(vid, n.id, n.cookie),
                         n.etag(), hashlib.md5(body).hexdigest(), len(body),
                         size))
        vol.sync()
    finally:
        vol.close()
    return rows


def stage_entries(db: str, folder: str, keys: list[str], batches, now: float):
    """The entry store of the loaded state: the folder, then a row a
    record as `FilerServer.save_bytes` would have written it, all under
    one commit.  `batches` yields `stage_volume`'s rows as they come."""
    from seaweedfs_tpu.filer.entry import (Attr, Entry, FileChunk,
                                           new_directory_entry)
    from seaweedfs_tpu.filer.filer import Filer
    from seaweedfs_tpu.filer.filer_store import SqliteStore

    store = SqliteStore(db)
    filer = Filer(store)
    filer.create_entry(new_directory_entry(folder))
    filer.close()
    sizes = set()
    with store.transaction():
        for rows in batches:
            for record, fid, etag, md5, nbytes, size in rows:
                sizes.add(size - nbytes)
                store.insert_entry(Entry(
                    full_path=f"{folder}/{keys[record]}",
                    attr=Attr(mtime=now, crtime=now, mime=RECORD_MIME,
                              md5=md5, file_size=nbytes),
                    chunks=[FileChunk(fid=fid, offset=0, size=nbytes,
                                      etag=etag,
                                      modified_ts_ns=int(now * 1e9))]))
    store.close()
    if len(sizes) != 1:
        raise BenchFailure(f"staged needles carry {sorted(sizes)} bytes "
                           f"beyond their data: expected one number")
    return sizes.pop()


def stage(run, state) -> None:
    """Volumes and entry store of the loaded state, under the run's
    directory, beside the booting cluster."""
    t = state.traffic
    directory = os.path.join(run.workdir, "staged")
    os.makedirs(directory)
    now = time.time()
    jobs = [{"seed": run.seed, "slot": slot, "volumes": t["volumes"],
             "records": t["records"], "fieldcount": t["fieldcount"],
             "fieldlength": t["fieldlength"], "directory": directory,
             "now": int(now)} for slot in range(t["volumes"])]
    t0 = time.perf_counter()
    if t["stage_workers"]:
        # spawn, not fork: the harness's boot thread is running
        pool = ProcessPoolExecutor(
            t["stage_workers"],
            mp_context=multiprocessing.get_context("spawn"))
        futures = [pool.submit(stage_volume, job) for job in jobs]
        batches = (f.result() for f in as_completed(futures))
    else:
        pool = None
        batches = (stage_volume(job) for job in jobs)
    try:
        state.keys = [reference_ycsb.key_of(r) for r in range(t["records"])]
        state.needle_overhead = stage_entries(
            state.db, t["folder"], state.keys, batches, now)
    finally:
        if pool is not None:
            pool.shutdown()
    state.staged = [os.path.join(directory, str(FIRST_VID + slot))
                    for slot in range(t["volumes"])]
    nbytes = sum(os.path.getsize(b + ".dat") for b in state.staged)
    run.log(f"staged {t['records']} records in {t['volumes']} volumes "
            f"({nbytes} bytes of .dat, {state.needle_overhead} bytes a "
            f"needle beyond its data) and an entry store of "
            f"{os.path.getsize(state.db)} bytes in "
            f"{time.perf_counter() - t0:.3f} s ({t['stage_workers']} worker "
            f"processes)")


def mount_staged(run, state):
    """Hand the staged volumes to the volume server and wait until the
    master serves them: the heartbeat that names a volume also raises
    the master's needle-id sequence over the staged ids."""
    vs, master = run.cluster.volume, run.cluster.master
    for slot, base in enumerate(state.staged):
        vid = FIRST_VID + slot
        volumes.link_volume(base, os.path.join(run.cluster.vol_dir, str(vid)))
        call(vs, "/admin/volume/mount", {"volume": vid, "collection": ""})
    for slot in range(len(state.staged)):
        vid = FIRST_VID + slot
        wait_until(f"the master to serve volume {vid}",
                   lambda: call(master, f"/dir/lookup?volumeId={vid}"),
                   60, run.cluster.daemons)


# -- the filer and its clients -----------------------------------------------------

class Caller:
    """One caller's keep-alive connection to the filer."""

    def __init__(self, addr: str, timeout: float):
        host, port = addr.rsplit(":", 1)
        self.host, self.port, self.timeout = host, int(port), timeout
        self.conn: http.client.HTTPConnection | None = None

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def ask(self, method: str, path: str, body: bytes | None = None,
            headers: dict | None = None) -> tuple[int, bytes]:
        """Any failure of the transport closes the connection and
        raises."""
        try:
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            self.conn.request(method, path, body=body, headers=headers or {})
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except (http.client.HTTPException, OSError):
            self.close()
            raise


class State:
    def __init__(self, run):
        self.run = run
        self.traffic = t = run.traffic
        self.db = os.path.join(run.workdir, "filer.db")
        self.filer = ""
        self.keys: list[str] = []
        self.staged: list[str] = []
        self.needle_overhead = 0
        self.records = reference_ycsb.Records(run.seed, t["fieldcount"],
                                              t["fieldlength"])
        self.register = reference_ycsb.Register(self.records)
        self.keyspace = reference_ycsb.ScrambledZipfian(t["records"])
        self.lock = threading.Lock()      # the register and the tallies
        self.sequence = [0] * t["clients"]
        self.samples: dict[str, list[float]] = {}
        self.done: list[float] = []
        self.wrong_bodies = 0
        self.stale_reads = 0
        self.deleted_before = (0, 0)
        self.touch = None

    def path(self, record: int) -> str:
        return f"{self.traffic['folder']}/{self.keys[record]}"

    def judge(self, record: int, began: float, ended: float,
              body: bytes) -> bool:
        with self.lock:
            verdict = self.register.check_read(record, began, ended, body)
            if verdict == reference_ycsb.STALE:
                self.stale_reads += 1
            elif verdict != reference_ycsb.OK:
                self.wrong_bodies += 1
        return verdict == reference_ycsb.OK


def draws(seed: int, caller: int, warm: bool = False) -> random.Random:
    """The stream a caller draws its operations, records and fields
    from; the set-up's callers draw from streams of their own."""
    return random.Random(seed * 7919 + caller + (0 if warm else 10007))


# each returns (seconds, ok); what it compares lies outside the seconds

def op_read(state: State, client: Caller, caller: int, rng):
    record = state.keyspace.record(rng.random())
    t0 = time.perf_counter()
    status, body = client.ask("GET", state.path(record))
    t1 = time.perf_counter()
    if status != 200:
        return t1 - t0, False
    body = state.run.control("get_body", body)
    return t1 - t0, state.judge(record, t0, t1, body)


def op_update(state: State, client: Caller, caller: int, rng):
    record = state.keyspace.record(rng.random())
    field = f"field{rng.randrange(state.traffic['fieldcount'])}"
    sequence = state.sequence[caller]
    state.sequence[caller] = sequence + 1
    path = state.path(record)
    t0 = time.perf_counter()
    status, body = client.ask("GET", path)
    t_read = time.perf_counter()
    if status != 200:
        return t_read - t0, False
    fields = json.loads(body)
    fields[field] = state.records.updated(caller, sequence)
    new = state.records.encode(fields)
    with state.lock:
        version = state.register.write_began(record, new,
                                             time.perf_counter())
    status, _ = client.ask("POST", path, new,
                           {"Content-Type": RECORD_MIME})
    t1 = time.perf_counter()
    if status not in (200, 201):
        return t1 - t0, False
    with state.lock:
        state.register.write_acked(version, t1)
    return t1 - t0, state.judge(record, t0, t_read, body)


OPS = {"read": op_read, "update": op_update}


def _callers(state: State, n: int, draw, until, record: bool) -> float:
    """Run n closed-loop callers until `until()`; `draw(caller, rng)`
    names each one's next operation.  Returns the time of the last
    completion."""
    last = [0.0] * n
    errors = []
    failed_in_setup = []
    timeout = state.traffic["request_timeout_s"]

    def caller(c: int):
        rng = draws(state.run.seed, c, warm=not record)
        client = Caller(state.filer, timeout)
        samples = {kind: [] for kind in KINDS}
        done = []
        try:
            while not until():
                kind = draw(c, rng)
                t0 = time.perf_counter()
                try:
                    took, ok = OPS[kind](state, client, c, rng)
                except (http.client.HTTPException, OSError) as e:
                    # a timed-out or short reply: a failed operation and
                    # a new connection, never a wait
                    took, ok = time.perf_counter() - t0, False
                    state.run.log(f"caller {c}: {kind} failed: "
                                  f"{type(e).__name__}: {e}"[:300])
                if record:
                    state.run.span("ycsb_" + kind, t0, t0 + took)
                    samples[kind].append(took if ok else float("nan"))
                    done.append(t0 + took)
                elif not ok:
                    failed_in_setup.append(kind)
                last[c] = time.perf_counter()
        except Exception as e:   # a caller thread must report, not vanish
            errors.append(e)
        finally:
            client.close()
        with state.lock:
            for kind, vals in samples.items():
                state.samples.setdefault(kind, []).extend(vals)
            state.done += done

    threads = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    if failed_in_setup:
        # a deployment that cannot serve its staged state cannot run the
        # cell: no window, no result
        raise BenchFailure(
            f"{len(failed_in_setup)} operations of the set-up failed or "
            f"answered wrong: " + ", ".join(
                f"{failed_in_setup.count(k)} {k}" for k in KINDS
                if k in failed_in_setup))
    return max(last)


def _start_filer(run, state: State):
    """`weed.py filer` as the configuration's `gateways` entry says, a
    child of the harness like the master and the volume server."""
    spec = run.config["gateways"][0]
    port = free_port()
    state.filer = f"127.0.0.1:{port}"
    fill = {"master": run.cluster.master, "filer_port": port,
            "filer_db": state.db}
    argv = [sys.executable, os.path.join(ROOT, "weed.py"),
            *[a.format(**fill) for a in spec["args"]]]
    # the master first: the filer registers with it at start
    wait_until("master", lambda: call(run.cluster.master,
                                      "/cluster/status"), 120,
               run.cluster.daemons)
    run.cluster.daemons.start(spec["name"], argv, run.cluster.env)
    # a route that leaves the entry store alone: the load phase is about
    # to write it.  A filer that does not know its flags has exited by now
    wait_until("the filer", lambda: scrape(state.filer), 120,
               run.cluster.daemons)
    run.log(f"filer on {state.filer}")


def _deleted(run) -> tuple[int, int]:
    """(needles, bytes) the volume server counts as deleted and not yet
    collected."""
    vols = call(run.cluster.volume, "/admin/status").get("volumes", [])
    return (sum(v.get("delete_count", 0) for v in vols),
            sum(v.get("deleted_byte_count", 0) for v in vols))


def prepare(run) -> State:
    state = State(run)
    t = state.traffic
    # the filer first: it touches its store when the first request does,
    # and a tree whose filer cannot start fails here, within seconds
    _start_filer(run, state)
    stage(run, state)
    touch = t["device_touch"]
    state.touch = _make_touch_volume(run, touch["collection"],
                                     touch["volume"]["objects"])
    run.wait_cluster()
    mount_staged(run, state)
    _touch_device(run, state)    # compiles or loads the encode step
    hot, share = state.keyspace.hottest()
    run.log(f"the hottest record is number {hot} ({state.keys[hot]}): "
            f"{100 * share:.2f}% of all draws")
    # the served path over the staged state, warmed by every caller and
    # held to the reference before a window is opened on it; the register
    # holds the set-up's writes too, so what they supersede is counted
    state.deleted_before = _deleted(run)
    t0 = time.perf_counter()
    _callers(state, t["clients"], lambda c, rng: "read",
             _after(t["warm_reads"]), False)
    _callers(state, t["clients"], lambda c, rng: "update",
             _after(t["warm_updates"]), False)
    run.log(f"{t['warm_reads']} reads and {t['warm_updates']} updates of "
            f"the set-up answered as the reference does, in "
            f"{time.perf_counter() - t0:.3f} s")
    return state


# -- the window ------------------------------------------------------------------------

def _scrape_filer(run, state: State):
    """The filer's `/metrics`, tolerantly: a filer that cannot be scraped
    leaves its metrics out and fails nothing."""
    try:
        samples = scrape(state.filer)
    except (BenchFailure, OSError, http.client.HTTPException) as e:
        run.log(f"the filer's /metrics could not be scraped: {e}")
        samples = []
    run.records.setdefault("filer_prom", []).append({"samples": samples})


def _log_filer(run):
    """What the filer's own counters say of the window, in every run's
    log: a stage's milliseconds a block, and the counters beside them."""
    before, after = (r["samples"] for r in run.records["filer_prom"][-2:])

    def delta(family: str, **labels) -> float:
        def total(samples):
            return sum(v for name, lab, v in samples if name == family
                       and all(lab.get(k) == w for k, w in labels.items()))
        return total(after) - total(before)

    stages = sorted({lab["stage"] for name, lab, _ in after
                     if name == "SeaweedFS_filer_stage_blocks_total"})
    cells = []
    for stage_ in stages:
        blocks = delta("SeaweedFS_filer_stage_blocks_total", stage=stage_)
        if blocks:
            ms = 1e3 * delta("SeaweedFS_filer_stage_seconds_total",
                             stage=stage_) / blocks
            cells.append(f"{stage_} {ms:.3f} x {blocks:.0f}")
    run.log("  the filer's stages, ms a block x blocks: " + "; ".join(cells))
    hits = delta("SeaweedFS_filer_chunk_cache_total", result="hit")
    misses = delta("SeaweedFS_filer_chunk_cache_total", result="miss")
    probes = delta("SeaweedFS_profiler_gil_wait_seconds_count")
    gil = 1e3 * delta("SeaweedFS_profiler_gil_wait_seconds_sum") / probes \
        if probes else float("nan")
    run.log(f"  the filer's counters: overwrites "
            f"{delta('SeaweedFS_filer_overwrites_total'):.0f}, chunks "
            f"reclaimed {delta('SeaweedFS_filer_reclaimed_chunks_total'):.0f}"
            f" ({delta('SeaweedFS_filer_reclaimed_bytes_total'):.0f} bytes),"
            f" reads that read again after a reclaimed chunk "
            f"{delta('SeaweedFS_filer_read_retries_total'):.0f}, chunk cache "
            f"{hits:.0f} hits / {misses:.0f} misses, fid lease refills "
            f"{delta('SeaweedFS_filer_fid_lease_total', event='refill'):.0f},"
            f" GIL wait {gil:.3f} ms over {probes:.0f} probes")


def window(run, state: State, seconds: float) -> dict:
    t = state.traffic
    state.samples = {}
    state.done = []
    weights = [t["mix"][k] for k in KINDS]
    _scrape_filer(run, state)
    t_open = time.perf_counter()
    t_end = t_open + seconds
    touch_error = []

    def touch():
        try:
            _touch_device(run, state)
        except Exception as e:   # raised below, on the window's thread
            touch_error.append(e)

    timer = threading.Timer(t["device_touch"]["at_s"], touch)
    timer.start()
    last = _callers(state, t["clients"],
                    lambda c, rng: rng.choices(KINDS, weights)[0],
                    lambda: time.perf_counter() >= t_end, True)
    timer.join()
    if touch_error:
        raise touch_error[0]
    _scrape_filer(run, state)
    t0, t1 = run.spans["device_touch.seal"][-1]
    run.log(f"the device touch (one ec.encode of a small volume) ran from "
            f"{t0 - t_open:.2f} to {t1 - t_open:.2f} s of the window")
    elapsed = last - t_open
    lat = np.array([v for vals in state.samples.values() for v in vals])
    good = lat[~np.isnan(lat)] * 1e3
    attempted = int(lat.size)
    failed = attempted - int(good.size)
    if not good.size:
        raise BenchFailure("no operation completed in the window")
    p50, p95 = np.percentile(good, [50, 95])
    run.log(f"window: {t['clients']} closed-loop callers for {elapsed:.3f} s:"
            f" {attempted} operations, {failed} failed or wrong; p50 "
            f"{p50:.3f} ms, p95 {p95:.3f} ms, max {good.max():.3f} ms")
    run.log("  deciles p10..p90 ms: " + " ".join(
        f"{v:.2f}" for v in np.percentile(good, range(10, 100, 10))))
    for kind in KINDS:
        v = np.array(state.samples.get(kind, []))
        ok = v[~np.isnan(v)] * 1e3
        if ok.size:
            q = np.percentile(ok, [50, 95, 99])
            run.log(f"  {kind}: {ok.size} ok of {v.size} "
                    f"({100 * v.size / attempted:.1f}% of the mix), p50 "
                    f"{q[0]:.3f} p95 {q[1]:.3f} p99 {q[2]:.3f} ms")
    edges = np.append(np.arange(0.0, seconds, SLICE_S), max(elapsed, seconds))
    at = np.array(state.done) - t_open
    run.log(f"  operations a second by {SLICE_S} s slices: " + " ".join(
        f"{r:.0f}" for r in np.histogram(at, edges)[0] / np.diff(edges)))
    written = state.register.written()
    most = max((len(state.register.versions[r]) - 1, r) for r in written) \
        if written else (0, -1)
    run.log(f"  {len(written)} records were written, record {most[1]} "
            f"{most[0]} times")
    _log_filer(run)
    # operations acknowledged and right, a second: per layer in these
    # cells (`goodput`'s list is held to put-get-open by an accepted test)
    run.counts["ycsb_ops_per_s"] = good.size / elapsed
    run.log(f"  {good.size / elapsed:.3f} operations a second")
    return {"attempted": attempted, "failed": failed, "elapsed_s": elapsed,
            "end_to_end": {"op_p50_ms": float(p50), "op_p95_ms": float(p95)}}


# -- after the window ----------------------------------------------------------------

def _listing(state: State, client: Caller) -> list[str]:
    folder = state.traffic["folder"]
    names, last = [], ""
    while True:
        status, body = client.ask(
            "GET", f"{folder}/?limit={state.traffic['listing_page']}"
                   f"&lastFileName={last}")
        if status != 200:
            raise BenchFailure(f"listing of {folder} -> {status}")
        page = json.loads(body)
        names += [e["FullPath"].rsplit("/", 1)[1] for e in page["Entries"]]
        if not page["ShouldDisplayLoadMore"]:
            return names
        last = page["LastFileName"]


def verify(run, state: State, result: dict) -> list[dict]:
    t = state.traffic
    reg = state.register
    wrong = state.wrong_bodies + state.stale_reads
    out = [run.compare("operations_failed", result["failed"] - wrong, 0),
           run.compare("get_bodies_torn_or_never_written",
                       state.wrong_bodies, 0),
           run.compare("stale_reads", state.stale_reads, 0)]
    client = Caller(state.filer, 60.0)
    # the folder's listing against the key set
    got = _listing(state, client)
    want = set(state.keys)
    missing = len(want - set(got))
    extra = len(got) - len(want & set(got))
    run.log(f"the folder lists {len(got)} names, the key set holds "
            f"{len(want)}; {missing} missing, {extra} extra or repeated")
    out.append(run.compare("listing_names_missing", missing, 0))
    out.append(run.compare("listing_names_extra", extra, 0))
    # the version that stands: every key written in the window, and a
    # seeded sample of the others
    written = sorted(reg.written())
    rng = random.Random(run.seed + 2)
    others = rng.sample(range(t["records"]),
                        min(t["records"], t["readback_others"]))
    standing = {}
    bad = lost = 0
    for record in dict.fromkeys(written + others):
        t0 = time.perf_counter()
        try:
            status, body = client.ask("GET", state.path(record))
        except (http.client.HTTPException, OSError):
            status, body = None, b""
            lost += 1
        t1 = time.perf_counter()
        if status == 200 and reg.check_read(record, t0, t1, body) \
                == reference_ycsb.OK:
            standing[record] = body
        else:
            bad += 1
    client.close()
    run.log(f"read back {len(written)} written records and "
            f"{len(others)} others: {bad} hold no whole version or a "
            f"superseded one ({lost} replies lost in transport)")
    out.append(run.compare("standing_versions_wrong", bad, 0))
    # what the volume server counts as deleted: the superseded chunks
    # and nothing else
    chunks, payload = reg.superseded_bytes(standing)
    owed = payload + chunks * state.needle_overhead
    needles, nbytes = (a - b for a, b in zip(_deleted(run),
                                             state.deleted_before))
    run.log(f"{reg.acknowledged_writes()} writes were acknowledged; "
            f"{chunks} chunks of {payload} bytes stand no longer ({owed} "
            f"bytes as needles); the volume server counts {needles} more "
            f"needles and {nbytes} more bytes deleted")
    out.append(run.compare("superseded_chunks_not_deleted_or_standing_"
                           "ones_deleted", abs(chunks - needles), 0))
    out.append(run.compare("deleted_bytes_off_the_superseded_chunks",
                           abs(owed - nbytes), 0))
    seals = run.records.get("device_touch", [])
    off = [r for r in seals
           if r.get("backend") != run.expect["encode_backend"]
           or (r.get("device") or {}).get("platform")
           != run.expect["platform"]]
    out.append(run.compare("device_touch_seals_missing_or_off_device",
                           int(len(seals) != 1) + len(off), 0))
    return out
