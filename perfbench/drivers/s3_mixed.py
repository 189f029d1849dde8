"""Driver `s3_mixed`: MinIO `warp mixed` against the S3 gateway.

N closed-loop callers, no think time, each drawing GET / STAT (HEAD) / PUT
/ DELETE by the traffic file's weights from its own seeded stream, over a
pool of objects that set-up PUT through the same gateway.  A DELETE takes
an object out of the pool and a PUT adds one, as `warp mixed` does.  Every
request carries a SigV4 header signature and the body's SHA-256
(`reference_s3.sign`); every answer is decided by the plain bucket of
`reference_s3`, which is why a key is taken out of the pool before its
DELETE goes out and never while a GET or HEAD of it is in flight.

`Cluster` starts one master and one volume server.  The gateway is this
driver's: `prepare` starts `weed.py s3` through `run.cluster.daemons`, the
harness's own child-process list, from the configuration's `gateways`
key, so that `Daemons.stop()`, `tails()` and `--keep-logs` cover it.

A body is a window into one pool of seeded bytes, so that a GET is
compared with the bytes its PUT sent without keeping them.  Latency is the
client's: signature begun to body read; the comparison lies outside it.
A timed-out or short reply is a failed operation and a new connection.

The harness scrapes the volume server only; the gateway's `/metrics` is
scraped here, before and after the callers run, and kept in
`run.records["s3_prom"]` for the reader `prometheus_delta`.

The device's part is `put-get-open`'s: `device_touch.at_s` into every
window one `ec.encode` of a small volume of another collection (copied
from `closed_loop_ops`: the benchmark's contract refuses a cell whose
traced window holds no device operation).
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import random
import sys
import threading
import time

import numpy as np

import reference
import reference_s3
import volumes
from cluster import (ROOT, BenchFailure, call, free_port, scrape,
                     wait_until)

ALL_SHARDS = list(range(reference.TOTAL_SHARDS))
KINDS = ("get", "stat", "put", "delete")
WRONG_SECRET = "not-the-secret"
SLICE_S = 4     # the window's log by slices
STALL_S = 0.1   # no caller answered for this long: a stall


class S3Client:
    """One caller's keep-alive connection to the gateway."""

    def __init__(self, state, timeout: float):
        self.addr = state.s3
        self.access_key = state.identity["access_key"]
        self.secret_key = state.identity["secret_key"]
        self.timeout = timeout
        self.conn: http.client.HTTPConnection | None = None
        self.buf = bytearray(state.traffic["object_bytes"])

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def ask(self, method: str, path: str, query: dict | None = None,
            body=b"", payload_hash: str | None = None,
            secret_key: str | None = None):
        """One signed request.  Returns (status, headers, body): the body
        is a view of this client's buffer when it fits, valid until the
        next call.  Any failure of the transport closes the connection
        and raises."""
        if payload_hash is None:
            payload_hash = hashlib.sha256(body).hexdigest() if len(body) \
                else reference_s3.EMPTY_SHA256
        headers = reference_s3.sign(
            method, self.addr, path, query, payload_hash, self.access_key,
            secret_key or self.secret_key)
        if len(body) or method == "PUT":
            headers["Content-Length"] = str(len(body))
        url = path if not query else path + "?" + "&".join(
            f"{k}={reference_s3.quote(str(v))}" for k, v in query.items())
        try:
            if self.conn is None:
                host, port = self.addr.rsplit(":", 1)
                self.conn = http.client.HTTPConnection(
                    host, int(port), timeout=self.timeout)
            self.conn.request(method, url, body=body if len(body) else None,
                              headers=headers)
            resp = self.conn.getresponse()
            length = resp.getheader("Content-Length")
            if method != "HEAD" and length is not None \
                    and int(length) <= len(self.buf):
                want, have = int(length), 0
                view = memoryview(self.buf)
                while have < want:
                    n = resp.readinto(view[have:want])
                    if not n:
                        raise http.client.IncompleteRead(
                            bytes(0), want - have)
                    have += n
                resp.read()     # the connection is free again
                data = view[:want]
            else:
                data = resp.read()
            return resp.status, resp.headers, data
        except (http.client.HTTPException, OSError):
            self.close()
            raise


class State:
    def __init__(self, run):
        self.run = run
        self.traffic = run.traffic
        self.bucket = run.traffic["bucket"]
        self.reference = reference_s3.Bucket()
        self.lock = threading.Lock()
        self.live: list[str] = []           # the pool a draw is made from
        self.where: dict[str, int] = {}     # key -> its index in `live`
        self.readers: dict[str, int] = {}   # key -> GETs / HEADs in flight
        self.offsets: dict[str, int] = {}   # key -> its window of the pool
        self.sequence = [0] * run.traffic["clients"]
        self.pool = b""
        self.s3 = ""
        self.identity: dict = {}
        self.samples: dict[str, list[float]] = {}
        self.done: list[tuple[float, str]] = []  # (completion, kind)
        self.wrong_bodies = 0
        self.wrong_heads = 0
        self.deleted_before = 0
        self.touch = None

    # -- the pool of live keys (under self.lock) -----------------------------
    def add(self, key: str):
        self.where[key] = len(self.live)
        self.live.append(key)

    def take_out(self, key: str):
        i = self.where.pop(key)
        last = self.live.pop()
        if last != key:
            self.live[i] = last
            self.where[last] = i

    def new_key(self, caller: int) -> str:
        """A key no PUT has used; its body is a window of the pool."""
        n = self.sequence[caller]
        self.sequence[caller] = n + 1
        key = f"c{caller:02d}/{n:06d}.rnd"
        serial = n * len(self.sequence) + caller
        room = len(self.pool) - self.traffic["object_bytes"]
        self.offsets[key] = (serial * 1_000_003) % (room + 1)
        return key

    def body_of(self, key: str) -> memoryview:
        off = self.offsets[key]
        return memoryview(self.pool)[off:off + self.traffic["object_bytes"]]

    def path(self, key: str = "") -> str:
        return f"/{self.bucket}/{key}" if key else f"/{self.bucket}"


# -- the four operations -------------------------------------------------------------
# each returns (seconds, ok); what it compares lies outside the seconds

def op_put(state: State, client: S3Client, caller: int, rng):
    key = state.new_key(caller)
    body = state.body_of(key)
    t0 = time.perf_counter()
    sha = hashlib.sha256(body)
    status, headers, _ = client.ask("PUT", state.path(key), body=body,
                                    payload_hash=sha.hexdigest())
    took = time.perf_counter() - t0
    if status != 200:
        return took, False
    stored = reference_s3.describe(body, sha.digest())
    with state.lock:
        want = state.reference.put(key, stored)
        state.add(key)      # acknowledged: may be read from now on
    if headers.get("ETag") != want.etag:
        with state.lock:
            state.wrong_heads += 1
        return took, False
    return took, True


def _draw_for_read(state: State, rng) -> str:
    with state.lock:
        key = state.live[rng.randrange(len(state.live))]
        state.readers[key] = state.readers.get(key, 0) + 1
    return key


def _read_done(state: State, key: str):
    with state.lock:
        n = state.readers[key] - 1
        if n:
            state.readers[key] = n
        else:
            del state.readers[key]


def _same_bytes(body, want: memoryview) -> bool:
    """One memcmp.  A bytearray on the left of == compares buffers; a
    memoryview on either side walks the items (30 ms for 10 MiB, under
    the GIL)."""
    if isinstance(body, memoryview) and len(body) == len(body.obj):
        body = body.obj
    if not isinstance(body, bytearray):
        body = bytearray(body)
    return body == want


def op_get(state: State, client: S3Client, caller: int, rng):
    key = _draw_for_read(state, rng)
    try:
        t0 = time.perf_counter()
        status, headers, body = client.ask("GET", state.path(key))
        took = time.perf_counter() - t0
    finally:
        _read_done(state, key)
    if status != 200:
        return took, False
    body = state.run.control("get_body", body)
    want = state.reference.get(key)
    if len(body) != want.size or not _same_bytes(body, state.body_of(key)) \
            or headers.get("ETag") != want.etag:
        with state.lock:
            state.wrong_bodies += 1
        return took, False
    return took, True


def _head_equal(status, headers, want) -> bool:
    if status != want.status:
        return False
    return status != 200 or (
        headers.get("Content-Length") == str(want.size)
        and headers.get("ETag") == want.etag)


def op_stat(state: State, client: S3Client, caller: int, rng):
    key = _draw_for_read(state, rng)
    try:
        t0 = time.perf_counter()
        status, headers, _ = client.ask("HEAD", state.path(key))
        took = time.perf_counter() - t0
    finally:
        _read_done(state, key)
    if status != 200:
        return took, False
    if not _head_equal(status, headers, state.reference.head(key)):
        with state.lock:
            state.wrong_heads += 1
        return took, False
    return took, True


def op_delete(state: State, client: S3Client, caller: int, rng):
    with state.lock:
        # out of the pool before the request goes out; never a key that
        # a GET or HEAD is reading, whose answer would be undecided
        for _ in range(64):
            key = state.live[rng.randrange(len(state.live))]
            if key not in state.readers:
                break
        else:
            raise BenchFailure("64 draws found no key without a reader")
        state.take_out(key)
        state.reference.delete(key)
    t0 = time.perf_counter()
    status, _, _ = client.ask("DELETE", state.path(key))
    took = time.perf_counter() - t0
    return took, status == 204


OPS = {"get": op_get, "stat": op_stat, "put": op_put, "delete": op_delete}


def _callers(state: State, n: int, draw, until, record: bool) -> float:
    """Run n closed-loop callers until `until()`; `draw(caller, rng)`
    names each one's next operation.  Returns the time of the last
    completion."""
    last = [0.0] * n
    errors = []
    failed_in_setup = []
    timeout = state.traffic["request_timeout_s"]

    def caller(c: int):
        rng = random.Random(state.run.seed * 7919 + c + (10007 if record
                                                         else 0))
        client = S3Client(state, timeout)
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
                    state.run.span("s3_" + kind, t0, t0 + took)
                    samples[kind].append(took if ok else float("nan"))
                    done.append((t0 + took, kind))
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
        # a deployment that cannot serve its own set-up cannot run the
        # cell: no window, no result
        raise BenchFailure(
            f"{len(failed_in_setup)} operations of the set-up failed or "
            f"answered wrong: " + ", ".join(
                f"{failed_in_setup.count(k)} {k}" for k in KINDS
                if k in failed_in_setup))
    return max(last)


# -- set-up ----------------------------------------------------------------------------

def _start_gateway(run, state: State):
    """`weed.py s3` as the configuration's `gateways` entry says, a child
    of the harness like the master and the volume server."""
    spec = run.config["gateways"][0]
    state.identity = spec["identity"]
    port = free_port()
    state.s3 = f"127.0.0.1:{port}"
    identities = os.path.join(run.workdir, "s3_identities.json")
    with open(identities, "w") as f:
        json.dump({"identities": [state.identity]}, f)
    fill = {"master": run.cluster.master, "s3_port": port,
            "s3_db": os.path.join(run.workdir, "filer.db"),
            "s3_identities": identities}
    argv = [sys.executable, os.path.join(ROOT, "weed.py"),
            *[a.format(**fill) for a in spec["args"]]]
    # the master first: the gateway's filer registers with it at start
    wait_until("master", lambda: call(run.cluster.master,
                                      "/cluster/status"), 120,
               run.cluster.daemons)
    run.cluster.daemons.start(spec["name"], argv, run.cluster.env)
    probe = S3Client(state, 10.0)

    def listed():
        try:
            return probe.ask("GET", "/")[0] == 200
        finally:
            probe.close()

    wait_until("the s3 gateway", listed, 120, run.cluster.daemons)
    run.log(f"s3 gateway on {state.s3}")


def _make_touch_volume(run, collection: str, objects) -> dict:
    pristine = os.path.join(run.workdir, "pristine")
    os.makedirs(pristine, exist_ok=True)
    vol = volumes.make_volume(pristine, collection, 900, objects, run.seed,
                              False)
    vol["collection"] = collection
    vol["live"] = os.path.join(run.cluster.vol_dir, f"{collection}_900")
    return vol


def _touch_device(run, state: State):
    """`put-get-open`'s one piece of device work: `ec.encode` of a small
    volume of a collection of its own."""
    vol = state.touch
    vs = run.cluster.volume
    if os.path.exists(vol["live"] + ".ecx"):
        call(vs, "/admin/ec/delete_shards",
             {"volume": vol["vid"], "collection": vol["collection"],
              "shard_ids": ALL_SHARDS})
    volumes.link_volume(vol["base"], vol["live"])
    call(vs, "/admin/volume/mount",
         {"volume": vol["vid"], "collection": vol["collection"]})
    t0 = time.perf_counter()
    for name, path, payload in volumes.seal_steps(vol["vid"],
                                                  vol["collection"]):
        reply = call(vs, path, payload, timeout=900)
        if name == "seal.generate":
            generated = reply
    run.span("device_touch.seal", t0, time.perf_counter())
    if run.window_open:
        run.records.setdefault("device_touch", []).append(generated)


def _deleted_bytes(run) -> int:
    """What the volume server counts as deleted and not yet collected."""
    status = call(run.cluster.volume, "/admin/status")
    return sum(v.get("deleted_byte_count", 0)
               for v in status.get("volumes", []))


def _after(n: int):
    """An `until` for `_callers` that lets n operations begin."""
    left = iter(range(n))
    lock = threading.Lock()

    def until() -> bool:
        with lock:
            return next(left, None) is None

    return until


def prepare(run) -> State:
    state = State(run)
    t = state.traffic
    starter_error = []

    def start_gateway():
        try:
            _start_gateway(run, state)
        except BaseException as e:  # re-raised below
            starter_error.append(e)

    starter = threading.Thread(target=start_gateway, name="s3-start",
                               daemon=True)
    starter.start()     # beside the volume server's device init
    state.pool = np.random.default_rng(run.seed).bytes(
        t["object_bytes"] + t["pool_extra_bytes"])
    touch = t["device_touch"]
    state.touch = _make_touch_volume(run, touch["collection"],
                                     touch["volume"]["objects"])
    try:
        run.wait_cluster()
    finally:
        starter.join()
    if starter_error:
        raise starter_error[0]
    _touch_device(run, state)    # compiles or loads the encode step
    admin = S3Client(state, 30.0)
    status, _, body = admin.ask("PUT", state.path())
    if status != 200:
        raise BenchFailure(f"PUT {state.path()} -> {status}: "
                           f"{bytes(body)[:300]!r}")
    admin.close()
    t0 = time.perf_counter()
    _callers(state, t["clients"], lambda c, rng: "put",
             _after(t["objects"]), False)
    took = time.perf_counter() - t0
    nbytes = len(state.live) * t["object_bytes"]
    run.log(f"{len(state.live)} objects of {t['object_bytes']} bytes PUT by "
            f"{t['clients']} callers in {took:.3f} s "
            f"({nbytes / took / 2**20:.1f} MiB/s) before the window")
    # the read path, warmed by every caller and held to the reference
    # before a window is opened on it
    _callers(state, t["clients"],
             lambda c, rng: ("get", "stat")[rng.randrange(2)],
             _after(t["warm_reads"]), False)
    run.log(f"{t['warm_reads']} GETs and HEADs of the set-up answered as "
            f"the reference does")
    return state


# -- the window ------------------------------------------------------------------------

def _scrape_gateway(run, state: State):
    """The gateway's `/metrics`, tolerantly: a gateway that cannot be
    scraped leaves its metrics out and fails nothing."""
    try:
        samples = scrape(state.s3)
    except (BenchFailure, OSError, http.client.HTTPException) as e:
        run.log(f"the gateway's /metrics could not be scraped: {e}")
        samples = []
    run.records.setdefault("s3_prom", []).append({"samples": samples})


def window(run, state: State, seconds: float) -> dict:
    t = state.traffic
    state.samples = {}
    state.done = []
    weights = [t["mix"][k] for k in KINDS]
    state.deleted_before = _deleted_bytes(run)
    _scrape_gateway(run, state)
    t_open = time.perf_counter()
    t_end = t_open + seconds
    touch_error = []

    def touch():
        try:
            _touch_device(run, state)
        except Exception as e:   # raised below, on the window's thread
            touch_error.append(e)

    def draw(c: int, rng) -> str:
        kind = rng.choices(KINDS, weights)[0]
        # a pool about to run dry takes a PUT for a DELETE: never at the
        # cell's 250 objects, where PUTs outnumber DELETEs three to two,
        # but a rehearsal's 12 can walk to none
        if kind == "delete" and len(state.live) <= t["clients"]:
            kind = "put"
        return kind

    timer = threading.Timer(t["device_touch"]["at_s"], touch)
    timer.start()
    last = _callers(state, t["clients"], draw,
                    lambda: time.perf_counter() >= t_end, True)
    timer.join()
    if touch_error:
        raise touch_error[0]
    _scrape_gateway(run, state)
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
            f"{p50:.3f} ms, p95 {p95:.3f} ms, max {good.max():.3f} ms; "
            f"{len(state.live)} objects live at the end")
    run.log("  deciles p10..p90 ms: " + " ".join(
        f"{v:.2f}" for v in np.percentile(good, range(10, 100, 10))))
    moved = 0
    for kind in KINDS:
        v = np.array(state.samples.get(kind, []))
        ok = v[~np.isnan(v)] * 1e3
        if ok.size:
            q = np.percentile(ok, [50, 95, 99])
            run.log(f"  {kind}: {ok.size} ok of {v.size} "
                    f"({100 * v.size / attempted:.1f}% of the mix), p50 "
                    f"{q[0]:.3f} p95 {q[1]:.3f} p99 {q[2]:.3f} ms")
        if kind in ("get", "put"):
            moved += int(ok.size) * t["object_bytes"]
    run.log(f"  bodies moved: {moved / elapsed / 2**20:.1f} MiB/s")
    # the window by slices: what moves `goodput` from run to run is the
    # entry store's commits (PUTs and DELETEs), and when in the window
    edges = np.append(np.arange(0.0, seconds, SLICE_S), elapsed)
    at = np.array([d[0] - t_open for d in state.done])
    commits = np.array([d[1] in ("put", "delete") for d in state.done])
    run.log(f"  operations a second by {SLICE_S} s slices: " + " ".join(
        f"{r:.0f}" for r in np.histogram(at, edges)[0] / np.diff(edges)))
    run.log(f"  PUTs and DELETEs a second by {SLICE_S} s slices: " + " ".join(
        f"{r:.1f}" for r in np.histogram(at[commits], edges)[0]
        / np.diff(edges)))
    # a stall: no caller of the twenty is answered for a tenth of a second
    # (the mean gap between two answers is 6 ms)
    at.sort()
    gaps = np.diff(at, prepend=0.0)
    stalls = np.flatnonzero(gaps > STALL_S)
    run.log(f"  stalls (no answer for over {STALL_S} s): {stalls.size}, "
            f"{gaps[stalls].sum():.3f} s in all, ending at " + " ".join(
                f"{at[i]:.1f}({gaps[i]:.2f})" for i in stalls[:24]))
    # operations acknowledged and right, a second: a per-layer number in
    # this cell, not an end-to-end one (PERF.md, section 2: it follows the
    # machine's file system more closely than its bound allows)
    run.counts["s3_ops_per_s"] = good.size / elapsed
    run.log(f"  {good.size / elapsed:.3f} operations a second")
    return {"attempted": attempted, "failed": failed, "elapsed_s": elapsed,
            "end_to_end": {"op_p50_ms": float(p50), "op_p95_ms": float(p95)}}


# -- after the window ----------------------------------------------------------------

def _listing(state: State, ask) -> list[tuple[str, int, str]]:
    rows, token = [], None
    while token != "":
        query = {"list-type": "2", "max-keys": "1000"}
        if token:
            query["continuation-token"] = token
        status, _, body = ask("GET", state.path(), query)
        if status != 200:
            raise BenchFailure(f"ListObjectsV2 -> {status}")
        page, token = reference_s3.parse_listing(bytes(body))
        rows += page
    return rows


class _Asker:
    """`S3Client.ask` for the checks after the window: a reply that the
    transport lost is an answer that equals nothing."""

    def __init__(self, state: State):
        self.client = S3Client(state, 60.0)
        self.lost = 0

    def __call__(self, *args, **kwargs):
        try:
            return self.client.ask(*args, **kwargs)
        except (http.client.HTTPException, OSError):
            self.lost += 1
            return None, {}, b""


def verify(run, state: State, result: dict) -> list[dict]:
    t = state.traffic
    ref = state.reference
    ask = _Asker(state)
    wrong = state.wrong_bodies + state.wrong_heads
    out = [run.compare("operations_failed", result["failed"] - wrong, 0),
           run.compare("get_bodies_not_equal_to_their_put",
                       state.wrong_bodies, 0),
           run.compare("heads_and_etags_not_equal_to_the_reference",
                       state.wrong_heads, 0)]
    # the listing against the reference's keys
    want = ref.list()
    got = _listing(state, ask)
    missing = len(set(want) - set(got))
    extra = len(set(got) - set(want))
    run.log(f"ListObjectsV2: {len(got)} rows, the reference holds "
            f"{len(want)}; {missing} missing or different, {extra} extra; "
            f"order {'kept' if got == want else 'NOT kept'}")
    out.append(run.compare("listing_keys_missing_or_different", missing, 0))
    out.append(run.compare("listing_keys_extra", extra, 0))
    out.append(run.compare("listing_out_of_order",
                           int([r[0] for r in got]
                               != sorted((r[0] for r in got),
                                         key=str.encode)), 0))
    # HEAD of every live and every deleted key
    bad_live = sum(1 for key in ref.objects if not _head_equal(
        *ask("HEAD", state.path(key))[:2], ref.head(key)))
    bad_dead = sum(1 for key in ref.deleted if not _head_equal(
        *ask("HEAD", state.path(key))[:2], ref.head(key)))
    run.log(f"HEAD of {len(ref.objects)} live keys: {bad_live} wrong; of "
            f"{len(ref.deleted)} deleted keys: {bad_dead} not 404")
    out.append(run.compare("live_keys_with_a_wrong_head", bad_live, 0))
    out.append(run.compare("deleted_keys_still_answered", bad_dead, 0))
    # GET of a seeded sample of live keys, by SHA-256 this time; and of
    # as many deleted ones, which must answer 404
    rng = random.Random(run.seed + 2)
    live = sorted(ref.objects)
    sample = rng.sample(live, min(len(live), t["readback_sample"]))
    bad = 0
    for key in sample:
        status, headers, body = ask("GET", state.path(key))
        a = ref.get(key)
        bad += not (status == 200 and len(body) == a.size
                    and hashlib.sha256(body).digest() == a.sha256
                    and headers.get("ETag") == a.etag)
    dead = sorted(ref.deleted)
    dead = rng.sample(dead, min(len(dead), t["readback_sample"]))
    undead = sum(1 for key in dead
                 if ask("GET", state.path(key))[0] != 404)
    run.log(f"read back {len(sample)} of {len(live)} live objects: {bad} "
            f"wrong; GET of {len(dead)} deleted keys: {undead} not 404")
    out.append(run.compare("live_objects_not_read_back", bad, 0))
    out.append(run.compare("deleted_objects_still_read", undead, 0))
    # the chunks' bytes counted as deleted by the volume server: what a
    # vacuum will collect
    counted = _deleted_bytes(run) - state.deleted_before
    owed = ref.deleted_bytes()
    run.log(f"the volume server counts {counted} more bytes deleted; the "
            f"deleted objects' payload is {owed}")
    out.append(run.compare("deleted_payload_bytes_the_volume_server_does_"
                           "not_count", max(0, owed - counted), 0))
    # authentication: the same request under another secret
    status, _, _ = ask("HEAD", state.path(live[0]),
                       secret_key=WRONG_SECRET)
    anonymous = http.client.HTTPConnection(
        *state.s3.rsplit(":", 1), timeout=30)
    anonymous.request("GET", state.path(live[0]))
    unsigned = anonymous.getresponse()
    unsigned.read()
    anonymous.close()
    run.log(f"a request signed with another secret -> {status}; an "
            f"unsigned one -> {unsigned.status}")
    out.append(run.compare("wrongly_signed_requests_not_refused",
                           int(status != 403) + int(unsigned.status != 403),
                           0))
    ask.client.close()
    if ask.lost:
        run.log(f"{ask.lost} replies of the checks were lost in transport")
    seals = run.records.get("device_touch", [])
    off = [r for r in seals
           if r.get("backend") != run.expect["encode_backend"]
           or (r.get("device") or {}).get("platform")
           != run.expect["platform"]]
    out.append(run.compare("device_touch_seals_missing_or_off_device",
                           int(len(seals) != 1) + len(off), 0))
    return out
