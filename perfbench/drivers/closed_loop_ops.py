"""Driver `closed_loop_ops`: N callers that each wait for a reply.

The traffic file names the `stage` that set-up builds (a sealed volume
with shards deleted, or open volumes with a preload) and the `mix` of
operations every client cycles through.  The per-file assign + write /
random-read loop is the one of upstream `weed benchmark`, copied from
`seaweedfs_tpu/benchmark.py`.  Every GET body is compared with the
reference dict as it arrives; latency is the client's, request sent to
body read, and the comparison is outside it.
"""

from __future__ import annotations

import itertools
import os
import random
import re
import threading
import time

import numpy as np

import reference
import volumes
from cluster import BenchFailure, call, request

ALL_SHARDS = list(range(reference.TOTAL_SHARDS))


class State:
    def __init__(self, run):
        self.run = run
        self.traffic = run.traffic
        self.collection = run.traffic["collection"]
        self.reference: dict[str, tuple[int, bytes]] = {}  # fid -> size, digest
        self.urls: dict[str, str] = {}
        self.read_order: list[str] = []     # get_sealed: the permutation
        self.cursor = itertools.count()
        self.acked: list[str] = []          # get_acked: fids to draw from
        self.put_seq = itertools.count()
        self.pool = b""
        self.samples: dict[str, list[float]] = {}
        self.lock = threading.Lock()
        self.wrong = 0
        self.touch = None


# -- operations ------------------------------------------------------------------

def _get(state: State, fid: str) -> tuple[float, bool]:
    url = state.urls.get(fid) or state.run.cluster.volume
    t0 = time.perf_counter()
    status, body = request(url, "GET", "/" + fid)
    took = time.perf_counter() - t0
    body = state.run.control("get_body", body)
    size, want = state.reference[fid]
    ok = status == 200
    if ok and (len(body) != size or reference.digest(body) != want):
        with state.lock:
            state.wrong += 1
        ok = False
    return took, ok


def op_get_sealed(state: State, rng) -> tuple[float, bool]:
    order = state.read_order
    return _get(state, order[next(state.cursor) % len(order)])


def op_get_acked(state: State, rng) -> tuple[float, bool]:
    return _get(state, state.acked[rng.randrange(len(state.acked))])


def op_put(state: State, rng) -> tuple[float, bool]:
    k = next(state.put_seq)
    nbytes = state.traffic["put_bytes"]
    start = (k * 37) % (len(state.pool) - nbytes)
    data = k.to_bytes(8, "big") + state.pool[start:start + nbytes - 8]
    master = state.run.cluster.master
    t0 = time.perf_counter()
    a = call(master, f"/dir/assign?collection={state.collection}")
    t1 = time.perf_counter()
    status, _ = request(a["url"], "POST", "/" + a["fid"], data)
    t2 = time.perf_counter()
    state.run.span("assign", t0, t1)
    if status not in (200, 201):
        return t2 - t0, False
    state.reference[a["fid"]] = (nbytes, reference.digest(data))
    state.urls[a["fid"]] = a["url"]
    state.acked.append(a["fid"])    # acknowledged: may be read from now on
    return t2 - t0, True


OPS = {"get_sealed": op_get_sealed, "get_acked": op_get_acked, "put": op_put}


def _clients(state: State, n: int, mix: list[str], until, record: bool,
             seed: int) -> float:
    """Run n closed-loop clients until `until()` is true; returns the
    time of the last completion."""
    last = [0.0] * n
    errors = []

    def client(c: int):
        rng = random.Random(seed * 7919 + c)
        samples = {kind: [] for kind in mix}
        try:
            for i in itertools.count(c):  # stagger the mix across clients
                if until():
                    break
                kind = mix[i % len(mix)]
                t0 = time.perf_counter()
                took, ok = OPS[kind](state, rng)
                if record:
                    state.run.span(kind, t0, t0 + took)
                    samples[kind].append(took if ok else float("nan"))
                elif not ok:
                    raise BenchFailure(f"a warm-up {kind} failed")
                last[c] = time.perf_counter()
        except Exception as e:   # a client thread must report, not vanish
            errors.append(e)
        with state.lock:
            for kind, vals in samples.items():
                state.samples.setdefault(kind, []).extend(vals)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return max(last)


# -- stages ------------------------------------------------------------------------

def _seal(run, collection: str, vid: int) -> dict:
    for name, path, payload in volumes.seal_steps(vid, collection):
        reply = call(run.cluster.volume, path, payload, timeout=900)
        if name == "seal.generate":
            generated = reply
    return generated


def _make_volume(run, collection: str, vid: int, objects, with_digests):
    pristine = os.path.join(run.workdir, "pristine")
    os.makedirs(pristine, exist_ok=True)
    vol = volumes.make_volume(pristine, collection, vid, objects, run.seed,
                              with_digests)
    vol["collection"] = collection
    vol["live"] = os.path.join(run.cluster.vol_dir, f"{collection}_{vid}")
    return vol


def stage_sealed_degraded(run, state: State):
    """One sealed volume with `lost_shards` deleted; the read set is the
    objects whose extent, by the .ecx, holds a block of a lost data shard."""
    t = state.traffic
    vol = _make_volume(run, state.collection, 1, t["volume"]["objects"],
                       True)
    run.wait_cluster()
    volumes.link_volume(vol["base"], vol["live"])
    call(run.cluster.volume, "/admin/volume/mount",
         {"volume": 1, "collection": state.collection})
    reply = _seal(run, state.collection, 1)
    lost = t["lost_shards"]
    call(run.cluster.volume, "/admin/ec/delete_shards",
         {"volume": 1, "collection": state.collection, "shard_ids": lost})
    gone = [s for s in lost if os.path.exists(
        vol["live"] + reference.shard_ext(s))]
    if gone:
        raise BenchFailure(f"shard files {gone} survived delete_shards")
    lost_data = {s for s in lost if s < reference.DATA_SHARDS}
    extents = reference.read_ecx(vol["live"] + ".ecx")
    degraded = {}    # fid -> the lost data shards its extent holds a block of
    for f, (nid, size, _) in vol["written"].items():
        offset, stored = extents[nid]
        degraded[f] = lost_data & reference.shards_of_extent(
            offset, reference.needle_disk_size(stored), vol["dat_bytes"])
    is_large = lambda f: vol["written"][f][1] >= t["large_from_bytes"]  # noqa: E731
    large = sorted(f for f, d in degraded.items() if d and is_large(f))
    small = sorted(f for f, d in degraded.items() if d and not is_large(f))
    # The read set and its cyclic order are fixed with the layout, and the
    # seed picks where in the cycle the window starts.  Which reads find a
    # neighbour's block still in the 64 MiB LRU depends on the order, and a
    # free permutation per seed moved op_p50_ms by 20% from seed to seed,
    # reproducibly (PERF.md, PR 24): the seed was changing the work.
    layout = random.Random(volumes.LAYOUT_SEED)
    layout.shuffle(small)
    keep = t["read_set"]["small"]
    if len(small) < keep:
        run.log(f"only {len(small)} small objects touch a lost shard "
                f"(the traffic asks for {keep})")
    cycle = large + small[:keep]
    layout.shuffle(cycle)
    start = random.Random(run.seed).randrange(len(cycle))
    state.read_order = cycle[start:] + cycle[:start]
    state.reference = {f: (size, dig)
                       for f, (_, size, dig) in vol["written"].items()}
    run.counts["read_set_not_on_lost_shard"] = sum(
        1 for f in state.read_order if not degraded[f])
    run.log(f"volume 1 sealed as {reply.get('backend')}, shards {lost} "
            f"deleted; read set {len(large)} large + "
            f"{len(state.read_order) - len(large)} small objects, every "
            f"extent holds a lost block")
    n = t["clients"]
    _warm_stacks(run, state, sorted(set(small) - set(cycle)) + small)
    # then the tail of the cycle under the window's own load, so that
    # the LRU holds what it would hold had the cycle been running
    warm = state.read_order[-max(n, len(state.read_order)
                                 * t["warm_share_pct"] // 100):]
    state.read_order, order = warm, state.read_order
    done = itertools.count()
    _clients(state, n, ["get_sealed"],
             lambda: next(done) >= len(warm), False, run.seed)
    state.read_order = order
    state.cursor = itertools.count()   # the window starts the permutation


def _warm_stacks(run, state: State, singles: list[str]):
    """Concurrent recovers of one lost shard are stacked into one decode,
    and every stack length (1..clients blocks) is a program of its own.
    Which lengths concurrent reads produce is a race: bursts of 2..9
    reads built 5 or 6 of the 8 programs and a window then built another
    (my chip runs, PR 24).  So each length is built by one read instead:
    with the program's live setting for the recovery block at n times its
    default, one object's recover decodes the shape of a stack of n.  The
    setting is put back before the window, and what JAX built is read
    back and logged."""
    want = state.traffic["clients"]
    warm = state.traffic["warm_stacks"]
    ask = run.cluster.control.ask
    try:
        for n in range(1, want + 1):
            ask(cmd="setenv", name=warm["setting"],
                value=n * warm["default"])
            if not _get(state, singles[n - 1])[1]:
                raise BenchFailure(f"the warm-up read at {n} blocks failed")
    finally:
        ask(cmd="setenv", name=warm["setting"], value=None)
    name = re.compile(warm["program"])
    have = sum(1 for _, fun, _ in ask(cmd="programs")["built"]
               if name.search(fun))
    run.log(f"stack warm-up: {have} programs matching {warm['program']!r} "
            f"built for stacks of 1..{want} blocks")


def stage_open_volumes(run, state: State):
    """Open volumes grown on the master, and a preload of acknowledged
    PUTs so that the first GETs have something to draw."""
    t = state.traffic
    state.pool = np.random.default_rng(run.seed).bytes(1 << 20)
    touch = t.get("device_touch")
    if touch:
        # its own collection: the master must not assign PUTs to it
        state.touch = _make_volume(run, state.collection + "touch", 900,
                                   touch["volume"]["objects"], False)
    run.wait_cluster()
    grown = call(run.cluster.master,
                 f"/vol/grow?collection={state.collection}"
                 f"&count={t['volumes']}", method="POST")
    if grown.get("count") != t["volumes"]:
        raise BenchFailure(f"/vol/grow -> {grown}")
    if touch:
        _touch_device(run, state)    # compiles or loads the encode step
    done = itertools.count()
    _clients(state, t["clients"], ["put"],
             lambda: next(done) >= t["preload_puts"], False, run.seed)
    run.log(f"{len(state.acked)} PUTs acknowledged before the window")


def _touch_device(run, state: State):
    """The traffic's one piece of device work: `ec.encode` of a small
    volume of a collection of its own, `device_touch.at_s` into every
    window (the benchmark's contract refuses a cell whose traced window
    holds no device operation)."""
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
    reply = _seal(run, vol["collection"], vol["vid"])
    run.span("device_touch.seal", t0, time.perf_counter())
    if run.window_open:
        run.records.setdefault("device_touch", []).append(reply)


STAGES = {"sealed_degraded": stage_sealed_degraded,
          "open_volumes": stage_open_volumes}


# -- the driver's three calls --------------------------------------------------------

def prepare(run) -> State:
    state = State(run)
    STAGES[run.traffic["stage"]](run, state)
    return state


def window(run, state: State, seconds: float) -> dict:
    t = state.traffic
    state.samples = {}
    t_open = time.perf_counter()
    t_end = t_open + seconds
    touch_error = []

    def touch():
        try:
            _touch_device(run, state)
        except Exception as e:   # raised below, on the window's thread
            touch_error.append(e)

    timer = None
    if state.touch:
        timer = threading.Timer(t["device_touch"]["at_s"], touch)
        timer.start()
    last = _clients(state, t["clients"], t["mix"],
                    lambda: time.perf_counter() >= t_end, True,
                    run.seed + 1)
    if timer:
        timer.join()
        if touch_error:
            raise touch_error[0]
    elapsed = last - t_open
    lat = np.array([v for vals in state.samples.values() for v in vals])
    good = lat[~np.isnan(lat)] * 1e3
    attempted = int(lat.size)
    failed = attempted - int(good.size)
    if not good.size:
        raise BenchFailure("no operation completed in the window")
    p50, p95 = np.percentile(good, [50, 95])
    beyond = int((good > p95).sum())
    run.log(f"window: {t['clients']} closed-loop clients for {elapsed:.3f} s:"
            f" {attempted} operations, {failed} failed or wrong; p50 "
            f"{p50:.3f} ms, p95 {p95:.3f} ms ({beyond} samples beyond it), "
            f"max {good.max():.3f} ms")
    run.log("  deciles p10..p90 ms: " + " ".join(
        f"{v:.2f}" for v in np.percentile(good, range(10, 100, 10))))
    for kind, vals in sorted(state.samples.items()):
        v = np.array(vals)
        v = v[~np.isnan(v)] * 1e3
        if v.size:
            q = np.percentile(v, [50, 95, 99])
            run.log(f"  {kind}: {v.size} ok, p50 {q[0]:.3f} p95 {q[1]:.3f} "
                    f"p99 {q[2]:.3f} ms")
    run.counts["reads_wrong"] = state.wrong
    end = {"op_p50_ms": float(p50), "op_p95_ms": float(p95)}
    if "goodput" in t["reports"]:
        end["goodput"] = good.size / elapsed
    return {"attempted": attempted, "failed": failed, "elapsed_s": elapsed,
            "end_to_end": end}


def verify(run, state: State, result: dict) -> list[dict]:
    t = state.traffic
    out = [run.compare("operations_failed", result["failed"] - state.wrong,
                       0),
           run.compare("reads_not_equal_to_their_put", state.wrong, 0)]
    if t["stage"] == "sealed_degraded":
        d = run.admin_delta("/admin/ec/recover_stats")
        run.log(f"recover in the window: {d}")
        out.append(run.compare("device_fallbacks",
                               d["device_fallbacks"], 0))
        out.append(run.compare("reads_off_a_lost_shard",
                               run.counts["read_set_not_on_lost_shard"], 0))
        if run.expect.get("recover_on_device", True):
            out.append(run.compare(
                "windows_without_device_decodes",
                int(d["device_decodes"] <= 0), 0))
        out.append(run.compare("windows_without_recovered_blocks",
                               int(d["cache_misses"] <= 0), 0))
    else:
        # every write acknowledged in the window is read back: a seeded
        # sample of them, beyond the random GETs the window verified
        fids = state.acked[t["preload_puts"]:]
        rng = random.Random(run.seed + 2)
        sample = rng.sample(fids, min(len(fids), t["readback_sample"]))
        bad = sum(1 for f in sample if not _get(state, f)[1])
        run.log(f"read back {len(sample)} of {len(fids)} writes acknowledged"
                f" in the window: {bad} wrong")
        out.append(run.compare("acknowledged_writes_not_read_back", bad, 0))
    if state.touch:
        seals = run.records.get("device_touch", [])
        off = [r for r in seals
               if r.get("backend") != run.expect["encode_backend"]
               or (r.get("device") or {}).get("platform")
               != run.expect["platform"]]
        out.append(run.compare("device_touch_seals_missing_or_off_device",
                               int(len(seals) != 1) + len(off), 0))
    return out
