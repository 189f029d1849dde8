"""Driver `zipf_sealed_reads`: N callers that each wait for a reply read
whole objects of one sealed volume, drawn by a zipfian rank over ALL of
its objects, while the configuration's `lost_shards` are gone.

Where `closed_loop_ops` cycles a fixed list in which every read holds a
lost block, this is the read path as its clients use it on an ordinary bad
day: most reads are plain (`.ecx` search, a `pread` an interval, join),
most of the others find their lost blocks in the recovered-block LRU, and
a trickle recovers on the device.  Which object a rank is, is fixed with
the layout (one shuffle, the same for every seed: a free order per seed
changed the work, PERF.md finding 3); the seed draws the callers' streams
and the objects' bytes.  Every GET body is compared with its PUT as it
arrives, outside the timed span (`closed_loop_ops._get`), and the cache
lookups the window's reads caused are held against what the plain
reference's extent maths says they need (`reference_reads.py`).
"""

from __future__ import annotations

import bisect
import itertools
import json
import os
import random
import threading
import time
from collections import Counter

import numpy as np

import reference
import reference_reads
import volumes
from cluster import BenchFailure, call, request
from drivers.closed_loop_ops import (State, _get, _make_volume, _seal,
                                     _warm_stacks)

VID = 1
RECOVER_STATS = "/admin/ec/recover_stats"
READ_STATS = "/admin/ec/read_stats"
LOOKUPS = ("cache_hits", "cache_misses", "coalesced")


def rank_order(n: int, seed_offset: int) -> list[int]:
    """perm[r] + 1 is the needle id of rank r: one shuffle of the layout's
    seed, the same for every run seed."""
    perm = list(range(n))
    random.Random(volumes.LAYOUT_SEED + seed_offset).shuffle(perm)
    return perm


class Zipf:
    """Rank r (0-based) with probability proportional to
    1 / (r + 1) ** constant, over n ranks."""

    def __init__(self, n: int, constant: float):
        self.weights = [1.0 / (r + 1) ** constant for r in range(n)]
        self.cumulative = list(itertools.accumulate(self.weights))
        self.total = self.cumulative[-1]

    def draw(self, rng: random.Random) -> int:
        r = bisect.bisect_left(self.cumulative, rng.random() * self.total)
        return min(r, len(self.weights) - 1)

    def mass(self, ranks) -> float:
        return sum(self.weights[r] for r in ranks) / self.total


class SealedReads(State):
    """`closed_loop_ops.State` (the reference dict, the count of wrong
    bodies) and what this driver adds to it."""

    def __init__(self, run):
        super().__init__(run)
        self.sealed_as: dict = {}          # the set-up seal's reply
        self.ranked: list[str] = []        # fid of rank r
        self.zipf: Zipf | None = None
        self.lookups: dict[str, int] = {}  # fid -> lost blocks a read needs
        self.large: set[str] = set()
        self.reads: Counter = Counter()    # the window's reads, by fid

    def draw(self, rng: random.Random) -> str:
        """The next object of a caller's zipf stream."""
        return self.ranked[self.zipf.draw(rng)]


def _stats(run, path: str) -> dict | None:
    """An admin route's JSON, or None where the program has no such
    route (the parent's: the request then falls to the object handler)."""
    status, body = request(run.cluster.volume, "GET", path)
    return json.loads(body) if status == 200 else None


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


def _hit_share(before: dict, after: dict) -> float | None:
    d = _delta(before, after)
    lookups = sum(d[k] for k in LOOKUPS)
    return 100.0 * d["cache_hits"] / lookups if lookups else None


def _callers(state: SealedReads, n: int, draw, record: bool, seed: int):
    """Run n closed-loop callers; `draw(rng)` gives the next fid, or None
    when the caller is done.  Returns the latencies (NaN: failed or
    wrong), the reads completed by fid, and the time of the last
    completion."""
    taken: list[list[float]] = [[] for _ in range(n)]
    reads = [Counter() for _ in range(n)]
    last = [0.0] * n
    errors = []

    def caller(c: int):
        rng = random.Random(seed * 7919 + c)
        try:
            while (fid := draw(rng)) is not None:
                t0 = time.perf_counter()
                took, ok = _get(state, fid)
                if record:
                    state.run.span("get_sealed", t0, t0 + took)
                    taken[c].append(took if ok else float("nan"))
                elif not ok:
                    raise BenchFailure(f"a warm-up read of {fid} failed")
                reads[c][fid] += 1
                last[c] = time.perf_counter()
        except Exception as e:   # a caller thread must report, not vanish
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return ([v for vals in taken for v in vals], sum(reads, Counter()),
            max(last))


def _in_turn(fids: list[str]):
    """A draw that hands `fids` out once, in order, over all callers."""
    cursor = itertools.count()

    def draw(rng):
        k = next(cursor)
        return fids[k] if k < len(fids) else None
    return draw


def prepare(run) -> SealedReads:
    t = run.traffic
    state = SealedReads(run)
    vol = _make_volume(run, state.collection, VID, t["volume"]["objects"],
                       True)
    run.wait_cluster()
    volumes.link_volume(vol["base"], vol["live"])
    call(run.cluster.volume, "/admin/volume/mount",
         {"volume": VID, "collection": state.collection})
    state.sealed_as = _seal(run, state.collection, VID)
    lost = list(run.config["lost_shards"])
    call(run.cluster.volume, "/admin/ec/delete_shards",
         {"volume": VID, "collection": state.collection, "shard_ids": lost})
    left = [s for s in lost
            if os.path.exists(vol["live"] + reference.shard_ext(s))]
    if left:
        raise BenchFailure(f"shard files {left} survived delete_shards")

    # what each object's read needs, by the plain reference
    extents = reference.read_ecx(vol["live"] + ".ecx")
    by_nid, plans = {}, {}
    for f, (nid, size, dig) in vol["written"].items():
        offset, stored = extents[nid]
        by_nid[nid] = f
        plans[f] = reference_reads.read_plan(
            offset, reference.needle_disk_size(stored), vol["dat_bytes"],
            lost, block=t["recover_block_bytes"])
        state.reference[f] = (size, dig)
    z = t["zipf"]
    perm = rank_order(len(by_nid), z["rank_seed_offset"])
    state.ranked = [by_nid[k + 1] for k in perm]
    state.zipf = Zipf(len(perm), z["constant"])
    state.lookups = {f: len(p["blocks"]) for f, p in plans.items()}
    state.large = {f for f, (_, size, _) in vol["written"].items()
                   if size >= t["large_from_bytes"]}
    degraded = [f for f in state.ranked if state.lookups[f]]
    blocks = {b for p in plans.values() for b in p["blocks"]}
    mass = lambda keep: 100 * state.zipf.mass(  # noqa: E731
        r for r, f in enumerate(state.ranked) if keep(f))
    run.log(f"volume {VID} sealed as {state.sealed_as.get('backend')}, "
            f"shards {lost} deleted; {len(perm)} objects by zipf "
            f"{z['constant']}: {len(degraded)} hold a lost block "
            f"({sum(1 for f in degraded if f in state.large)} large), "
            f"{len(blocks)} lost blocks of {sum(b[2] for b in blocks)} "
            f"bytes; rank mass on large objects "
            f"{mass(lambda f: f in state.large):.3f}%, on objects that "
            f"hold a lost block {mass(lambda f: state.lookups[f]):.3f}%")

    # one program a stack length, each built by one read whose enlarged
    # block lies whole inside the shard file (a clipped block is another
    # shape); then the LRU as the traffic would have left it
    n = t["clients"]
    shard_size = reference_reads.shard_file_size(vol["dat_bytes"])
    unit = t["recover_block_bytes"]
    whole = [f for f in reversed(degraded) if f not in state.large and all(
        at // (k * unit) * (k * unit) + k * unit <= shard_size
        for _, at, _ in plans[f]["recovered"] for k in range(1, n + 1))]
    if len(whole) < n:
        raise BenchFailure(f"only {len(whole)} small objects can build a "
                           f"stack's program (the traffic asks for {n})")
    _warm_stacks(run, state, whole)
    _callers(state, n, _in_turn(degraded[::-1]), False, run.seed)
    left = itertools.count()
    _callers(state, n, lambda rng: state.draw(rng)
             if next(left) < t["warm_reads"] else None, False,
             volumes.LAYOUT_SEED)
    run.log(f"LRU warmed: one pass over the {len(degraded)} objects that "
            f"hold a lost block, coldest rank first, then "
            f"{t['warm_reads']} reads of the zipf stream")
    return state


def window(run, state: SealedReads, seconds: float) -> dict:
    t = run.traffic
    t_open = time.perf_counter()
    t_end = t_open + seconds
    recover = {0.0: _stats(run, RECOVER_STATS)}
    served_before = _stats(run, READ_STATS)
    # the hit share of the window's first and last ten seconds: a cache
    # still filling, or draining, shows as a difference between them
    marks = [m for m in (10.0, seconds - 10.0) if 0 < m < seconds] \
        if seconds >= 30 else []
    timers = [threading.Timer(m, lambda m=m: recover.__setitem__(
        m, _stats(run, RECOVER_STATS))) for m in marks]
    for timer in timers:
        timer.start()
    lat, state.reads, last = _callers(
        state, t["clients"],
        lambda rng: state.draw(rng) if time.perf_counter() < t_end else None,
        True, run.seed + 1)
    for timer in timers:
        timer.join()
    recover[seconds] = _stats(run, RECOVER_STATS)
    served_after = _stats(run, READ_STATS)
    elapsed = last - t_open

    lat = np.array(lat)
    good = lat[~np.isnan(lat)] * 1e3
    attempted = int(lat.size)
    failed = attempted - int(good.size)
    if not good.size:
        raise BenchFailure("no operation completed in the window")
    p50, p95 = np.percentile(good, [50, 95])
    run.log(f"window: {t['clients']} closed-loop callers for {elapsed:.3f} "
            f"s: {attempted} reads ({attempted / elapsed:.1f} a second), "
            f"{failed} failed or wrong; p50 {p50:.3f} ms, p95 {p95:.3f} ms "
            f"({int((good > p95).sum())} samples beyond it), max "
            f"{good.max():.3f} ms")
    run.log("  deciles p10..p90 ms: " + " ".join(
        f"{v:.2f}" for v in np.percentile(good, range(10, 100, 10))))
    n_large = sum(k for f, k in state.reads.items() if f in state.large)
    n_degraded = sum(k for f, k in state.reads.items() if state.lookups[f])
    run.log(f"  {n_large} reads of large objects "
            f"({100 * n_large / attempted:.2f}%), {n_degraded} of objects "
            f"that hold a lost block ({100 * n_degraded / attempted:.2f}%)")
    d = _delta(recover[0.0], recover[seconds])
    lookups = sum(d[k] for k in LOOKUPS)
    run.log(f"  recover: {lookups} block lookups, {d['cache_hits']} hits, "
            f"{d['cache_misses']} recovered "
            f"({d['cache_misses'] / elapsed:.2f} a second, "
            f"{1000 * d['cache_misses'] / attempted:.2f} a 1,000 reads), "
            f"{d['coalesced']} waited for another reader's")
    if marks:
        first = _hit_share(recover[0.0], recover[marks[0]])
        final = _hit_share(recover[marks[1]], recover[seconds])
        run.log(f"  hit share of the first 10 s {first!r} %, of the last "
                f"10 s {final!r} %")
    if served_before and served_after:
        # the program's own account of its sealed reads, for the
        # per-layer readers (a parent's program has no such route)
        served = _delta(served_before, served_after)
        run.records["sealed_read"] = [{"read_stats": served}]
        run.log(f"  sealed reads by the program: {served}")
    run.counts["reads_wrong"] = state.wrong
    return {"attempted": attempted, "failed": failed, "elapsed_s": elapsed,
            "end_to_end": {"op_p50_ms": float(p50),
                           "op_p95_ms": float(p95)}}


def verify(run, state: SealedReads, result: dict) -> list[dict]:
    expect = run.expect
    d = run.admin_delta(RECOVER_STATS)
    run.log(f"recover in the window: {d}")
    need = sum(k * state.lookups[f] for f, k in state.reads.items())
    made = sum(d[k] for k in LOOKUPS)
    plain = sum(k for f, k in state.reads.items() if not state.lookups[f])
    degraded = sum(state.reads.values()) - plain
    run.log(f"block lookups: the program made {made}, the reference needs "
            f"{need} for the {sum(state.reads.values())} reads completed")
    sealed = state.sealed_as
    out = [
        run.compare("reads_not_equal_to_their_put", state.wrong, 0),
        run.compare("operations_failed", result["failed"] - state.wrong, 0),
        run.compare("device_fallbacks", d["device_fallbacks"], 0)]
    if expect.get("recover_on_device", True):
        out.append(run.compare("windows_without_device_decodes",
                               int(d["device_decodes"] <= 0), 0))
    out += [
        run.compare("windows_without_lru_hits",
                    int(d["cache_hits"] <= 0), 0),
        run.compare("windows_without_plain_reads", int(plain <= 0), 0),
        run.compare("windows_without_reads_of_a_lost_block",
                    int(degraded <= 0), 0),
        run.compare("block_lookups_off_the_reference", abs(made - need), 0),
        run.compare(
            f"setup_seal_not_on_{expect['encode_backend']}_x"
            f"{expect['encode_devices']}",
            int(sealed.get("backend") != expect["encode_backend"]
                or sealed.get("devices") != expect["encode_devices"]
                or (sealed.get("device") or {}).get("platform")
                != expect["platform"]), 0)]
    return out
