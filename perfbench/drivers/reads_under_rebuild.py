"""Driver `reads_under_rebuild`: clients read two sealed volumes that each
lost the configuration's `lost_shards`, while one caller repairs the
second of them with `ec.rebuild`, again and again, in the same volume
server.

It is made of the two accepted drivers' steps and edits neither.  Volume 1
is `zipf_sealed_reads`' from end to end: its `prepare` writes, seals and
degrades it and brings its LRU to the steady state, its `window` runs the
closed-loop callers, whose draw is widened here: one draw in
`repairing_volume.read_share` is an object of volume 2, uniform over all
of them.  Volume 2 is `rebuild_restore`'s: its `Rebuilder` seals it, keeps
the digests, loses the shards and repairs with the shell's three steps, and
its `window` loops that on a thread of its own for as long as the callers
run.  `rebuild_restore` names its volume in a module constant, so the
repair side is a private copy of that module with the constant at 2.

What this file adds is what only the composition can say: which GETs
overlapped a rebuild's timed span, the repair's share of the window, the
digest of every rebuilt shard (kept by a hard link when it is deleted
again, digested beside the repair loop), and the comparison of volume 1's block lookups alone with the plain reference
(volume 2's lookups depend on when its reads fall).
"""

from __future__ import annotations

import importlib.util
import os
import queue
import threading
import time

import numpy as np

import reference
import reference_reads
from cluster import BenchFailure
from drivers import zipf_sealed_reads as zr
from drivers.closed_loop_ops import _make_volume

WAITING_VID = zr.VID     # volume 1: waits its turn, read by zipf
REPAIRING_VID = 2        # volume 2: under repair for the whole window


def _with_vid(module: str, vid: int):
    """A private copy of an accepted driver whose steps name their volume
    in a module constant, with that constant at `vid`."""
    spec = importlib.util.find_spec(module)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    copy.VID = vid
    return copy


rr = _with_vid("drivers.rebuild_restore", REPAIRING_VID)


class Repairer(rr.Rebuilder):
    """`rebuild_restore.Rebuilder` over volume 2, which also keeps every
    rebuild's timed span and every rebuilt shard: a hard link taken
    between its mount and its next delete (free on one file system) keeps
    the bytes, and a thread of the harness holds them against the digest
    taken before the first loss, beside the repair loop and not in it: the
    loop goes straight from mount to delete to the next rebuild."""

    def __init__(self, run, vol: dict):
        super().__init__(run, vol)
        self.walls: list[tuple[float, float]] = []
        self.kept_dir = os.path.join(run.workdir, "rebuilt")
        os.makedirs(self.kept_dir)
        self.kept: queue.SimpleQueue = queue.SimpleQueue()
        self.rebuilt_kept = 0
        self.rebuilt_checked = 0
        self.rebuilt_differ = 0

    def rebuild(self):
        t0 = time.perf_counter()
        out = super().rebuild()
        self.walls.append((t0, time.perf_counter()))
        return out

    def lose(self):
        if self.repaired and self.run.window_open:
            for sid in self.lost:
                link = os.path.join(
                    self.kept_dir,
                    f"{self.rebuilt_kept}{reference.shard_ext(sid)}")
                os.link(self.shard(sid), link)
                self.rebuilt_kept += 1
                self.kept.put((sid, link))
        super().lose()

    def digest_kept(self):
        """The digests' thread: every link kept, until a None."""
        while (kept := self.kept.get()) is not None:
            sid, link = kept
            differs = rr._file_digest(link) != self.lost_digests[sid]
            os.unlink(link)
            self.rebuilt_differ += int(differs)
            self.rebuilt_checked += 1


class State:
    def __init__(self, reads: zr.SealedReads, rb: Repairer):
        self.reads = reads
        self.rb = rb
        self.of_volume_2: set[str] = set()
        self.read_result: dict = {}
        self.repair_result: dict = {}


def prepare(run) -> State:
    t = run.traffic
    # volume 2's bytes while the daemons start; volume 1's follow inside
    # zipf_sealed_reads.prepare, which also waits for the cluster
    vol2 = _make_volume(run, t["collection"], REPAIRING_VID,
                        t["volume"]["objects"], True)
    vol2["pristine_stat"] = rr._stat(vol2["base"] + ".dat")
    reads = zr.prepare(run)
    rb = Repairer(run, vol2)
    rb.seal()
    rb.lose()
    took, reply, step_s, refusal = rb.rebuild()
    if reply is None:
        raise BenchFailure(f"the warm-up rebuild was refused: {refusal}")
    run.log(f"volume {REPAIRING_VID} sealed as "
            f"{rb.sealed_as.get('backend')}; warm-up rebuild of shards "
            f"{rb.lost} took {took:.3f} s as {reply.get('backend')} "
            f"(lookup, rebuild, mount: {step_s})")
    rb.lose()

    state = State(reads, rb)
    # volume 2's objects join the callers' draw and the reference dict
    extents = reference.read_ecx(vol2["live"] + ".ecx")
    other = []
    for f, (nid, size, dig) in sorted(vol2["written"].items()):
        offset, stored = extents[nid]
        plan = reference_reads.read_plan(
            offset, reference.needle_disk_size(stored), vol2["dat_bytes"],
            rb.lost, block=t["recover_block_bytes"])
        reads.reference[f] = (size, dig)
        reads.lookups[f] = len(plan["blocks"])   # while it is degraded
        if size >= t["large_from_bytes"]:
            reads.large.add(f)
        other.append(f)
    state.of_volume_2 = set(other)
    share = t["repairing_volume"]["read_share"]
    zipf_draw = reads.draw

    def draw(rng):
        if rng.random() < share:
            return other[rng.randrange(len(other))]
        return zipf_draw(rng)

    reads.draw = draw
    run.log(f"the callers' draw: {100 * share:.1f}% an object of volume "
            f"{REPAIRING_VID}, uniform over its {len(other)}, the others "
            f"volume {WAITING_VID}'s zipf stream")
    return state


def _percentile(ms: np.ndarray, q: float) -> float | None:
    return float(np.percentile(ms, q)) if ms.size else None


def overlaps(gets: np.ndarray, walls: np.ndarray):
    """For GETs (n, 2) and rebuilds (m, 2) as (start, end) rows, the
    rebuilds in the order they ran, one after another: which GETs
    overlapped a rebuild's span, and which began inside one."""
    walls = walls.reshape(-1, 2)
    if not len(gets) or not len(walls):
        none = np.zeros(len(gets), dtype=bool)
        return none, none.copy()
    starts, ends = walls[:, 0], walls[:, 1]
    # the last rebuild that began before the GET ended overlaps it if it
    # ended after the GET began; an earlier one ended earlier still
    last = np.searchsorted(starts, gets[:, 1], side="left") - 1
    beside = (last >= 0) & (ends[np.maximum(last, 0)] > gets[:, 0])
    own = np.searchsorted(starts, gets[:, 0], side="right") - 1
    began = (own >= 0) & (ends[np.maximum(own, 0)] > gets[:, 0])
    return beside, began


def window(run, state: State, seconds: float) -> dict:
    repair: dict = {}

    def repairs():
        try:
            repair["result"] = rr.window(run, state.rb, seconds)
        except BaseException as e:   # raised below, on the window's thread
            repair["error"] = e

    rb = state.rb
    rb.walls.clear()             # the warm-up rebuild's is not the window's
    digests = threading.Thread(target=rb.digest_kept, name="digests",
                               daemon=True)
    th = threading.Thread(target=repairs, name="repair", daemon=True)
    digests.start()
    th.start()
    state.read_result = reads = zr.window(run, state.reads, seconds)
    th.join()
    rb.kept.put(None)
    digests.join()
    if "error" in repair:
        raise repair["error"]
    state.repair_result = repaired = repair["result"]

    # a rebuild reads ten of the thirteen survivors (rebuild_restore counts
    # all that stand, which are the ten it reads in its own cell)
    rebuilds = run.records["rebuild"]
    shard_bytes = rb.survivor_stats[rb.survivors[0]][0]
    run.counts["survivor_bytes"] = \
        len(rebuilds) * reference.DATA_SHARDS * shard_bytes
    for r in rebuilds:
        r["rebuilds"] = 1        # the readers' denominator: a rebuild
    for r in run.records.get("sealed_read", []):
        r["windows"] = 1         # ... and a window

    # which GETs overlapped a rebuild's timed span (lookup -> mount)
    gets = np.array(run.spans.get("get_sealed", []),
                    dtype=np.float64).reshape(-1, 2)
    beside, began = overlaps(gets, np.array(rb.walls, dtype=np.float64))
    began_inside = int(began.sum())
    ms = (gets[:, 1] - gets[:, 0]) * 1e3
    for name, value in (
            ("get_beside_rebuild_p50_ms", _percentile(ms[beside], 50)),
            ("get_beside_rebuild_p95_ms", _percentile(ms[beside], 95)),
            ("get_between_rebuilds_p50_ms", _percentile(ms[~beside], 50))):
        if value is not None:
            run.counts[name] = value
    run.log(f"  {int(beside.sum())} GETs overlapped a rebuild's span (p50 "
            f"{run.counts.get('get_beside_rebuild_p50_ms')}, p95 "
            f"{run.counts.get('get_beside_rebuild_p95_ms')} ms), "
            f"{int((~beside).sum())} none (p50 "
            f"{run.counts.get('get_between_rebuilds_p50_ms')} ms); "
            f"{began_inside} began inside one")
    run.counts["reads_begun_inside_a_rebuild"] = began_inside
    wall = sum(r["took_s"] for r in rebuilds)
    run.counts["repair_duty_share"] = 100.0 * wall / max(
        repaired["elapsed_s"], 1e-9)
    restores = sorted(e - s for s, e in run.spans.get("restore", []))
    run.log(f"repair beside the reads: {len(rebuilds)} rebuilds in "
            f"{wall:.3f} s of {repaired['elapsed_s']:.3f} s (duty share "
            f"{run.counts['repair_duty_share']:.2f}%), "
            f"{repaired['end_to_end']['bulk_rate']:.2f} MiB/s of .dat; the "
            f"untimed delete-and-wait between rebuilds: {len(restores)}, "
            f"median {restores[len(restores) // 2] if restores else 0:.3f} "
            f"s, max {restores[-1] if restores else 0:.3f} s; "
            f"{rb.rebuilt_kept} "
            f"rebuilt shards kept by a hard link before their delete, "
            f"{rb.rebuilt_checked} digested beside the loop, "
            f"{rb.rebuilt_differ} differ")
    return {"attempted": reads["attempted"] + repaired["attempted"],
            "failed": reads["failed"] + repaired["failed"],
            "elapsed_s": reads["elapsed_s"],
            "end_to_end": {**reads["end_to_end"],
                           "bulk_rate": repaired["end_to_end"]["bulk_rate"]}}


def _lookups_off(run, state: State) -> int:
    """Volume 1's block lookups of the window, as its recovered-block
    cache counts them, against the reference's count for its completed
    reads."""
    reads = state.reads
    done_1 = {f: k for f, k in reads.reads.items()
              if f not in state.of_volume_2}
    need_1 = sum(k * reads.lookups[f] for f, k in done_1.items())
    before, after = (snap.get("volumes", {}).get(str(WAITING_VID), {})
                     for snap in run.admin[zr.RECOVER_STATS])
    if "lookups" not in after:
        run.log(f"block lookups of volume {WAITING_VID}: the program keeps "
                f"no count a volume: nothing to compare with the "
                f"{need_1} the reference needs")
        return 0
    made_1 = after["lookups"] - before.get("lookups", 0)
    run.log(f"block lookups of volume {WAITING_VID}: the program made "
            f"{made_1}, the reference needs {need_1} for its "
            f"{sum(done_1.values())} reads completed")
    return abs(made_1 - need_1)


def verify(run, state: State, result: dict) -> list[dict]:
    expect = run.expect
    reads, rb = state.reads, state.rb
    d = run.admin_delta(zr.RECOVER_STATS)
    run.log(f"recover in the window: {d}")
    sealed = reads.sealed_as
    out = [
        run.compare("reads_not_equal_to_their_put", reads.wrong, 0),
        run.compare("operations_failed",
                    state.read_result["failed"] - reads.wrong, 0),
        run.compare("device_fallbacks", d["device_fallbacks"], 0)]
    if expect.get("recover_on_device", True):
        out.append(run.compare("windows_without_device_decodes",
                               int(d["device_decodes"] <= 0), 0))
    out += [
        run.compare("windows_without_lru_hits",
                    int(d["cache_hits"] <= 0), 0),
        run.compare(f"block_lookups_of_volume_{WAITING_VID}_off_the_"
                    f"reference",
                    _lookups_off(run, state), 0),
        run.compare("windows_without_a_completed_rebuild",
                    int(not run.records["rebuild"]), 0),
        run.compare("windows_without_a_read_begun_inside_a_rebuild",
                    int(run.counts["reads_begun_inside_a_rebuild"] <= 0), 0),
        # a link kept and not digested is not shown to be equal
        run.compare("rebuilt_shards_of_the_window_differ_from_the_sealed",
                    rb.rebuilt_differ + rb.rebuilt_kept - rb.rebuilt_checked,
                    0),
        run.compare(
            f"setup_seal_of_volume_{WAITING_VID}_not_on_"
            f"{expect['encode_backend']}_x{expect['encode_devices']}",
            int(sealed.get("backend") != expect["encode_backend"]
                or sealed.get("devices") != expect["encode_devices"]
                or (sealed.get("device") or {}).get("platform")
                != expect["platform"]), 0)]
    # the repair's own checks, rebuild-4lost's, under this cell's names
    # where a name of the read side's says something else
    renamed = {"operations_failed": "rebuilds_refused"}
    for c in rr.verify(run, rb, state.repair_result):
        name = renamed.get(c["name"], c["name"]).replace(
            "setup_seal_not_on", f"setup_seal_of_volume_{REPAIRING_VID}_"
                                 f"not_on")
        out.append({**c, "name": name})
    return out
