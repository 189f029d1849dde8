"""Driver `rebuild_restore`: `ec.rebuild` of one sealed volume that lost
shards, again and again.

Set-up writes the volume through the repo's volume writer, seals it with
the shell's steps, keeps a digest of every shard file it is about to
lose, deletes those (`lost_shards` of the configuration) and waits until
the master lists the ten that are left.  The window repairs the volume
with exactly the shell's steps on one holder (master `/ec/lookup` ->
`/admin/ec/rebuild` -> `/admin/ec/mount` of the rebuilt ids;
shell/commands.py ec_rebuild, without the copies a one-holder deployment
has none of), timed from the client's side.  Before the next rebuild the
same shards are deleted again and the master waited for, outside the timed
span; the window's last rebuild stays on disk and mounted and is what the
checks read.  A rebuild the server refuses (a rebuilt CRC that misses the
`.vif` record: a corrupt survivor) is an operation that failed, counted
and compared against 0.
"""

from __future__ import annotations

import hashlib
import os
import random
import time

import reference
import reference_rebuild
import volumes
from cluster import BenchFailure, call, request, wait_until
from drivers.seal_restore import _h2d_bytes   # the same counter, summed

VID = 1


def _stat(path: str) -> list:
    st = os.stat(path)
    return [st.st_size, st.st_mtime_ns]


def _file_digest(path: str) -> bytes | None:
    """blake2b of a whole file, None where there is none."""
    h = hashlib.blake2b(digest_size=16)
    try:
        with open(path, "rb") as f:
            while chunk := f.read(32 * reference.MIB):
                h.update(chunk)
    except OSError:
        return None
    return h.digest()


class Rebuilder:
    def __init__(self, run, vol: dict):
        self.run = run
        self.vs = run.cluster.volume
        self.master = run.cluster.master
        self.collection = run.traffic["collection"]
        self.vol = vol
        self.lost = list(run.config["lost_shards"])
        self.survivors = [s for s in range(reference.TOTAL_SHARDS)
                          if s not in self.lost]
        self.live = os.path.join(run.cluster.vol_dir,
                                 f"{self.collection}_{VID}")
        self.repaired = True    # all fourteen on disk and mounted
        self.leftover = False   # files of a refused rebuild on disk
        self.sealed_as: dict = {}
        self.lost_digests: dict[int, bytes] = {}
        self.survivor_stats: dict[int, list] = {}
        self.drawn_survivor = random.Random(run.seed).choice(self.survivors)
        self.drawn_digest = None

    def shard(self, sid: int) -> str:
        return self.live + reference.shard_ext(sid)

    def listed(self) -> list[int]:
        """The shard ids the master's `/ec/lookup` lists for the volume."""
        reply = call(self.master, f"/ec/lookup?volumeId={VID}")
        return sorted(e["shard_id"] for e in reply["shard_id_locations"])

    def seal(self):
        volumes.link_volume(self.vol["base"], self.live)
        call(self.vs, "/admin/volume/mount",
             {"volume": VID, "collection": self.collection})
        for name, path, payload in volumes.seal_steps(VID, self.collection):
            reply = call(self.vs, path, payload, timeout=900)
            if name == "seal.generate":
                self.sealed_as = reply
        for sid in self.lost:
            self.lost_digests[sid] = _file_digest(self.shard(sid))
        for sid in self.survivors:
            self.survivor_stats[sid] = _stat(self.shard(sid))
        self.drawn_digest = _file_digest(self.shard(self.drawn_survivor))

    def lose(self):
        """Unmount and delete the lost shards (the one admin call does
        both) and wait until the master lists exactly the survivors."""
        t0 = time.perf_counter()
        call(self.vs, "/admin/ec/delete_shards",
             {"volume": VID, "collection": self.collection,
              "shard_ids": self.lost})
        left = [s for s in self.lost if os.path.exists(self.shard(s))]
        if left:
            raise BenchFailure(f"shard files {left} survived delete_shards")
        wait_until(f"the master to list shards {self.survivors}",
                   lambda: self.listed() == self.survivors,
                   self.run.traffic["master_wait_s"],
                   self.run.cluster.daemons)
        self.repaired = self.leftover = False
        self.run.span("restore", t0, time.perf_counter())

    def rebuild(self) -> tuple[float, dict | None, list[float], str]:
        """`weed shell ec.rebuild` on one holder.  Returns the client's
        wall, the rebuild's reply (None where the server refused it), the
        seconds of each step and the refusal."""
        t_start = t0 = time.perf_counter()
        missing = [s for s in range(reference.TOTAL_SHARDS)
                   if s not in self.listed()]
        t1 = time.perf_counter()
        self.run.span("rebuild.lookup", t0, t1)
        step_s = [round(t1 - t0, 3)]
        reply, refusal = None, ""
        try:
            reply = call(self.vs, "/admin/ec/rebuild",
                         {"volume": VID, "collection": self.collection},
                         timeout=900)
        except BenchFailure as e:
            refusal = str(e)
            self.leftover = True
        t2 = time.perf_counter()
        self.run.span("rebuild.rebuild", t1, t2)
        step_s.append(round(t2 - t1, 3))
        if reply is not None:
            call(self.vs, "/admin/ec/mount",
                 {"volume": VID, "collection": self.collection,
                  "shard_ids": missing})
            t3 = time.perf_counter()
            self.run.span("rebuild.mount", t2, t3)
            step_s.append(round(t3 - t2, 3))
            self.repaired = True
            if reply.get("rebuilt_shard_ids") != missing:
                reply, refusal = None, (
                    f"rebuilt {reply.get('rebuilt_shard_ids')}, the master "
                    f"listed {missing} as missing")
        return time.perf_counter() - t_start, reply, step_s, refusal


def prepare(run) -> Rebuilder:
    pristine = os.path.join(run.workdir, "pristine")
    os.makedirs(pristine)
    # while the daemons start and the volume server finds its device
    vol = volumes.make_volume(pristine, run.traffic["collection"], VID,
                              run.traffic["volume"]["objects"], run.seed,
                              with_digests=True)
    vol["pristine_stat"] = _stat(vol["base"] + ".dat")
    run.log(f"one volume of {vol['dat_bytes']} .dat bytes written")
    rb = Rebuilder(run, vol)
    run.wait_cluster()
    rb.seal()
    run.log(f"sealed as {rb.sealed_as.get('backend')}; digests kept of "
            f"shards {rb.lost}, and of survivor {rb.drawn_survivor}")
    rb.lose()
    # the seal warmed nothing of the rebuild: this builds or loads its step
    took, reply, step_s, refusal = rb.rebuild()
    if reply is None:
        raise BenchFailure(f"the warm-up rebuild was refused: {refusal}")
    run.log(f"warm-up rebuild took {took:.3f} s as {reply.get('backend')} "
            f"(lookup, rebuild, mount: {step_s})")
    rb.lose()
    return rb


def window(run, rb: Rebuilder, seconds: float) -> dict:
    h2d0 = _h2d_bytes(run)
    nbytes = rb.vol["dat_bytes"]
    rebuilds, refused = [], 0
    t_open = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if rb.repaired or rb.leftover:
            rb.lose()    # outside the timed span
        restore_s = time.perf_counter() - t0
        sent = time.perf_counter() - t_open
        took, reply, step_s, refusal = rb.rebuild()
        if reply is None:
            refused += 1
            run.log(f"rebuild sent at +{sent:.3f} s FAILED after "
                    f"{took:.3f} s: {refusal}")
        else:
            stats = reply.get("stage_stats") or {}
            rebuilt = sum(os.path.getsize(rb.shard(s)) for s in rb.lost)
            rebuilds.append({
                "sent_s": sent, "took_s": took, "bytes": nbytes,
                "gib": nbytes / (1 << 30), "rebuilt_bytes": rebuilt,
                "backend": reply.get("backend"),
                "devices": reply.get("devices"),
                "platform": (reply.get("device") or {}).get("platform"),
                "step_s": step_s, "stage_stats": stats})
            run.log(f"rebuild {len(rebuilds)} sent at +{sent:.3f} s (after "
                    f"a restore of {restore_s:.3f} s) took {took:.3f} s "
                    f"(lookup, rebuild, mount: {step_s}); {rebuilt} shard "
                    f"bytes rebuilt; pipeline wall {stats.get('wall')} s, "
                    + " ".join(f"{k} {stats.get(k)}" for k in (
                        "read", "dispatch", "h2d", "d2h_wait", "crc",
                        "write_wait", "write")) + " s")
        if time.perf_counter() - t_open >= seconds:
            break   # the last rebuild stays on disk for the checks
    elapsed = time.perf_counter() - t_open
    repaired = nbytes * len(rebuilds)
    wall = sum(r["took_s"] for r in rebuilds)
    survivor_bytes = len(rebuilds) * sum(
        size for size, _ in rb.survivor_stats.values())
    run.records["rebuild"] = rebuilds
    run.counts["repaired_bytes"] = repaired
    run.counts["survivor_bytes"] = survivor_bytes
    run.counts["h2d_bytes"] = _h2d_bytes(run) - h2d0
    took = sorted(r["took_s"] for r in rebuilds) or [0.0]
    run.log(f"{len(rebuilds)} whole rebuilds of {repaired} volume bytes "
            f"({sum(r['rebuilt_bytes'] for r in rebuilds)} shard bytes "
            f"rebuilt from {survivor_bytes} survivor bytes) in {wall:.3f} s "
            f"of rebuilds (sum of reply - sent), {refused} refused; rebuild "
            f"time min/median/max {took[0]:.3f}/{took[len(took) // 2]:.3f}/"
            f"{took[-1]:.3f} s; window {elapsed:.3f} s, the restores "
            f"between rebuilds are not in the rate")
    return {"attempted": len(rebuilds) + refused, "failed": refused,
            "elapsed_s": elapsed,
            "end_to_end": {
                "bulk_rate": repaired / (1 << 20) / wall if wall else 0.0}}


def _read_back(run, rb: Rebuilder) -> tuple[int, int]:
    """A seeded draw of the volume's objects, read whole after the mount:
    those not byte-identical, and the recovers the reads went through (a
    repaired volume serves every read from its own shards)."""
    written = rb.vol["written"]
    fids = random.Random(run.seed + 3).sample(
        sorted(written), min(len(written), run.traffic["readback_objects"]))
    stats = lambda: call(rb.vs, "/admin/ec/recover_stats")  # noqa: E731
    before = stats()
    bad = 0
    for f in fids:
        status, body = request(rb.vs, "GET", "/" + f)
        _, size, want = written[f]
        if status != 200 or len(body) != size \
                or reference.digest(body) != want:
            bad += 1
    after = stats()
    recovers = sum(after[k] - before[k]
                   for k in ("cache_hits", "cache_misses"))
    run.log(f"read back {len(fids)} of {len(written)} objects: {bad} "
            f"wrong, {recovers} through a recover")
    return bad, recovers


def verify(run, rb: Rebuilder, result: dict) -> list[dict]:
    expect = run.expect
    vol = rb.vol
    wrong = [r for r in run.records["rebuild"]
             if r["backend"] != expect["rebuild_backend"]
             or r["devices"] != expect["rebuild_devices"]
             or r["platform"] != expect["platform"]]
    if wrong:
        run.log(f"first rebuild off its path: {wrong[0]['backend']} on "
                f"{wrong[0]['devices']} x {wrong[0]['platform']}")
    sealed = rb.sealed_as
    out = [
        run.compare(f"rebuilds_not_on_{expect['rebuild_backend']}_x"
                    f"{expect['rebuild_devices']}", len(wrong), 0),
        run.compare(
            f"setup_seal_not_on_{expect['encode_backend']}_x"
            f"{expect['encode_devices']}",
            int(sealed.get("backend") != expect["encode_backend"]
                or sealed.get("devices") != expect["encode_devices"]), 0),
        run.compare("operations_failed", result["failed"], 0)]
    if expect.get("h2d_covers_survivors", True):
        out.append(run.compare(
            "h2d_bytes_short_of_survivor_bytes",
            max(0, run.counts["survivor_bytes"] - run.counts["h2d_bytes"]),
            0))
    run.control("shard_file", rb.shard(11))
    differ = sum(1 for sid in rb.lost
                 if _file_digest(rb.shard(sid)) != rb.lost_digests[sid])
    changed = sum(1 for sid, stat in rb.survivor_stats.items()
                  if _stat(rb.shard(sid)) != stat)
    changed += int(_file_digest(rb.shard(rb.drawn_survivor))
                   != rb.drawn_digest)
    out += [run.compare("rebuilt_files_differ_from_the_sealed", differ, 0),
            run.compare("survivor_files_changed", changed, 0)]
    present = all(os.path.exists(rb.shard(s))
                  for s in range(reference.TOTAL_SHARDS))
    out.append(run.compare("shard_crc32c_differ_from_vif",
                           reference.check_shard_crcs(rb.live)
                           if present else reference.TOTAL_SHARDS, 0))
    if present:
        sample = run.traffic["parity_sample_bytes"]
        got = reference.check_stripe_sample(rb.live, vol["base"] + ".dat",
                                            run.seed + VID, sample)
        out += [
            run.compare("parity_bytes_differ_from_reference",
                        got["parity_bytes_differ"], 0),
            run.compare("data_shard_bytes_differ_from_dat",
                        got["data_bytes_differ"], 0),
            run.compare("stripe_sample_bytes_short",
                        max(0, min(sample, vol["dat_bytes"])
                            - got["data_bytes_compared"]), 0)]
    want = run.traffic["reconstruction_sample_bytes"]
    got = reference_rebuild.check_rebuilt_sample(rb.live, rb.lost,
                                                 run.seed + 2, want)
    shard_bytes = rb.survivor_stats[rb.survivors[0]][0] * len(rb.lost)
    out += [
        run.compare("rebuilt_bytes_differ_from_reconstruction",
                    got["bytes_differ"], 0),
        run.compare("reconstruction_sample_bytes_short",
                    max(0, min(want, shard_bytes) - got["bytes_compared"]),
                    0)]
    bad, recovers = _read_back(run, rb) if rb.repaired and present \
        else (run.traffic["readback_objects"], 0)
    out += [
        run.compare("objects_not_read_back", bad, 0),
        run.compare("objects_read_back_through_a_recover", recovers, 0),
        run.compare("pristine_dat_changed",
                    int(_stat(vol["base"] + ".dat")
                        != vol["pristine_stat"]), 0)]
    return out
