"""Driver `seal_restore`: `ec.encode` of one seeded volume, again and again.

Set-up writes the volume through the repo's volume writer and mounts it.
The window seals it with exactly the shell's steps (readonly ->
/admin/ec/generate -> mount -> delete volume), timed from the client's
side.  Before the next seal the volume is restored from its hard-linked
pristine `.dat`/`.idx`, outside the timed span; the window's last seal
stays on disk and is what the checks read.  One volume and not a ring of
several: on the check's machines the shard files of a second sealed
volume stall every file operation (PERF.md, PR 24, finding 1).
"""

from __future__ import annotations

import os
import time

import reference
import volumes
from cluster import BenchFailure, call, scrape

ALL_SHARDS = list(range(reference.TOTAL_SHARDS))
VID = 1


def _h2d_bytes(run) -> float:
    return sum(v for name, labels, v in scrape(run.cluster.volume)
               if name.endswith("ec_device_h2d_bytes_total")
               and labels.get("device") != "host")


class Sealer:
    def __init__(self, run, vol: dict):
        self.run = run
        self.vs = run.cluster.volume
        self.collection = run.traffic["collection"]
        self.vol = vol
        self.live = os.path.join(run.cluster.vol_dir,
                                 f"{self.collection}_{VID}")
        self.sealed = False

    def mount(self):
        volumes.link_volume(self.vol["base"], self.live)
        call(self.vs, "/admin/volume/mount",
             {"volume": VID, "collection": self.collection})

    def restore(self):
        t0 = time.perf_counter()
        call(self.vs, "/admin/ec/delete_shards",
             {"volume": VID, "collection": self.collection,
              "shard_ids": ALL_SHARDS})
        self.mount()
        self.sealed = False
        self.run.span("restore", t0, time.perf_counter())

    def seal(self) -> tuple[float, dict]:
        t_seal = time.perf_counter()
        step_s = []
        for name, path, payload in volumes.seal_steps(VID, self.collection):
            t0 = time.perf_counter()
            reply = call(self.vs, path, payload, timeout=900)
            t1 = time.perf_counter()
            self.run.span(name, t0, t1)
            step_s.append(round(t1 - t0, 3))
            if name == "seal.generate":
                generated = reply
        took = time.perf_counter() - t_seal
        generated["step_s"] = step_s
        self.sealed = True
        return took, generated


def prepare(run) -> Sealer:
    pristine = os.path.join(run.workdir, "pristine")
    os.makedirs(pristine)
    # while the daemons start and the volume server finds its device
    vol = volumes.make_volume(pristine, run.traffic["collection"], VID,
                              run.traffic["volume"]["objects"], run.seed,
                              with_digests=False)
    st = os.stat(vol["base"] + ".dat")
    vol["pristine_stat"] = [st.st_size, st.st_mtime_ns]
    run.log(f"one volume of {vol['dat_bytes']} .dat bytes written")
    sealer = Sealer(run, vol)
    run.wait_cluster()
    sealer.mount()
    took, reply = sealer.seal()   # compiles or loads the step
    run.log(f"warm-up seal took {took:.3f} s as {reply.get('backend')}")
    return sealer


def window(run, sealer: Sealer, seconds: float) -> dict:
    h2d0 = _h2d_bytes(run)
    nbytes = sealer.vol["dat_bytes"]
    seals = []
    t_open = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        if sealer.sealed:
            sealer.restore()    # outside the timed span
        restore_s = time.perf_counter() - t0
        sent = time.perf_counter() - t_open
        took, reply = sealer.seal()
        stats = reply.get("stage_stats") or {}
        seals.append({"sent_s": sent, "took_s": took, "bytes": nbytes,
                      "gib": nbytes / (1 << 30),
                      "backend": reply.get("backend"),
                      "devices": reply.get("devices"),
                      "platform": (reply.get("device") or {}).get("platform"),
                      "stage_stats": stats})
        run.log(f"seal {len(seals)} sent at +{sent:.3f} s (after a restore "
                f"of {restore_s:.3f} s) took {took:.3f} s (readonly, "
                f"generate, mount, delete: {reply['step_s']}); pipeline "
                f"wall {stats.get('wall')} s, busy read {stats.get('read')} "
                f"dispatch {stats.get('dispatch')} encode_crc "
                f"{stats.get('encode_crc')} write {stats.get('write')} s")
        if time.perf_counter() - t_open >= seconds:
            break   # the last seal stays on disk for the checks
    elapsed = time.perf_counter() - t_open
    sealed_bytes = nbytes * len(seals)
    seal_wall = sum(s["took_s"] for s in seals)
    run.records["seal"] = seals
    run.counts["sealed_bytes"] = sealed_bytes
    run.counts["h2d_bytes"] = _h2d_bytes(run) - h2d0
    took = sorted(s["took_s"] for s in seals)
    run.log(f"{len(seals)} whole seals of {sealed_bytes} volume bytes in "
            f"{seal_wall:.3f} s of seals (sum of reply - sent); seal time "
            f"min/median/max {took[0]:.3f}/{took[len(took) // 2]:.3f}/"
            f"{took[-1]:.3f} s; window {elapsed:.3f} s, the restores "
            f"between seals are not in the rate")
    return {"attempted": len(seals), "failed": 0, "elapsed_s": elapsed,
            "end_to_end": {
                "bulk_rate": sealed_bytes / (1 << 20) / seal_wall}}


def verify(run, sealer: Sealer, result: dict) -> list[dict]:
    expect = run.expect
    vol = sealer.vol
    wrong = [s for s in run.records["seal"]
             if s["backend"] != expect["encode_backend"]
             or s["devices"] != expect["encode_devices"]
             or s["platform"] != expect["platform"]]
    if wrong:
        run.log(f"first seal off its path: {wrong[0]['backend']} on "
                f"{wrong[0]['devices']} x {wrong[0]['platform']}")
    out = [run.compare(
        f"seals_not_on_{expect['encode_backend']}_x"
        f"{expect['encode_devices']}", len(wrong), 0)]
    if expect.get("h2d_covers_dat", True):
        out.append(run.compare(
            "h2d_bytes_short_of_sealed_bytes",
            max(0, run.counts["sealed_bytes"] - run.counts["h2d_bytes"]), 0))
    if not sealer.sealed:
        raise BenchFailure("no seal is left on disk to check")
    run.control("shard_file", sealer.live + reference.shard_ext(11))
    sample = run.traffic["parity_sample_bytes"]
    got = reference.check_stripe_sample(sealer.live, vol["base"] + ".dat",
                                        run.seed + VID, sample)
    st = os.stat(vol["base"] + ".dat")
    out += [
        run.compare("shard_crc32c_differ_from_vif",
                    reference.check_shard_crcs(sealer.live), 0),
        run.compare("parity_bytes_differ_from_reference",
                    got["parity_bytes_differ"], 0),
        run.compare("data_shard_bytes_differ_from_dat",
                    got["data_bytes_differ"], 0),
        run.compare("stripe_sample_bytes_short",
                    max(0, min(sample, vol["dat_bytes"])
                        - got["data_bytes_compared"]), 0),
        run.compare("pristine_dat_changed",
                    int([st.st_size, st.st_mtime_ns]
                        != vol["pristine_stat"]), 0)]
    return out
