"""The sealed volume's read path (`EcVolume.read_needle`) against the plain
reference of the benchmark (`perfbench/reference.py`, `reference_reads.py`,
`reference_rebuild.py`): with each one of the fourteen shards lost in turn,
and with none, every object reads back byte-identical, the program's new
counters (`ReadStats`, `/admin/ec/read_stats`) count the intervals and the
bytes the reference's extent maths gives, the recovered-block cache is
looked up once for every recovery block the reference names, and the shard
the program reconstructs from the ten survivors it picked is the pristine
one.  Then the three `ec.read.*` spans, the route and the Prometheus
families.

Small sizes at the deployment's block sizes, seeded bytes, the CPU
backend: results and counts, never a time."""

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import reference  # noqa: E402
import reference_reads  # noqa: E402
import reference_rebuild  # noqa: E402

from seaweedfs_tpu import tracing  # noqa: E402
from seaweedfs_tpu.stats import metrics as stats  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import encoder as enc  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import \
    recover as recover_mod  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (  # noqa: E402
    READ_STATS, EcVolume, EcVolumeShard, ReadStats)
from seaweedfs_tpu.storage.needle import Needle  # noqa: E402
from seaweedfs_tpu.storage.volume import Volume  # noqa: E402

VID = 1
SIZES = [4 << 20] * 3 + [32 << 10] * 40     # 13.25 MiB: two 10 MiB rows
LOOKUPS = ("cache_hits", "cache_misses", "coalesced")
READ_SPANS = ("ec.read.locate", "ec.read.shard", "ec.read.assemble")
COUNTERS = ("needles", "timed_needles", "intervals", "intervals_plain",
            "intervals_recovered", "bytes_plain", "bytes_recovered",
            "index_preads", "needles_beside_job", "local_fallbacks")
SECONDS = ("locate_seconds", "shard_seconds", "assemble_seconds")


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """One volume of 4 MiB and 32 KiB objects in a seeded order, sealed on
    the CPU: its fourteen pristine shard files, its bodies, and what the
    reference says each object's read needs."""
    d = str(tmp_path_factory.mktemp("sealed_read"))
    rng = np.random.default_rng(36)
    sizes = list(SIZES)
    rng.shuffle(sizes)
    v = Volume(d, "", VID)
    bodies = {}
    for nid, nbytes in enumerate(sizes, 1):
        data = rng.bytes(nbytes)
        n = Needle.create(data)
        n.id, n.cookie = nid, 0x3600 + nid
        v.write_needle(n)
        bodies[nid] = (n.cookie, data)
    v.sync()
    base = v.file_name()
    v.close()
    enc.write_ec_files(base)
    enc.write_sorted_file_from_idx(base)
    enc.save_volume_info(base, version=3)
    dat_size = os.path.getsize(base + ".dat")
    extents = {nid: (offset, reference.needle_disk_size(stored))
               for nid, (offset, stored)
               in reference.read_ecx(base + ".ecx").items()}
    assert sorted(extents) == sorted(bodies)
    shard_size = os.path.getsize(base + reference.shard_ext(0))
    assert shard_size == reference_reads.shard_file_size(dat_size) == 2 << 20
    return {"dir": d, "base": base, "bodies": bodies, "dat_size": dat_size,
            "extents": extents, "shard_size": shard_size}


def _mount(sealed, lost):
    ev = EcVolume(sealed["dir"], "", VID)
    for sid in range(reference.TOTAL_SHARDS):
        if sid != lost:
            ev.add_shard(EcVolumeShard(sealed["dir"], "", VID, sid))
    return ev


def _delta(before, after, keys):
    return {k: after[k] - before[k] for k in keys}


def _shard_file(sealed, sid):
    with open(sealed["base"] + reference.shard_ext(sid), "rb") as f:
        return f.read()


@pytest.mark.parametrize("lost", [None, *range(reference.TOTAL_SHARDS)],
                         ids=lambda s: "none" if s is None else f"shard{s}")
def test_reads_counters_lookups_and_reconstruction_against_the_reference(
        sealed, lost):
    plans = {nid: reference_reads.read_plan(
        offset, length, sealed["dat_size"], [] if lost is None else [lost])
        for nid, (offset, length) in sealed["extents"].items()}
    ev = _mount(sealed, lost)
    try:
        read0, recover0 = READ_STATS.snapshot(), recover_mod.STATS.snapshot()
        for nid, (cookie, data) in sealed["bodies"].items():
            assert ev.read_needle(nid, cookie=cookie).data == data, nid
        read = _delta(read0, READ_STATS.snapshot(), COUNTERS)
        recover = _delta(recover0, recover_mod.STATS.snapshot(), LOOKUPS)

        # intervals and bytes, plain against recovered
        intervals = sum(len(p["intervals"]) for p in plans.values())
        recovered = [iv for p in plans.values() for iv in p["recovered"]]
        assert read["needles"] == len(sealed["bodies"])
        assert read["index_preads"] == 0      # the index is a mapping
        assert read["intervals_plain"] + read["intervals_recovered"] \
            == read["intervals"] == intervals
        assert read["intervals_recovered"] == len(recovered)
        assert read["bytes_recovered"] == sum(n for _, _, n in recovered)
        assert read["bytes_plain"] + read["bytes_recovered"] == sum(
            length for _, length in sealed["extents"].values())
        data_shard_lost = lost is not None and lost < reference.DATA_SHARDS
        assert bool(recovered) == data_shard_lost

        # one cache lookup for every recovery block the reference names;
        # one caller and a cache larger than the shard: a miss a block
        blocks = [b for p in plans.values() for b in p["blocks"]]
        assert sum(recover.values()) == len(blocks)
        assert recover["cache_misses"] == len(set(blocks))
        assert recover["coalesced"] == 0
        if data_shard_lost:   # the second row ends inside shard 3
            assert 0 < len(set(blocks)) <= sealed["shard_size"] // \
                reference_reads.RECOVER_BLOCK

        if lost is None:
            return
        # the ten survivors the program picks are the first ten present
        # (parity 10 stands in for a lost data shard), and what they
        # reconstruct is the pristine shard: by the reference's GF(2^8)
        # maths and by the program's own recovery
        size = sealed["shard_size"]
        survivors, rows = ev._fetch_survivors(lost, 0, size)
        assert list(survivors) == [s for s in range(reference.TOTAL_SHARDS)
                                   if s != lost][:reference.DATA_SHARDS]
        pristine = _shard_file(sealed, lost)
        rebuilt = reference_rebuild.reconstruct(list(survivors), rows, [lost])
        assert rebuilt[0].tobytes() == pristine
        assert ev._recover_span(lost, 0, size) == pristine
    finally:
        ev.close()


def test_reference_intervals_are_the_program_s(sealed):
    """The reference's pieces (shard, offset in its file, bytes) against
    the program's `locate_data`, object by object."""
    ev = _mount(sealed, None)
    try:
        for nid, (offset, length) in sealed["extents"].items():
            _, _, intervals = ev.locate_needle(nid)
            got = [(*iv.to_shard_id_and_offset(ev.large_block_size,
                                               ev.small_block_size), iv.size)
                   for iv in intervals]
            assert got == reference_reads.intervals_of_extent(
                offset, length, sealed["dat_size"]), nid
    finally:
        ev.close()


# -- the spans ------------------------------------------------------------------

def _stage_names(monkeypatch) -> list:
    names = []
    real = tracing.stage.__init__

    def recording(self, name, *a, **kw):
        names.append(name)
        real(self, name, *a, **kw)

    monkeypatch.setattr(tracing.stage, "__init__", recording)
    return names


@pytest.fixture
def sampled_request(monkeypatch):
    """The thread inside a request that tracing sampled, as the volume
    server's GET handler is for one request in a hundred by default."""
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "1")
    tracing.RECORDER.reset()
    root = tracing.start("needle.read", service="volume")
    assert root.sampled
    prev = tracing.swap(root)
    yield root
    tracing.restore(prev)
    root.finish()


def _one_of(sealed, large: bool, lost):
    """A needle id of the wanted size whose read holds (or, with `lost`
    None, does not need) a block of the lost shard."""
    for nid, (offset, length) in sealed["extents"].items():
        plan = reference_reads.read_plan(offset, length, sealed["dat_size"],
                                         [0])
        if (length > 1 << 20) == large and bool(plan["recovered"]) == (
                lost is not None):
            return nid, plan
    raise AssertionError("no such object in the layout")


@pytest.mark.parametrize("large", [False, True], ids=["32KiB", "4MiB"])
def test_a_plain_sealed_get_is_locate_a_shard_read_an_interval_assemble(
        sealed, large, monkeypatch, sampled_request):
    nid, plan = _one_of(sealed, large, None)
    ev = _mount(sealed, None)
    names = _stage_names(monkeypatch)
    try:
        ev.read_needle(nid)
    finally:
        ev.close()
    k = len(plan["intervals"])
    assert k == (5 if large else 1)
    assert names == ["ec.read.locate"] + ["ec.read.shard"] * k \
        + ["ec.read.assemble"]
    assert 3 <= len(names) <= 8       # the cost stated: 3 to 8 a GET


def test_a_recovered_interval_has_no_shard_span_and_keeps_its_recover_spans(
        sealed, monkeypatch, sampled_request):
    nid, plan = _one_of(sealed, True, 0)
    ev = _mount(sealed, 0)
    names = _stage_names(monkeypatch)
    try:
        ev.read_needle(nid)
    finally:
        ev.close()
    plain = len(plan["intervals"]) - len(plan["recovered"])
    assert names.count("ec.read.shard") == plain
    assert names.count("ec.recover.serve") == len(plan["recovered"]) == 1
    assert names[0] == "ec.read.locate" and names[-1] == "ec.read.assemble"
    assert "ec.recover.fetch" in names


def test_read_spans_are_children_of_a_sampled_request(sealed, monkeypatch,
                                                      sampled_request):
    root = sampled_request
    nid, plan = _one_of(sealed, False, None)
    built = []
    real = tracing.Span.finish

    def finishing(self, *a, **kw):
        built.append(self)
        return real(self, *a, **kw)

    monkeypatch.setattr(tracing.Span, "finish", finishing)
    ev = _mount(sealed, None)
    try:
        before = READ_STATS.snapshot()
        ev.read_needle(nid)
        after = READ_STATS.snapshot()
    finally:
        ev.close()
    spans = {sp.name: sp for sp in built}
    assert set(spans) == set(READ_SPANS)
    for name, key in zip(READ_SPANS, SECONDS):
        assert spans[name].parent_id == root.span_id
        # the span and the counter are the same measurement
        assert after[key] - before[key] == pytest.approx(
            spans[name].duration, abs=2e-6)
    assert spans["ec.read.assemble"].tags == {
        "n": 1, "bytes": plan["intervals"][0][2]}
    assert after["timed_needles"] - before["timed_needles"] == 1


@pytest.mark.parametrize("span", ["none", "unsampled"])
def test_a_get_nobody_samples_is_counted_and_not_timed(sealed, monkeypatch,
                                                       span):
    """The path of every GET: no stage is built, no clock is read for
    one, and the needle is still counted with its intervals and bytes."""
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    nid, plan = _one_of(sealed, True, 0)
    ev = _mount(sealed, 0)
    names = _stage_names(monkeypatch)
    root = tracing.start("needle.read") if span == "unsampled" else None
    prev = tracing.swap(root)
    try:
        before = READ_STATS.snapshot()
        ev.read_needle(nid)
        after = READ_STATS.snapshot()
    finally:
        tracing.restore(prev)
        ev.close()
    assert not [n for n in names if n.startswith("ec.read.")]
    assert "ec.recover.serve" in names     # per block, timed as ever
    d = _delta(before, after, COUNTERS + SECONDS)
    assert d == {**dict.fromkeys(SECONDS, 0.0), "needles": 1,
                 "timed_needles": 0, "intervals": len(plan["intervals"]),
                 "intervals_plain": len(plan["intervals"]) - 1,
                 "intervals_recovered": 1,
                 "bytes_plain": sum(n for _, _, n in plan["intervals"])
                 - plan["recovered"][0][2],
                 "bytes_recovered": plan["recovered"][0][2],
                 "index_preads": 0, "needles_beside_job": 0,
                 "local_fallbacks": 0}


def test_sampled_stage_is_a_stage_when_sampled_or_profiled(monkeypatch):
    got = {}
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    with tracing.sampled_stage("ec.read.locate", got.__setitem__,
                               "locate") as st:
        assert st is None
    assert got == {}

    class On:
        def __init__(self, name):
            pass

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_trace_annotation", On)
    with tracing.sampled_stage("ec.read.locate", got.__setitem__, "locate",
                               2, 64) as st:
        assert isinstance(st, tracing.stage) and (st.n, st.nbytes) == (2, 64)
    assert got["locate"] == st.seconds >= 0.0


def test_a_read_that_fails_counts_no_needle(sealed):
    from seaweedfs_tpu.storage.erasure_coding.ec_volume import \
        EcNotFoundError

    ev = _mount(sealed, None)
    before = READ_STATS.snapshot()
    try:
        with pytest.raises(EcNotFoundError):
            ev.read_needle(10_000)
    finally:
        ev.close()
    after = READ_STATS.snapshot()
    assert _delta(before, after, COUNTERS) == dict.fromkeys(COUNTERS, 0)
    assert after["locate_seconds"] >= before["locate_seconds"]


# -- the index: searched in memory, counted where a path reads the file ----------

def _count_ecx_preads(monkeypatch):
    """The `os.pread` calls made on any descriptor of an `.ecx` file."""
    calls = []
    real = os.pread

    def counting(fileno, *a):
        if os.readlink(f"/proc/self/fd/{fileno}").endswith(".ecx"):
            calls.append(a)
        return real(fileno, *a)

    monkeypatch.setattr(os, "pread", counting)
    return calls


def test_index_preads_counts_the_preads_of_a_lookup_sent_to_the_file(
        sealed, monkeypatch):
    """The counter counts: a `_search_ecx` that goes to the file as
    `rebuild_ecx_file`'s search does costs a needle its probes, the
    number of `os.pread` calls the `.ecx`'s descriptor saw; the search
    that is there costs none."""
    from seaweedfs_tpu.storage.erasure_coding import ec_volume as ecv

    ev = _mount(sealed, None)
    calls = _count_ecx_preads(monkeypatch)
    nids = list(sealed["bodies"])[:7]
    try:
        before = READ_STATS.snapshot()
        for nid in nids:
            ev.read_needle(nid)
        assert calls == []
        assert _delta(before, READ_STATS.snapshot(),
                      ("needles", "index_preads")) == {
            "needles": len(nids), "index_preads": 0}

        monkeypatch.setattr(
            EcVolume, "_search_ecx",
            lambda self, nid: ecv.search_sorted_index(
                self._ecx.fileno(), self.ecx_file_size // 16, nid))
        before = READ_STATS.snapshot()
        for nid in nids:
            ev.read_needle(nid)
        d = _delta(before, READ_STATS.snapshot(), ("needles", "index_preads"))
        assert d == {"needles": len(nids), "index_preads": len(calls)}
        # 43 entries: at most six probes a lookup, and never none
        assert len(nids) <= len(calls) <= 6 * len(nids)
        # a search outside a read leaks into no needle's count
        ecv.search_sorted_index(ev._ecx.fileno(), ev.ecx_file_size // 16, 1)
        monkeypatch.undo()
        before = READ_STATS.snapshot()
        ev.read_needle(nids[0])
        assert READ_STATS.snapshot()["index_preads"] \
            == before["index_preads"]
    finally:
        ev.close()


def test_deep_scrub_s_needle_walk_skips_a_tombstone(sealed, tmp_path,
                                                    monkeypatch):
    """The curator's walk over every `.ecx` entry of a sealed volume
    (`deep_scrub_host`): a deleted needle is not re-read, every other
    one is, and the walk preads the `.ecx` for none of them."""
    import shutil

    from seaweedfs_tpu.maintenance.deep_scrub import deep_scrub_host
    from seaweedfs_tpu.storage.tools import shard_file_crc32c

    for name in os.listdir(sealed["dir"]):
        if not name.endswith((".dat", ".idx")):
            shutil.copy(os.path.join(sealed["dir"], name), tmp_path)
    base = str(tmp_path / str(VID))
    enc.save_volume_info(base, version=3, extra={"shard_crc32c": [
        shard_file_crc32c(base + reference.shard_ext(sid))
        for sid in range(reference.TOTAL_SHARDS)]})
    ev = EcVolume(str(tmp_path), "", VID)
    gone = sorted(sealed["bodies"])[len(sealed["bodies"]) // 2]
    ev.delete_needle(gone)
    ev.close()
    ecx_reads = _count_ecx_preads(monkeypatch)
    before = READ_STATS.snapshot()
    report = deep_scrub_host(str(tmp_path), "", VID)
    monkeypatch.undo()
    live = len(sealed["bodies"]) - 1
    assert report["ok"] and report["needles_bad"] == 0
    assert report["needles_checked"] == live
    assert _delta(before, READ_STATS.snapshot(),
                  ("needles", "index_preads")) == {
        "needles": live, "index_preads": 0}
    assert ecx_reads == []


# -- the counters' own arithmetic, the route and the families -------------------

def test_read_stats_snapshot_and_reset():
    s = ReadStats()
    assert s.snapshot() == {**dict.fromkeys(SECONDS, 0.0),
                            **dict.fromkeys(COUNTERS, 0)}
    s.add_stage("locate", 0.0000123)
    s.add_stage("locate", 0.0004)
    s.needle(5, 4194336, 1, 1000, True, 13)
    s.needle(1, 32800, 0, 0, False)
    snap = s.snapshot()
    assert list(snap) == list(SECONDS) + list(COUNTERS)
    assert snap["locate_seconds"] == 0.000412     # microseconds kept
    assert snap["needles"] == 2 and snap["timed_needles"] == 1
    assert snap["intervals"] == 6
    assert snap["intervals_plain"] == 5 and snap["intervals_recovered"] == 1
    assert snap["bytes_plain"] == 4194336 - 1000 + 32800
    assert snap["bytes_recovered"] == 1000
    assert snap["index_preads"] == 13     # an addend, 0 unless given
    with pytest.raises(KeyError):
        s.add_stage("no_such_stage", 1.0)
    s.reset()
    assert s.snapshot()["needles"] == 0


def test_read_stats_loses_no_update_under_many_threads():
    """More threads than cores and a switch every few bytecodes: a
    read-modify-write outside the lock would lose counts."""
    import threading

    s = ReadStats()
    threads, rounds = 16, 2000

    def work():
        for _ in range(rounds):
            s.needle(5, 1000, 1, 100, True, 12)
            s.add_stage("shard", 0.5)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(60)
        assert not any(th.is_alive() for th in pool)
    finally:
        sys.setswitchinterval(interval)
    total = threads * rounds
    assert s.snapshot() == {
        "locate_seconds": 0.0, "shard_seconds": 0.5 * total,
        "assemble_seconds": 0.0, "needles": total, "timed_needles": total,
        "intervals": 5 * total, "intervals_plain": 4 * total,
        "intervals_recovered": total, "bytes_plain": 900 * total,
        "bytes_recovered": 100 * total, "index_preads": 12 * total,
        "needles_beside_job": 0, "local_fallbacks": 0}


def _family(text, family):
    return {m.group(1) or "": float(m.group(2)) for m in re.finditer(
        r"^" + re.escape(family) + r"(\{[^}]*\})? (\S+)$", text, re.M)}


def test_prometheus_families_move_with_the_counters(sealed):
    fam = "SeaweedFS_volumeServer_ec_read_"

    def scrape():    # what the volume server's /metrics does first
        READ_STATS.export()
        return stats.REGISTRY.expose(), READ_STATS.snapshot()

    ev = _mount(sealed, 0)
    text0, snap0 = scrape()
    try:
        for nid, (cookie, _) in sealed["bodies"].items():
            ev.read_needle(nid, cookie=cookie)
    finally:
        ev.close()
    # a GET touches no ec_read vector: they move at a scrape
    for family in ("needles_total", "intervals_total", "bytes_total",
                   "index_preads_total"):
        assert _family(stats.REGISTRY.expose(), fam + family) \
            == _family(text0, fam + family)
    text1, snap1 = scrape()
    d = _delta(snap0, snap1, COUNTERS)
    assert d["intervals_recovered"] > 0 and d["intervals_plain"] > 0

    def moved(family, labels=""):
        return _family(text1, family)[labels] \
            - _family(text0, family).get(labels, 0.0)

    assert moved(fam + "needles_total", '{needles="all"}') == d["needles"]
    assert moved(fam + "needles_total", '{needles="timed"}') \
        == d["timed_needles"]
    for served in ("plain", "recovered"):
        labels = f'{{served="{served}"}}'
        assert moved(fam + "intervals_total", labels) \
            == d["intervals_" + served]
        assert moved(fam + "bytes_total", labels) == d["bytes_" + served]
    assert moved(fam + "index_preads_total") == d["index_preads"] == 0
    for stage, key in zip(("locate", "shard", "assemble"), SECONDS):
        # the gauge is the process's cumulative seconds, as the route's
        assert _family(text1, fam + "stage_seconds")[
            f'{{stage="{stage}"}}'] == pytest.approx(snap1[key], abs=2e-6)
    for family in ("needles_total", "intervals_total", "bytes_total",
                   "index_preads_total", "stage_seconds"):
        assert f"# TYPE {fam}{family} " in text1


def test_admin_route_over_a_served_volume_with_shard_0_deleted(
        sealed, tmp_path, monkeypatch):
    """The deployment's flow in one process: fourteen shards mounted,
    shard 0 deleted through `/admin/ec/delete_shards`, GET every object
    over the volume port; `/admin/ec/read_stats` gives the counters,
    `/metrics` the families, `/admin/ec/recover_stats` keeps its keys."""
    import shutil

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import RpcError, call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    monkeypatch.setenv("WEED_TRACE_SAMPLE", "1")   # every GET is timed
    vs_dir = tmp_path / "vs"
    vs_dir.mkdir()
    for ext in [".ecx", ".vif"] + [reference.shard_ext(s)
                                   for s in range(reference.TOTAL_SHARDS)]:
        shutil.copy(sealed["base"] + ext, vs_dir)
    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    vs = VolumeServer([str(vs_dir)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    try:
        # the server mounted the fourteen shards it found at its start
        call(vs.address, "/admin/ec/delete_shards",
             {"volume": VID, "collection": "", "shard_ids": [0]})
        assert not os.path.exists(str(vs_dir / "1") + reference.shard_ext(0))
        before = call(vs.address, "/admin/ec/read_stats")
        assert list(before) == list(SECONDS) + list(COUNTERS)
        for nid, (cookie, data) in sealed["bodies"].items():
            got = call(vs.address, f"/{VID},{nid:x}{cookie:08x}",
                       parse=False)
            assert got == data, nid
        after = call(vs.address, "/admin/ec/read_stats")
        d = _delta(before, after, COUNTERS)
        plans = [reference_reads.read_plan(offset, length,
                                           sealed["dat_size"], [0])
                 for offset, length in sealed["extents"].values()]
        assert d["needles"] == d["timed_needles"] == len(plans)
        assert d["index_preads"] == 0
        assert d["intervals"] == sum(len(p["intervals"]) for p in plans)
        assert d["intervals_recovered"] == sum(
            len(p["recovered"]) for p in plans) > 0
        for key in SECONDS:
            assert after[key] > before[key], key
        recover = call(vs.address, "/admin/ec/recover_stats")
        assert {"fetch_seconds", "decode_seconds", "serve_seconds",
                "cache_hits", "cache_misses", "coalesced", "device_decodes",
                "device_fallbacks", "volumes", "device"} <= set(recover)
        assert not set(recover) & set(COUNTERS)
        text = call(vs.address, "/metrics", parse=False).decode()
        for family in ("needles_total", "intervals_total", "bytes_total",
                       "index_preads_total", "stage_seconds"):
            assert f"SeaweedFS_volumeServer_ec_read_{family}" in text
        with pytest.raises(RpcError):    # a POST is no such route
            call(vs.address, "/admin/ec/read_stats", payload={})
    finally:
        vs.stop()
        master.stop()
