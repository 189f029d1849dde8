"""The read stage of `rebuild_shards` (parallel/batched_encode.py): the
coordinator and I/O workers of `_ReadStage`, which the seal's pipeline
composes too, reading the ten survivors ahead of the pipeline thread.  The
same rebuilt files and CRCs whatever the worker count and the batch size,
for a few loss patterns; a survivor that ends early or a worker that fails
fails the rebuild, leaves no shard file, thread, lease or descriptor
behind; the reply's counters.  Results, never timings: the CPU backend says
nothing about the chip host's files.
"""

import functools
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import crc32c as crc_host
from seaweedfs_tpu.ops.device_pool import get_pool
from seaweedfs_tpu.parallel import batched_encode as be
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.storage.erasure_coding import TOTAL_SHARDS_COUNT, to_ext
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store

VID = 9
# tests/test_ec_rebuild_stages.py's: the benchmark's pattern (two data,
# two parity), one all-data, one all-parity and a single loss
LOSSES = [(0, 3, 11, 13), (1, 4, 6, 8), (10, 11, 12, 13), (5,)]
READ_STAGE_KEYS = ("read", "read_worker_busy", "read_slot_wait",
                   "read_wait")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(loss):
    return "lost-" + "-".join(map(str, loss))


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """One volume of ~31 MB (shards of 3 MiB: at one unit a batch a
    rebuild takes three batches, as many as it has staging slots) sealed
    through the store on a mesh of one device, and its fourteen shard
    files as the seal wrote them."""
    d = tmp_path_factory.mktemp("rebuild_read_pool")
    mp = pytest.MonkeyPatch()
    mp.setenv("WEED_EC_DEVICE_SHARD", "1")
    store = Store([str(d)], ec_encoder_backend="tpu")
    store.add_volume(VID)
    rng = np.random.default_rng(31)
    for i in range(1, 25):
        n = Needle.create(rng.bytes(1_300_000))
        n.id, n.cookie = i, 0x3100 + i
        store.write_needle(VID, n)
    store.ec_generate(VID)
    base = store.find_volume(VID).file_name()
    files = []
    for sid in range(TOTAL_SHARDS_COUNT):
        with open(base + to_ext(sid), "rb") as f:
            files.append(f.read())
    with open(base + ".vif") as f:
        vif = json.load(f)
    yield {"store": store, "base": base, "files": files,
           "crcs": vif["shard_crc32c"]}
    store.close()
    mp.undo()


@pytest.fixture
def whole(sealed):
    """Every test leaves the fourteen files as the seal wrote them."""
    yield
    for sid, data in enumerate(sealed["files"]):
        path = sealed["base"] + to_ext(sid)
        if not os.path.exists(path) or os.path.getsize(path) != len(data):
            with open(path, "wb") as f:
                f.write(data)


def _force_workers(monkeypatch, n: int):
    """Through the constructor's argument, as a caller with its own rule
    for N would; no environment variable reaches it."""
    monkeypatch.setattr(be, "_ReadStage", functools.partial(
        be._ReadStage, read_workers=n))


def _lose(sealed, loss):
    for sid in loss:
        os.remove(sealed["base"] + to_ext(sid))


def _rebuild_threads() -> list[str]:
    return [t.name for t in threading.enumerate()
            if t.name.startswith("ec-rebuild")]


def _open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def _assert_rebuilt(sealed, loss, crcs):
    assert sorted(crcs) == sorted(loss)
    for sid in loss:
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            got = f.read()
        assert got == sealed["files"][sid], f"shard {sid} differs"
        assert crcs[sid] == sealed["crcs"][sid] == crc_host.crc32c(got)


# -- the same bytes whatever N and the batch size ------------------------------

@pytest.mark.parametrize("loss", LOSSES, ids=_ids)
@pytest.mark.parametrize("batch_units", [1, None], ids=["b1", "bdefault"])
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_rebuilt_files_and_crcs_do_not_depend_on_the_worker_count(
        sealed, whole, monkeypatch, eager_switching, workers, batch_units,
        loss):
    _force_workers(monkeypatch, workers)
    _lose(sealed, loss)
    st: dict = {}
    crcs = be.rebuild_shards(sealed["base"], batch_units=batch_units,
                             stage_stats=st)
    _assert_rebuilt(sealed, loss, crcs)
    assert st["read_workers"] == workers
    assert st["batches"] == (3 if batch_units == 1 else 1)
    assert st["missing"] == list(loss)
    assert not _rebuild_threads()


@pytest.mark.parametrize("workers", [1, 4, 32])     # 32: more than cores
def test_the_store_checks_the_rebuilt_crcs_against_the_vif(
        sealed, whole, monkeypatch, eager_switching, workers):
    _force_workers(monkeypatch, workers)
    loss = LOSSES[0]
    _lose(sealed, loss)
    st: dict = {}
    assert sealed["store"].ec_rebuild(VID, stage_stats=st) == sorted(loss)
    assert st["backend"] == "device-apply-xla"
    assert st["read_workers"] == workers
    for sid in loss:
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            assert f.read() == sealed["files"][sid]
    for sid in set(range(TOTAL_SHARDS_COUNT)) - set(loss):
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            assert f.read() == sealed["files"][sid]    # not written


# -- the reply's counters -------------------------------------------------------

@pytest.mark.parametrize("batch_units", [1, None], ids=["b1", "bdefault"])
def test_reply_carries_the_read_stage_s_counters(sealed, whole, batch_units):
    _lose(sealed, LOSSES[0])
    st: dict = {}
    be.rebuild_shards(sealed["base"], batch_units=batch_units,
                      stage_stats=st)
    for key in READ_STAGE_KEYS:
        assert isinstance(st[key], float) and st[key] >= 0.0, key
    assert st["read_workers"] == be._read_workers()
    assert isinstance(st["read_workers"], int)
    # thread-seconds inside a fan-out: at most N workers for its length
    assert 0.0 < st["read_worker_busy"] \
        <= st["read_workers"] * st["read"] + 1e-3
    # the coordinator is in a fan-out or waits for a slot, never both
    assert st["read"] + st["read_slot_wait"] <= st["wall"]
    # the pipeline thread waits for the read stage at least once: the
    # first batch's fill hides behind nothing
    assert 0.0 < st["read_wait"] <= st["wall"]


def test_a_metric_file_can_read_the_counters_as_data(sealed, whole):
    """What a `benchmark` PR's two per-layer files would name
    (`harness_record`, record `rebuild`): the reader that is there reads
    both from a reply of this tree and None from a parent's reply."""
    spec = importlib.util.spec_from_file_location(
        "_harness_record", os.path.join(ROOT, "perfbench", "readers",
                                        "harness_record.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    _lose(sealed, LOSSES[0])
    st: dict = {}
    be.rebuild_shards(sealed["base"], stage_stats=st)
    overlap = {"record": "rebuild", "key": "stage_stats.read_worker_busy",
               "per": "stage_stats.read"}
    slot_wait = {"record": "rebuild", "key": "stage_stats.read_slot_wait",
                 "per": "gib"}
    ctx = {"records": {"rebuild": [{"gib": 0.03, "stage_stats": st}] * 2}}
    assert reader.read(overlap, ctx) == pytest.approx(
        st["read_worker_busy"] / st["read"])
    assert 0.0 < reader.read(overlap, ctx) <= st["read_workers"] + 0.01
    assert reader.read(slot_wait, ctx) == pytest.approx(
        st["read_slot_wait"] / 0.03)
    parent = {k: v for k, v in st.items()
              if k not in ("read_worker_busy", "read_slot_wait",
                           "read_wait", "read_workers")}
    old = {"records": {"rebuild": [{"gib": 0.03, "stage_stats": parent}]}}
    assert reader.read(overlap, old) is None
    assert reader.read(slot_wait, old) is None


def test_three_staging_slots_are_reused_from_rebuild_to_rebuild(
        sealed, whole):
    pool = get_pool()
    _lose(sealed, LOSSES[0])
    be.rebuild_shards(sealed["base"], batch_units=1)
    before = pool.snapshot()
    _lose(sealed, LOSSES[0])
    be.rebuild_shards(sealed["base"], batch_units=1)
    after = pool.snapshot()
    assert be._REBUILD_SLOTS == 3
    assert after["lease_hits"] - before["lease_hits"] == 3
    assert after["allocs"] == before["allocs"]
    assert after["evictions"] == before["evictions"]
    assert after["leased_slots"] == before["leased_slots"]
    # three slots of a 1 GB volume's geometry fit the pool's retention
    assert 3 * 6 * 10 * be.MAX_CHUNK_BYTES <= 256 << 20


# -- failures --------------------------------------------------------------------

def _truncate_under_the_rebuild(monkeypatch, sealed, sid: int, cut: int):
    """Survivor `sid` loses its last `cut` bytes after the rebuild has
    planned from the files' sizes and before it reads a byte."""
    real = mesh_mod.make_sharded_apply
    path = sealed["base"] + to_ext(sid)

    def apply_then_truncate(*a, **kw):
        os.truncate(path, len(sealed["files"][sid]) - cut)
        return real(*a, **kw)

    monkeypatch.setattr(mesh_mod, "make_sharded_apply", apply_then_truncate)


@pytest.mark.parametrize("batch_units", [1, None], ids=["b1", "bdefault"])
@pytest.mark.parametrize("workers", [1, 4])
def test_survivor_truncated_under_the_rebuild_fails_it(
        sealed, whole, monkeypatch, workers, batch_units):
    loss = LOSSES[0]
    _force_workers(monkeypatch, workers)
    _lose(sealed, loss)
    _truncate_under_the_rebuild(monkeypatch, sealed, 5, 4096)
    pool, fds = get_pool(), _open_fds()
    leased = pool.snapshot()["leased_slots"]
    with pytest.raises(OSError, match="shorter than planned"):
        be.rebuild_shards(sealed["base"], batch_units=batch_units)
    # no zero-filled shard where a lost one was, nothing left running,
    # leased or open
    for sid in loss:
        assert not os.path.exists(sealed["base"] + to_ext(sid))
    assert not _rebuild_threads()
    assert pool.snapshot()["leased_slots"] == leased
    assert _open_fds() == fds


def test_store_mounts_nothing_of_a_rebuild_that_failed(sealed, whole,
                                                        monkeypatch):
    loss = LOSSES[0]
    _lose(sealed, loss)
    _truncate_under_the_rebuild(monkeypatch, sealed, 12, 1)
    store = sealed["store"]
    with pytest.raises(OSError, match="shorter than planned"):
        store.ec_rebuild(VID)
    ev = store.find_ec_volume(VID)
    assert ev is None or not set(loss) & set(ev.shards)
    for sid in loss:
        assert not os.path.exists(sealed["base"] + to_ext(sid))


@pytest.mark.parametrize("workers", [1, 4])
def test_failing_worker_stops_the_others_and_reaches_the_caller(
        sealed, whole, monkeypatch, workers):
    loss = LOSSES[1]
    _force_workers(monkeypatch, workers)
    _lose(sealed, loss)
    real, calls = os.preadv, []

    def preadv(fd, bufs, off):
        calls.append(off)
        if len(calls) == 7:
            raise OSError(5, "injected read error")
        return real(fd, bufs, off)

    monkeypatch.setattr(os, "preadv", preadv)
    pool, fds = get_pool(), _open_fds()
    leased = pool.snapshot()["leased_slots"]
    with pytest.raises(OSError, match="injected read error"):
        be.rebuild_shards(sealed["base"], batch_units=1)
    # thirty rows were planned: the others stopped early
    assert len(calls) < 7 + workers + 1
    for sid in loss:
        assert not os.path.exists(sealed["base"] + to_ext(sid))
    assert not _rebuild_threads()
    assert pool.snapshot()["leased_slots"] == leased
    assert _open_fds() == fds


def test_failing_writer_fails_the_rebuild_and_leaves_no_file(
        sealed, whole, monkeypatch):
    loss = LOSSES[2]
    _lose(sealed, loss)
    monkeypatch.setattr(os, "pwritev", lambda fd, bufs, off: 0)
    fds = _open_fds()
    with pytest.raises(OSError, match="no progress"):
        be.rebuild_shards(sealed["base"], batch_units=1)
    for sid in loss:
        assert not os.path.exists(sealed["base"] + to_ext(sid))
    assert not _rebuild_threads()
    assert _open_fds() == fds


def test_a_rebuild_after_a_failed_one_is_whole(sealed, whole, monkeypatch):
    loss = LOSSES[0]
    _lose(sealed, loss)
    with monkeypatch.context() as mp:
        _truncate_under_the_rebuild(mp, sealed, 1, 100)
        with pytest.raises(OSError, match="shorter than planned"):
            be.rebuild_shards(sealed["base"])
    with open(sealed["base"] + to_ext(1), "wb") as f:
        f.write(sealed["files"][1])
    _assert_rebuilt(sealed, loss, be.rebuild_shards(sealed["base"]))
