"""The repair of a sealed volume through `Store.ec_rebuild` with
`ec_encoder_backend="tpu"` (the device pipeline, on the CPU backend here):
what it rebuilds against the files the seal wrote, against
`ops/rs_numpy.py` and against the `.vif` record, for a few loss patterns;
the stage seconds of its reply and the spans behind them; and that the
bytes it reads, uploads, computes and writes are the ones it moved before
it was observed.

Small sizes, seeded: it proves bytes, keys and arithmetic, never a time."""

import json
import os

import numpy as np
import pytest

from seaweedfs_tpu import tracing
from seaweedfs_tpu.ops import crc32c as crc_host
from seaweedfs_tpu.ops.rs_numpy import NumpyEncoder
from seaweedfs_tpu.parallel import batched_encode as be
from seaweedfs_tpu.storage.erasure_coding import TOTAL_SHARDS_COUNT, to_ext
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import VolumeError

VID = 7
# the benchmark's pattern (two data, two parity: a true inverse), one
# all-data, one all-parity (the encode matrix again) and a single loss
LOSSES = [(0, 3, 11, 13), (1, 4, 6, 8), (10, 11, 12, 13), (5,)]
# a key of `stage_stats` -> the `ec.rebuild.*` stage that measures it;
# `read_worker_busy` is thread-seconds inside the read stage's workers,
# taken with bare clock readings: a key and no stage
SPAN_OF = {"read": "read", "read_slot_wait": "stage_wait",
           "read_wait": "read_wait", "dispatch": "dispatch", "h2d": "h2d",
           "d2h_wait": "d2h_wait", "crc": "crc", "write_wait": "write_wait",
           "write": "write"}
STAGES = tuple(SPAN_OF) + ("read_worker_busy",)
# disjoint on the pipeline thread; the read stage's three and the
# writer's `write` overlap them
ON_THE_PIPELINE_THREAD = ("read_wait", "dispatch", "d2h_wait", "crc",
                          "write_wait")
REBUILD_SPANS = {"ec.rebuild." + name for name in SPAN_OF.values()}


def _ids(loss):
    return "lost-" + "-".join(map(str, loss))


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """One volume of ~31 MB (shards of 3 MiB: three batches of a rebuild
    at one unit a batch) sealed through the store on a mesh of one device,
    as the benchmark's configurations pin theirs, and the bytes of its
    fourteen shard files as the seal wrote them."""
    d = tmp_path_factory.mktemp("rebuild_stages")
    mp = pytest.MonkeyPatch()
    mp.setenv("WEED_EC_DEVICE_SHARD", "1")
    store = Store([str(d)], ec_encoder_backend="tpu")
    store.add_volume(VID)
    rng = np.random.default_rng(29)
    for i in range(1, 25):
        n = Needle.create(rng.bytes(1_300_000))
        n.id, n.cookie = i, 0x2900 + i
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


def _lose(sealed, loss):
    for sid in loss:
        os.remove(sealed["base"] + to_ext(sid))


def _rebuild(sealed, loss, **kw) -> dict:
    _lose(sealed, loss)
    stats: dict = {}
    rebuilt = sealed["store"].ec_rebuild(VID, stage_stats=stats, **kw)
    assert rebuilt == sorted(loss)
    return stats


@pytest.fixture(scope="module")
def rebuilds(sealed):
    """Each loss pattern repaired once, in turn, on the one volume."""
    return {loss: _rebuild(sealed, loss) for loss in LOSSES}


@pytest.mark.parametrize("loss", LOSSES, ids=_ids)
def test_rebuilt_files_equal_the_sealed_files_rs_numpy_and_the_vif(
        loss, sealed, rebuilds):
    assert rebuilds[loss]["backend"] == "device-apply-xla"
    files = sealed["files"]
    shards = [None if sid in loss else np.frombuffer(files[sid], np.uint8)
              for sid in range(TOTAL_SHARDS_COUNT)]
    want = NumpyEncoder(10, 4).reconstruct(shards)
    for sid in loss:
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            got = f.read()
        assert got == files[sid], f"shard {sid} differs from the seal's"
        assert got == np.asarray(want[sid]).tobytes(), \
            f"shard {sid} differs from rs_numpy's reconstruction"
        assert crc_host.crc32c(got) == sealed["crcs"][sid]


@pytest.mark.parametrize("loss", LOSSES, ids=_ids)
def test_survivors_are_not_written(loss, sealed, rebuilds):
    for sid in set(range(TOTAL_SHARDS_COUNT)) - set(loss):
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            assert f.read() == sealed["files"][sid]


@pytest.mark.parametrize("loss", LOSSES, ids=_ids)
def test_reply_carries_every_stage_second(loss, rebuilds):
    st = rebuilds[loss]
    for key in STAGES + ("wall",):
        assert isinstance(st[key], float) and st[key] >= 0.0, key
    assert sum(st[k] for k in ON_THE_PIPELINE_THREAD) <= st["wall"]
    assert st["h2d"] <= st["dispatch"]
    # the read stage's coordinator: its fan-outs and its waits for a
    # slot are disjoint too, and its workers are busy inside a fan-out
    assert st["read"] + st["read_slot_wait"] <= st["wall"]
    assert st["read_workers"] == be._read_workers()
    assert 0.0 < st["read_worker_busy"] \
        <= st["read_workers"] * st["read"] + 1e-3
    assert st["missing"] == list(loss)


@pytest.mark.parametrize("loss", LOSSES, ids=_ids)
def test_rebuild_moves_the_bytes_it_moved_before_it_was_observed(
        loss, sealed, rebuilds):
    """`h2d_bytes`, `d2h_bytes` and `batches` as at 6881c23: whole
    batches of `batch_units` chunks of ten survivor rows up, one row a
    lost shard back."""
    st = rebuilds[loss]
    shard_size = len(sealed["files"][0])
    chunk = min(be.MAX_CHUNK_BYTES, shard_size)
    chunks = -(-shard_size // chunk)
    units = min(max(1, be.TARGET_BATCH_BYTES // (10 * chunk)), chunks)
    assert st["batch_units"] == units
    assert st["batches"] == -(-chunks // units)
    assert st["h2d_bytes"] == st["batches"] * units * 10 * chunk
    assert st["d2h_bytes"] == st["batches"] * units * len(loss) * chunk


def test_small_batches_take_the_same_path_batch_after_batch(sealed):
    """Three batches of one unit: the drain of batch n-1 after the
    dispatch of batch n, and the two left at the end."""
    loss = LOSSES[0]
    _lose(sealed, loss)
    stats: dict = {}
    be.rebuild_shards(sealed["base"], batch_units=1, stage_stats=stats)
    assert stats["batches"] == 3 and stats["batch_units"] == 1
    assert sum(stats[k] for k in ON_THE_PIPELINE_THREAD) <= stats["wall"]
    assert stats["read"] + stats["read_slot_wait"] <= stats["wall"]
    for sid in loss:
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            assert f.read() == sealed["files"][sid]


def _stage_names(monkeypatch) -> list:
    names = []
    real = tracing.stage.__init__

    def recording(self, name, *a, **kw):
        names.append(name)
        real(self, name, *a, **kw)

    monkeypatch.setattr(tracing.stage, "__init__", recording)
    return names


def test_stages_are_timed_once_a_batch_never_a_row(sealed, monkeypatch):
    names = _stage_names(monkeypatch)
    _lose(sealed, LOSSES[0])
    stats: dict = {}
    be.rebuild_shards(sealed["base"], batch_units=1, stage_stats=stats)
    n = stats["batches"]
    assert set(names) == REBUILD_SPANS
    # six a batch on the pipeline thread, two on the read stage's
    # coordinator, one on the writer's, and the join at the end
    assert len(names) == 9 * n + 1
    assert names.count("ec.rebuild.write_wait") == n + 1
    for name in REBUILD_SPANS - {"ec.rebuild.write_wait"}:
        assert names.count(name) == n, name


def test_a_one_gib_rebuild_makes_fewer_than_160_stage_calls():
    shard_size = 97 << 20           # the 1,006,723,848 B volume's shards
    chunk = min(be.MAX_CHUNK_BYTES, shard_size)
    units = be.TARGET_BATCH_BYTES // (10 * chunk)
    batches = -(-(shard_size // chunk) // units)
    assert batches == 17 and 9 * batches + 1 < 160 <= 10 * 97


def test_spans_hang_under_a_sampled_request(sealed, monkeypatch):
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "1")
    tracing.RECORDER.reset()
    root = tracing.start("POST /admin/ec/rebuild", service="volume")
    assert root.sampled
    prev = tracing.swap(root)
    try:
        stats = _rebuild(sealed, LOSSES[0])
    finally:
        tracing.restore(prev)
        root.finish()
    agg = tracing.RECORDER.aggregate("ec.rebuild.")
    assert set(agg) == REBUILD_SPANS
    for key, name in SPAN_OF.items():
        # one measurement is the counter and the span
        assert agg["ec.rebuild." + name]["seconds"] == pytest.approx(
            stats[key], abs=2e-6)
    for name in ("read", "stage_wait", "read_wait"):
        assert agg["ec.rebuild." + name]["count"] == stats["batches"]


def test_no_span_is_built_without_a_sampled_request(sealed, monkeypatch):
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    built = []
    real = tracing.Span.__init__

    def counting(self, *a, **kw):
        built.append(a[3] if len(a) > 3 else kw.get("name"))
        real(self, *a, **kw)

    root = tracing.start("POST /admin/ec/rebuild")
    assert not root.sampled
    prev = tracing.swap(root)
    monkeypatch.setattr(tracing.Span, "__init__", counting)
    try:
        stats = _rebuild(sealed, LOSSES[0])
    finally:
        tracing.restore(prev)
    assert built == [] and stats["read"] > 0.0


def test_a_corrupt_survivor_is_refused_not_laundered(sealed):
    """One flipped byte of a survivor: every rebuilt CRC misses the
    `.vif` record and the store raises."""
    loss = LOSSES[0]
    path = sealed["base"] + to_ext(1)
    with open(path, "r+b") as f:
        f.seek(1000)
        byte = f.read(1)
        f.seek(1000)
        f.write(bytes([byte[0] ^ 0x01]))
    try:
        _lose(sealed, loss)
        with pytest.raises(VolumeError, match="do not match"):
            sealed["store"].ec_rebuild(VID)
    finally:
        with open(path, "r+b") as f:
            f.seek(1000)
            f.write(byte)
    _rebuild(sealed, loss)     # deletes what the refused rebuild left
    for sid in loss:
        with open(sealed["base"] + to_ext(sid), "rb") as f:
            assert f.read() == sealed["files"][sid]
