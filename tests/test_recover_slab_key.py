"""The device slab pool's resident key of a degraded read's decode is the
identity of the survivor spans (a mounted volume's token, each block's
offset and length), never a hash of their bytes: two stacks of different
bytes never share a slab, the same spans decoded for another lost shard
skip the upload, an inline volume passes no key, and nothing on the path
hashes the stack.  `/admin/ec/recover_stats` counts the reuse where the
benchmark's `recover_slab_hit_share` reads it.

The CPU backend at tiny sizes, `WEED_EC_RECOVER_DEVICE=1`: results and
counts, never a time."""

import hashlib
import json
import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

from readers import admin_json  # noqa: E402

from seaweedfs_tpu.ops import codec as codec_mod  # noqa: E402
from seaweedfs_tpu.ops import rs_numpy  # noqa: E402
from seaweedfs_tpu.ops.device_pool import get_pool, reset_pool  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import TOTAL_SHARDS_COUNT  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import ec_volume as ec_volume_mod  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import encoder as enc  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding import recover as recover_mod  # noqa: E402
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (  # noqa: E402
    EcVolume, EcVolumeShard)
from seaweedfs_tpu.storage.needle import Needle  # noqa: E402
from seaweedfs_tpu.storage.volume import Volume  # noqa: E402

LARGE, SMALL = 40000, 400
LOST = (0, 3, 6, 13)
SURVIVORS = tuple(s for s in range(TOTAL_SHARDS_COUNT) if s not in LOST)
BLOCK = 4096
EXTS = [enc.to_ext(s) for s in range(TOTAL_SHARDS_COUNT)] + [".ecx", ".vif"]


def _sealed_volume(directory, seed, vid=1, **block_sizes):
    """A sealed volume of seeded bytes: other seeds, the same shape."""
    os.makedirs(directory, exist_ok=True)
    v = Volume(str(directory), "", vid)
    rng = np.random.default_rng(seed)
    for i in range(1, 121):
        n = Needle.create(rng.integers(0, 256, 3000).astype(
            np.uint8).tobytes(), name=f"f{i}".encode())
        n.id, n.cookie = i, 0x1000 + i
        v.write_needle(n)
    v.sync()
    base = v.file_name()
    v.close()
    enc.write_ec_files(base, **block_sizes)
    enc.write_sorted_file_from_idx(base)
    enc.save_volume_info(base, version=3)
    return base


def _mount(directory, vid=1):
    ev = EcVolume(str(directory), "", vid, large_block_size=LARGE,
                  small_block_size=SMALL)
    for sid in SURVIVORS:
        ev.add_shard(EcVolumeShard(str(directory), "", vid, sid))
    return ev


@pytest.fixture(scope="module")
def volumes(tmp_path_factory):
    """Two sealed volumes of one shape and different bytes."""
    d = tmp_path_factory.mktemp("slab_key")
    sizes = {"large_block_size": LARGE, "small_block_size": SMALL}
    return {k: os.path.dirname(_sealed_volume(d / k, seed, **sizes))
            for k, seed in (("a", 39), ("b", 40))}


@pytest.fixture
def device(monkeypatch):
    monkeypatch.setenv("WEED_EC_RECOVER_DEVICE", "1")
    monkeypatch.setenv("WEED_EC_RECOVER_DEVICE_MIN_KB", "1")
    reset_pool()
    yield get_pool()
    reset_pool()


def _blocks(n):
    """The first `n` blocks of a shard: what a stack of `n` is made of."""
    return [(i * BLOCK, BLOCK) for i in range(n)]


def _recover(ev, target, blocks):
    """`_recover_block` of every block, as ONE decode batch: a stack of
    one is a plain call; for more the batcher's key is held busy until
    every caller has queued, and the test then leads their batch."""
    if len(blocks) == 1:
        return [ev._recover_block(target, *blocks[0])]
    batcher = ev._recover_batcher
    key = (SURVIVORS, target)
    outs = [None] * len(blocks)
    with batcher._lock:
        batcher._busy.add(key)

    def follow(i):
        outs[i] = ev._recover_block(target, *blocks[i])

    threads = []
    for i in range(len(blocks)):      # one at a time: the stack's order
        th = threading.Thread(target=follow, args=(i,))
        th.start()
        threads.append(th)
        deadline = time.monotonic() + 10
        while len(batcher._queues.get(key, ())) <= i:
            assert time.monotonic() < deadline, "the follower never queued"
            time.sleep(0.001)
    with batcher._lock:
        batch = batcher._queues.pop(key)
        batcher._busy.discard(key)
    batcher._decode_batch(SURVIVORS, target, batch)
    for th in threads:
        th.join(10)
    return outs


def _host(directory, target, blocks, vid=1):
    """The host codec over the shard files: the same decode rows on the
    same survivor spans, by `rs_numpy.gf_apply_matrix`."""
    rows = codec_mod.decode_rows(10, TOTAL_SHARDS_COUNT, SURVIVORS,
                                 (target,))
    outs = []
    for offset, size in blocks:
        spans = []
        for sid in SURVIVORS:
            with open(os.path.join(str(directory), str(vid))
                      + enc.to_ext(sid), "rb") as f:
                f.seek(offset)
                spans.append(np.frombuffer(f.read(size), dtype=np.uint8))
        outs.append(rs_numpy.gf_apply_matrix(
            rows, np.stack(spans))[0].tobytes())
    return outs


def _pool_counts(pool):
    snap = pool.snapshot()
    return snap["resident_hits"], snap["resident_misses"], snap["h2d_bytes"]


def _case_two_volumes(volumes, n, tmp_path, monkeypatch, pool):
    """(a) two volumes of different bytes decode the same (survivors,
    offset, size): each its own bytes, each an upload."""
    blocks = _blocks(n)
    want = {k: _host(volumes[k], 0, blocks) for k in "ab"}
    assert want["a"] != want["b"]
    for k in "ab":
        ev = _mount(volumes[k])
        hits, misses, _ = _pool_counts(pool)
        try:
            assert _recover(ev, 0, blocks) == want[k]
        finally:
            ev.close()
        assert _pool_counts(pool)[:2] == (hits, misses + 1)


def _case_remounted(volumes, n, tmp_path, monkeypatch, pool):
    """(a) one volume closed and mounted again over changed shard files:
    the new bytes, and an upload again."""
    d = tmp_path / "v"
    shutil.copytree(volumes["a"], d)
    blocks = _blocks(n)
    want_a = _host(volumes["a"], 0, blocks)
    want_b = _host(volumes["b"], 0, blocks)
    ev = _mount(d)
    assert _recover(ev, 0, blocks) == want_a
    ev.close()
    for ext in EXTS:
        shutil.copy(os.path.join(volumes["b"], "1") + ext, d)
    hits, misses, _ = _pool_counts(pool)
    ev = _mount(d)
    try:
        assert _recover(ev, 0, blocks) == want_b
        assert _pool_counts(pool)[:2] == (hits, misses + 1)
        # a shard mounted again (a rebuilt one) names the spans anew too
        shard = ev.delete_shard(1)
        ev.add_shard(shard)
        assert _recover(ev, 3, blocks) == _host(volumes["b"], 3, blocks)
        assert _pool_counts(pool)[:2] == (hits, misses + 2)
    finally:
        ev.close()


def _case_second_lost_shard(volumes, n, tmp_path, monkeypatch, pool):
    """(b) the same spans decoded for a second lost shard: one resident
    hit, no byte uploaded, both outputs the host codec's."""
    from seaweedfs_tpu.stats import metrics as stats

    def h2d_total():
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in stats.REGISTRY.expose().splitlines()
                   if line.startswith(
                       "SeaweedFS_volumeServer_ec_device_h2d_bytes_total"))

    blocks = _blocks(n)
    ev = _mount(volumes["a"])
    try:
        assert _recover(ev, 0, blocks) == _host(volumes["a"], 0, blocks)
        hits, misses, h2d = _pool_counts(pool)
        assert h2d >= 10 * n * BLOCK
        prom = h2d_total()
        assert prom >= 10 * n * BLOCK
        assert _recover(ev, 3, blocks) == _host(volumes["a"], 3, blocks)
        assert _pool_counts(pool) == (hits + 1, misses, h2d)
        assert h2d_total() == prom
    finally:
        ev.close()


def _case_inline_volume(volumes, n, tmp_path, monkeypatch, pool):
    """(c) a volume whose shard bytes can still change (a `tail_reader`
    set) decodes with `slab_key=None`: the plain upload, no slab."""
    keys = []
    real = codec_mod.reconstruct_span

    def spy(*a, **kw):
        keys.append(kw["slab_key"])
        return real(*a, **kw)

    monkeypatch.setattr(codec_mod, "reconstruct_span", spy)
    blocks = _blocks(n)
    ev = _mount(volumes["a"])
    try:
        ev.tail_reader = lambda sid, offset, size: None
        before = _pool_counts(pool)
        assert _recover(ev, 0, blocks) == _host(volumes["a"], 0, blocks)
        assert keys == [None]
        assert _pool_counts(pool) == before
        # ... and the sealed volume beside it names its stack
        del ev.tail_reader
        assert _recover(ev, 3, blocks) == _host(volumes["a"], 3, blocks)
        token = ev._slab_token
        assert keys[1] == tuple((token, *b) for b in blocks)
    finally:
        ev.close()


def _case_nothing_hashes_the_stack(volumes, n, tmp_path, monkeypatch, pool):
    """(d) no `hashlib.blake2b` on the decode path."""
    def no_hash(*a, **kw):
        raise AssertionError("blake2b on the decode path")

    monkeypatch.setattr(hashlib, "blake2b", no_hash)
    assert not hasattr(ec_volume_mod, "hashlib")
    blocks = _blocks(n)
    ev = _mount(volumes["a"])
    try:
        hits, misses, _ = _pool_counts(pool)
        assert _recover(ev, 0, blocks) == _host(volumes["a"], 0, blocks)
        assert _recover(ev, 6, blocks) == _host(volumes["a"], 6, blocks)
        assert _pool_counts(pool)[:2] == (hits + 1, misses + 1)
    finally:
        ev.close()


@pytest.mark.parametrize("n_blocks", [1, 2], ids=["stack1", "stack2"])
@pytest.mark.parametrize("case", [
    _case_two_volumes, _case_remounted, _case_second_lost_shard,
    _case_inline_volume, _case_nothing_hashes_the_stack],
    ids=lambda f: f.__name__[6:])
def test_slab_key_is_the_identity_of_the_survivor_spans(
        case, n_blocks, volumes, tmp_path, monkeypatch, device):
    before = recover_mod.STATS.snapshot()
    case(volumes, n_blocks, tmp_path, monkeypatch, device)
    after = recover_mod.STATS.snapshot()
    # every decode of the case was one batch of n_blocks, on the device
    batches = after["decode_batches"] - before["decode_batches"]
    assert batches >= 2
    assert after["decode_blocks"] - before["decode_blocks"] \
        == batches * n_blocks
    assert after["device_decodes"] - before["device_decodes"] == batches
    assert after["device_fallbacks"] == before["device_fallbacks"]


def test_the_batcher_hands_the_hook_its_members_identities_in_stack_order():
    seen = []

    def hook(survivors, target, stacked, idents):
        seen.append((stacked.shape[1], idents))
        return stacked[0].copy()

    batcher = recover_mod.SpanDecodeBatcher(hook, recover_mod.RecoverStats())
    inputs = np.zeros((10, 8), dtype=np.uint8)
    batcher.decode((1,), 0, inputs, ("t", 0, 8))
    batcher.decode((1,), 0, inputs)
    reqs = [recover_mod._DecodeReq(inputs, ident)
            for ident in (("t", 8, 8), ("t", 0, 8))]
    batcher._decode_batch((1,), 0, reqs)
    reqs = [recover_mod._DecodeReq(inputs, ident)
            for ident in (("t", 8, 8), None)]
    batcher._decode_batch((1,), 0, reqs)
    assert seen == [(8, (("t", 0, 8),)), (8, None),
                    (16, (("t", 8, 8), ("t", 0, 8))), (16, None)]


def test_tokens_are_taken_from_a_counter_once_a_mount(volumes):
    ev1 = _mount(volumes["a"])
    t1 = ev1._slab_token
    ev1.close()
    ev2 = _mount(volumes["a"])
    ev3 = _mount(volumes["b"])
    try:
        assert t1 < ev2._slab_token < ev3._slab_token
        assert isinstance(t1, int)
    finally:
        ev2.close()
        ev3.close()


# -- the counter the benchmark reads ------------------------------------------

def test_recover_slab_hit_share_reads_the_share_and_nothing_from_a_parent():
    with open(os.path.join(ROOT, "perfbench", "layer_metrics",
                           "recover_slab_hit_share.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == {
        "kind": "admin_json", "path": "/admin/ec/recover_stats",
        "key": "decode_slab_hits", "per": "decode_batches", "scale": 100}
    path = spec["reader"]["path"]
    before = {"decode_batches": 10, "decode_slab_hits": 4,
              "decode_slab_uploads": 6}
    after = {"decode_batches": 110, "decode_slab_hits": 19,
             "decode_slab_uploads": 91}
    assert admin_json.read(spec["reader"], {"admin": {path: (before, after)}}) \
        == pytest.approx(15.0)
    # a parent's reply nests the pool's counts and has no such key
    parent = ({"decode_batches": 10, "device_pool": {"resident_hits": 4}},
              {"decode_batches": 110, "device_pool": {"resident_hits": 19}})
    assert admin_json.read(spec["reader"], {"admin": {path: parent}}) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        # PR 39's one entry; later PRs append theirs behind it
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == "recover_slab_hit_share"]
    assert entry == {"name": "recover_slab_hit_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "Host-device link", "moves": "op_p50_ms",
                     "workloads": ["degraded-get"]}
    assert {k: spec[k] for k in entry if k != "better"} \
        == {k: v for k, v in entry.items() if k != "better"}


def test_admin_route_carries_the_pool_s_resident_counts(tmp_path, device,
                                                        monkeypatch):
    """`/admin/ec/recover_stats` of a served volume without shards 0, 3,
    6, 13, its LRU off so that a block is recovered again and again:
    `decode_slab_hits` / `decode_slab_uploads` are the pool's
    `resident_hits` / `resident_misses`, and together the device's
    decode batches."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    monkeypatch.setenv("WEED_EC_RECOVER_CACHE_MB", "0")
    monkeypatch.setenv("WEED_EC_RECOVER_BLOCK_KB", "4")
    vs_dir = tmp_path / "vs"
    base = _sealed_volume(vs_dir, seed=41)     # the server's block sizes
    for ext in [".dat", ".idx"] + [enc.to_ext(s) for s in LOST]:
        os.remove(base + ext)
    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    vs = VolumeServer([str(vs_dir)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    try:
        before = call(vs.address, "/admin/ec/recover_stats")
        assert before["decode_slab_hits"] == before["decode_slab_uploads"] \
            == 0
        for nid in range(1, 121):
            call(vs.address, f"/1,{nid:x}{0x1000 + nid:08x}", parse=False)
        after = call(vs.address, "/admin/ec/recover_stats")
        pool = after["device_pool"]
        assert after["decode_slab_hits"] == pool["resident_hits"] > 0
        assert after["decode_slab_uploads"] == pool["resident_misses"] > 0
        assert after["decode_slab_hits"] + after["decode_slab_uploads"] \
            == after["device_decodes"] - before["device_decodes"]
    finally:
        vs.stop()
        master.stop()
