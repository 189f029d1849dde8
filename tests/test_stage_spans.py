"""Stage spans of the two device EC paths (tracing.stage): the counter
each feeds, the names the benchmark's metric files read, the host events a
profiler session sees, and the kernels' names.

Everything runs on the CPU backend at tiny sizes: it proves the keys, the
names and the arithmetic between the counters, never a time."""

import glob
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import tracing
from seaweedfs_tpu.parallel import batched_encode as be
from seaweedfs_tpu.stats import metrics as stats
from seaweedfs_tpu.storage.erasure_coding import TOTAL_SHARDS_COUNT
from seaweedfs_tpu.storage.erasure_coding import encoder as enc
from seaweedfs_tpu.storage.erasure_coding import recover as recover_mod
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (READ_STATS,
                                                            EcVolume,
                                                            EcVolumeShard)
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_map import load_needle_map_from_idx
from seaweedfs_tpu.storage.volume import Volume

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYER_METRICS = os.path.join(ROOT, "perfbench", "layer_metrics")
LARGE, SMALL = 40000, 400
LOST = (0, 3, 6, 13)

ENCODE_SPANS = {"ec.encode.read", "ec.encode.stage_wait", "ec.encode.h2d",
                "ec.encode.dispatch", "ec.encode.d2h_wait", "ec.encode.crc",
                "ec.encode.write"}
HOST_ENCODE_SPANS = {"ec.encode.read", "ec.encode.crc", "ec.encode.write"}
RECOVER_SPANS = {"ec.recover.fetch", "ec.recover.decode.queue",
                 "ec.recover.decode", "ec.recover.decode.stack",
                 "ec.recover.decode.h2d", "ec.recover.decode.apply",
                 "ec.recover.serve"}
# the sealed read's own (PR 36): a degraded read is a sealed read first
READ_SPANS = {"ec.read.locate", "ec.read.shard", "ec.read.assemble"}
REBUILD_LOST = (0, 3, 11, 13)
NEW_ENCODE_KEYS = ("read_dat", "read_data_write", "read_slot_wait", "h2d",
                   "d2h_wait", "crc_host", "read_worker_busy")
OLD_ENCODE_KEYS = ("read", "dispatch", "encode_crc", "write", "wall")
NEW_RECOVER_KEYS = ("decode_queue_seconds", "decode_stack_seconds",
                    "decode_h2d_seconds", "decode_apply_seconds",
                    "decode_batches", "decode_blocks")
OLD_RECOVER_KEYS = ("fetch_seconds", "decode_seconds", "serve_seconds",
                    "cache_misses")


def _make_volume(directory, vid=1, count=120, data_size=3000):
    v = Volume(str(directory), "", vid)
    rng = np.random.default_rng(vid)
    for i in range(1, count + 1):
        n = Needle.create(rng.integers(0, 256, data_size).astype(
            np.uint8).tobytes(), name=f"f{i}".encode())
        n.id, n.cookie = i, 0x1000 + i
        v.write_needle(n)
    v.sync()
    base = v.file_name()
    v.close()
    return base


def _seal(base, **kw) -> dict:
    st: dict = {}
    be.encode_volumes([base], large_block=LARGE, small_block=SMALL,
                      stage_stats=st, **kw)
    return st


def _degraded_volume(directory, vid=1):
    ev = EcVolume(str(directory), "", vid, large_block_size=LARGE,
                  small_block_size=SMALL)
    for i in range(TOTAL_SHARDS_COUNT):
        if i not in LOST:
            ev.add_shard(EcVolumeShard(str(directory), "", vid, i))
    return ev


def _read_all(ev, base, limit=None):
    nm = load_needle_map_from_idx(base + ".idx")
    n = 0
    for nid, nv in nm.items_ascending():
        if nv.size < 0:
            continue
        assert ev.read_needle(nid).id == nid   # CRC verified inside
        n += 1
        if limit and n >= limit:
            break
    return n


def _stack_counts() -> dict:
    """{blocks: batches} of ec_recover_decode_stack_total right now."""
    text = stats.REGISTRY.expose()
    out = {}
    for m in re.finditer(
            r'^SeaweedFS_volumeServer_ec_recover_decode_stack_total'
            r'\{blocks="(\d+)"\} (\S+)$', text, re.M):
        out[int(m.group(1))] = float(m.group(2))
    return out


@pytest.fixture(scope="module")
def paths(tmp_path_factory):
    """One CPU seal through the device pipeline and one CPU degraded
    read through the device decode, once for the module: the seal's
    `stage_stats`, the recover stats before and after, the stack counter
    before and after; then one CPU rebuild of four of its shard files
    through the device pipeline, and its `stage_stats`."""
    d = tmp_path_factory.mktemp("stage_spans")
    mp = pytest.MonkeyPatch()
    mp.setenv("WEED_EC_RECOVER_DEVICE", "1")
    mp.setenv("WEED_EC_RECOVER_DEVICE_MIN_KB", "1")
    mp.setenv("WEED_EC_RECOVER_BLOCK_KB", "4")
    try:
        base = _make_volume(d)
        stage_stats = _seal(base)
        enc.write_sorted_file_from_idx(base)
        before = recover_mod.STATS.snapshot()
        stacks_before = _stack_counts()
        ev = _degraded_volume(d)
        reads = _read_all(ev, base)
        ev.close()
        after = recover_mod.STATS.snapshot()
        read_stats = READ_STATS.snapshot()
        stacks_after = _stack_counts()
        for sid in REBUILD_LOST:
            os.remove(base + enc.to_ext(sid))
        rebuild_stats: dict = {}
        be.rebuild_shards(base, stage_stats=rebuild_stats)
    finally:
        mp.undo()
    assert reads > 50
    return {"dir": d, "base": base, "stage_stats": stage_stats,
            "rebuild_stats": rebuild_stats, "read_stats": read_stats,
            "recover_before": before, "recover_after": after,
            "stacks_before": stacks_before, "stacks_after": stacks_after}


def _metric_files():
    out = []
    for path in sorted(glob.glob(os.path.join(LAYER_METRICS, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"]["kind"] in ("harness_record", "admin_json",
                                      "prometheus", "prometheus_value"):
            out.append(spec)
    return out


def _one_timed_object_request(method: str):
    """One sampled request to the object route (label `*`) of a server
    under the volume server's name, and the scrape's export."""
    from seaweedfs_tpu.rpc import http_rpc

    srv = http_rpc.RpcServer(service_name="volume")
    srv.default_route = lambda method, req: b"x"
    srv.start()
    try:
        http_rpc.call(srv.address, "/3,0101020304", method=method,
                      headers={tracing.TRACE_HEADER: "f" * 16,
                               tracing.SAMPLED_HEADER: "1"}, parse=False)
        while not http_rpc.REQUEST_STAGES.snapshot()[
                ("volume", "*", method)]["timed_requests"]:
            time.sleep(0.005)
        http_rpc.REQUEST_STAGES.export()
    finally:
        srv.stop()


# -- (a) every key a metric file names is there -------------------------------

@pytest.mark.parametrize("spec", _metric_files(), ids=lambda s: s["name"])
def test_key_named_by_a_metric_file_is_present(spec, paths, monkeypatch,
                                               tmp_path):
    """A renamed key of `stage_stats`, of /admin/ec/recover_stats or a
    renamed family fails here, on the CPU, and not in a chip run."""
    reader = spec["reader"]
    kind = reader["kind"]
    if kind == "harness_record":
        # the replies a driver keeps: a seal's or a rebuild's, or the
        # window's delta of /admin/ec/read_stats
        section, got = {
            "seal": ("stage_stats", paths["stage_stats"]),
            "rebuild": ("stage_stats", paths["rebuild_stats"]),
            "sealed_read": ("read_stats", paths["read_stats"]),
        }[reader["record"]]
        for dotted in (reader["key"], reader["per"]):
            # the driver's own, not the program's: a size, a unit count;
            # or the rebuild handler's, not the pipeline's: the reads it
            # served meanwhile (test_reads_under_rebuild holds the reply)
            if dotted in ("gib", "windows", "rebuilds",
                          "stage_stats.foreground_reads"):
                continue
            assert dotted.partition(".")[0] == section
            key = dotted.partition(".")[2]
            assert key in got, sorted(got)
            assert isinstance(got[key], float if section == "stage_stats"
                              or key.endswith("_seconds") else int)
    elif kind == "admin_json":
        assert reader["path"] == "/admin/ec/recover_stats"
        after = paths["recover_after"]
        for key in (reader["key"], reader.get("per", reader["key"])):
            assert key in after, sorted(after)
            assert isinstance(after[key], (int, float))
    else:
        if reader["family"].endswith("startup_seconds"):
            # recorded at this process's first jax.devices(): ask again
            from seaweedfs_tpu.util import platform as platform_util

            monkeypatch.setattr(platform_util, "_cache", {})
            assert platform_util.device_info()["platform"] == "cpu"
        labels = reader.get("labels") or {}
        if reader["family"].endswith("request_seconds"):
            stats.VolumeServerRequestHistogram.labels(
                labels["type"]).observe(0.001)
        if reader["family"].startswith("SeaweedFS_rpc_server_"):
            _one_timed_object_request(labels["method"])
        if reader["family"].endswith("volumeServer_replicate_total"):
            # there from a volume server's start, before any write
            from seaweedfs_tpu.volume_server.server import VolumeServer

            vs = VolumeServer([str(tmp_path)], "127.0.0.1:1", port=0)
            vs.start()
            vs.stop()
        if reader["family"].endswith("profiler_gil_wait_seconds"):
            from seaweedfs_tpu import profiling

            sampler = profiling.StackSampler(hz=200, publish=True)
            sampler.start()
            while not sampler.gil_count:
                time.sleep(0.005)
            sampler.stop()
        text = stats.REGISTRY.expose()
        pat = re.compile(r"^" + re.escape(reader["family"])
                         + r"(_sum|_count)?(\{[^}]*\})? \S+$", re.M)
        samples = [m.group(0) for m in pat.finditer(text)
                   if all(f'{k}="{v}"' in m.group(0)
                          for k, v in labels.items())]
        assert samples, f"no sample of {reader['family']} {labels}"


@pytest.mark.parametrize("key", OLD_ENCODE_KEYS + NEW_ENCODE_KEYS)
def test_stage_stats_key_after_a_cpu_seal(key, paths):
    value = paths["stage_stats"][key]
    assert isinstance(value, float) and value >= 0.0
    # the gauge carries every stage of the last seal, and its wall
    assert f'ec_encode_stage_seconds{{stage="{key}"}}' in \
        stats.REGISTRY.expose()


@pytest.mark.parametrize("key", OLD_RECOVER_KEYS + NEW_RECOVER_KEYS)
def test_recover_stats_key_after_a_cpu_degraded_read(key, paths):
    before, after = paths["recover_before"], paths["recover_after"]
    assert key in after
    assert after[key] >= before.get(key, 0)
    if key != "decode_queue_seconds":    # one caller: nobody queued
        assert after[key] > before.get(key, 0), key


# -- (b) the arithmetic between the counters ----------------------------------

def test_read_splits_into_dat_read_and_data_write(paths):
    # thread-seconds over the read stage's workers against its own wall
    st = paths["stage_stats"]
    cap = st["read"] * st["read_workers"] + 0.002
    assert st["read_dat"] + st["read_data_write"] <= cap
    assert st["read_worker_busy"] <= cap
    assert st["read_dat"] > 0 or st["read"] < 0.002


def test_encode_crc_holds_d2h_wait_and_crc_host(paths):
    st = paths["stage_stats"]
    assert st["d2h_wait"] + st["crc_host"] <= st["encode_crc"] + 0.002


def test_dispatch_holds_h2d(paths):
    st = paths["stage_stats"]
    assert st["h2d"] <= st["dispatch"] + 0.002


def test_decode_holds_stack_h2d_and_apply(paths):
    d = {k: paths["recover_after"][k] - paths["recover_before"].get(k, 0)
         for k in ("decode_seconds", "decode_stack_seconds",
                   "decode_h2d_seconds", "decode_apply_seconds")}
    inner = (d["decode_stack_seconds"] + d["decode_h2d_seconds"]
             + d["decode_apply_seconds"])
    assert 0 < inner <= d["decode_seconds"] + 0.001   # 3-decimal reply


def test_stack_counter_sums_to_decode_blocks(paths):
    b, a = paths["stacks_before"], paths["stacks_after"]
    blocks = sum(n * (a[n] - b.get(n, 0)) for n in a)
    batches = sum(a[n] - b.get(n, 0) for n in a)
    d = {k: paths["recover_after"][k] - paths["recover_before"].get(k, 0)
         for k in ("decode_blocks", "decode_batches", "spans", "batches")}
    assert blocks == d["decode_blocks"] == d["spans"] > 0
    assert batches == d["decode_batches"] == d["batches"] > 0


def test_new_recover_seconds_keep_microseconds():
    s = recover_mod.RecoverStats()
    s.add_stage("decode_h2d", 0.0000123)
    s.add_stage("decode", 0.0004)
    snap = s.snapshot()
    assert snap["decode_h2d_seconds"] == pytest.approx(0.000012, abs=1e-9)
    assert snap["decode_seconds"] == 0.0     # today's keys: 3 decimals


# -- (c) the accepted trace patterns still find the three functions -----------

def _ec_mesh(n: int):
    import jax

    from seaweedfs_tpu.parallel import mesh as mesh_mod

    return mesh_mod.make_ec_mesh(devices=jax.devices()[:n])


def _patterns(metric: str) -> list:
    with open(os.path.join(LAYER_METRICS, metric + ".json")) as f:
        return [re.compile(p) for p in json.load(f)["reader"]["patterns"]]


def _matches(metric: str, fn) -> bool:
    name = "jit_" + fn.__name__ + "("
    return any(p.search(name) for p in _patterns(metric))


def test_roofline_pattern_matches_the_one_chip_step():
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    mesh = _ec_mesh(1)
    for words in (False, True):
        step = mesh_mod.make_sharded_encoder(mesh, words=words)
        assert step.__name__ == "step"
        assert _matches("encode_kernel_roofline", step)


@pytest.mark.parametrize("devices", [1, 4])
def test_roofline_pattern_matches_the_pooled_fused_step(devices):
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    step = mesh_mod.make_parity_step(_ec_mesh(devices), fused_crc=True)
    assert step.__name__ == "_fused"
    assert _matches("encode_kernel_roofline", step)


def test_recover_kernel_pattern_matches_apply_pallas():
    from seaweedfs_tpu.ops import rs_pallas

    assert rs_pallas._apply_pallas.__name__ == "_apply_pallas"
    assert _matches("recover_kernel_us", rs_pallas._apply_pallas)


@pytest.mark.parametrize("scope,build", [
    ("ec.encode.step", "step"), ("ec.encode.fused", "fused"),
    ("ec.encode.fused_words", "fused_words"),
    ("ec.recover.apply", "apply"), ("ec.crc32c", "fused"),
    ("ec.rebuild.apply", "rebuild")])
def test_kernel_scope_names_are_in_the_lowered_program(scope, build):
    """The scope is what a trace shows whatever the function is called:
    it has to reach the compiled program's metadata."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf256, rs_pallas
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    matrix = gf256.parity_matrix(10, 14)
    if build == "step":
        step = mesh_mod.make_sharded_encoder(_ec_mesh(1))
        lowered = step.lower(jax.ShapeDtypeStruct((1, 10, 512), jnp.uint8))
    elif build == "fused":
        step = mesh_mod.make_parity_step(_ec_mesh(1), fused_crc=True)
        lowered = step.lower(jax.ShapeDtypeStruct((10, 1, 128), jnp.int32),
                             jax.ShapeDtypeStruct((4, 1, 128), jnp.int32))
    elif build == "rebuild":
        from seaweedfs_tpu.parallel.batched_encode import rebuild_matrix

        _, m = rebuild_matrix([1, 2, 4, 5, 6, 7, 8, 9, 10, 12],
                              list(REBUILD_LOST))
        step = mesh_mod.make_sharded_apply(_ec_mesh(1), m)
        lowered = step.lower(jax.ShapeDtypeStruct((1, 10, 512), jnp.uint8))
    elif build == "fused_words":
        lowered = jax.jit(lambda w: rs_pallas.fused_encode_words(
            matrix, w, interpret=True)).lower(
                jax.ShapeDtypeStruct((1, 10, 512), jnp.int32))
    else:
        lowered = jax.jit(lambda d: rs_pallas.apply_matrix_pallas(
            matrix, d, interpret=True)).lower(
                jax.ShapeDtypeStruct((10, 2048), jnp.uint8))
    text = lowered.as_text(debug_info=True)
    assert scope in text, f"{scope} not in the lowered program's locations"


# -- (d) what a profiler session sees -----------------------------------------

def _start_trace(logdir):
    """A session as the benchmark's helper starts it: host TraceMe events
    and device planes, no Python tracer (which hooks every call of every
    thread and would slow the tests that run after this one)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=options)


def _host_event_names(logdir) -> set:
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb")))
    assert found, "the profiler left no .xplane.pb"
    names = set()
    for plane in ProfileData.from_file(found[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ec."):
                    names.add(e.name)
    return names


def _queued_decode():
    """Two requests of one key through the batcher, the second arriving
    while the first decodes: a leader, and a follower that waits."""
    batcher = None
    entered = threading.Event()

    def slow_decode(survivors, target, stacked, idents):
        entered.set()
        deadline = time.monotonic() + 5
        while not batcher._queues and time.monotonic() < deadline:
            time.sleep(0.002)          # until the follower has queued
        time.sleep(0.01)
        return stacked[0].copy()

    stats_ = recover_mod.RecoverStats()
    batcher = recover_mod.SpanDecodeBatcher(slow_decode, stats_)
    inputs = np.zeros((10, 64), dtype=np.uint8)
    outs = []
    leader = threading.Thread(
        target=lambda: outs.append(batcher.decode((1,), 0, inputs)))
    t0 = time.perf_counter()
    leader.start()
    assert entered.wait(5)
    outs.append(batcher.decode((1,), 0, inputs))     # the follower
    leader.join(5)
    assert len(outs) == 2
    return stats_.snapshot(), time.perf_counter() - t0


def test_queue_wait_is_counted_for_the_follower_only():
    snap, around = _queued_decode()
    assert snap["decode_batches"] == 2 and snap["decode_blocks"] == 2
    # a floor from the leader's own sleep, and a wait that lies inside
    # the bracket this test put around both decodes
    assert 0.005 < snap["decode_queue_seconds"] <= around
    # the wait lies outside the decode stage: it ends where a batch starts
    assert snap["decode_seconds"] >= 0.02


def test_profiler_session_sees_exactly_the_stage_names(tmp_path,
                                                       monkeypatch):
    import jax

    monkeypatch.setenv("WEED_EC_RECOVER_DEVICE", "1")
    monkeypatch.setenv("WEED_EC_RECOVER_DEVICE_MIN_KB", "1")
    monkeypatch.setenv("WEED_EC_RECOVER_BLOCK_KB", "4")
    base = _make_volume(tmp_path, count=40)
    logdir = tmp_path / "trace"
    _start_trace(logdir)
    try:
        _seal(base)
        enc.write_sorted_file_from_idx(base)
        ev = _degraded_volume(tmp_path)
        _read_all(ev, base, limit=5)
        ev.close()
        _queued_decode()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(logdir)
    assert names == ENCODE_SPANS | RECOVER_SPANS | READ_SPANS, sorted(
        names ^ (ENCODE_SPANS | RECOVER_SPANS | READ_SPANS))


def test_host_pipeline_uses_the_same_span_names(tmp_path):
    import jax

    base = _make_volume(tmp_path, count=40)
    logdir = tmp_path / "trace"
    _start_trace(logdir)
    try:
        st = _seal(base, host_codec=True)
    finally:
        jax.profiler.stop_trace()
    assert st["backend"] == "host-pipeline"
    assert _host_event_names(logdir) == HOST_ENCODE_SPANS


class _FakeAnnotation:
    built = 0
    enabled = False

    def __init__(self, name):
        type(self).built += 1

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("session_on", [False, True])
def test_trace_annotation_is_built_only_in_a_session(tmp_path, monkeypatch,
                                                     session_on):
    fake = type("Fake", (_FakeAnnotation,), {"built": 0,
                                             "enabled": session_on})
    monkeypatch.setattr(tracing, "_trace_annotation", fake)
    _seal(_make_volume(tmp_path, count=20))
    assert (fake.built > 0) is session_on


# -- the span itself ----------------------------------------------------------

def test_stage_adds_the_same_seconds_to_counter_and_span(monkeypatch):
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "1")
    tracing.RECORDER.reset()
    got = {}
    root = tracing.start("root", service="volume")
    prev = tracing.swap(root)
    try:
        with tracing.stage("ec.encode.read", got.__setitem__, "read", 3,
                           4096) as st:
            inner = tracing.current()
            time.sleep(0.002)
    finally:
        tracing.restore(prev)
        root.finish()
    assert inner.name == "ec.encode.read" and inner.parent_id == root.span_id
    assert inner.trace_id == root.trace_id
    assert inner.tags == {"n": 3, "bytes": 4096}
    assert got["read"] == st.seconds == inner.duration >= 0.002
    assert tracing.current() is prev


def test_stage_without_a_sampled_span_builds_no_span(monkeypatch):
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    built = []
    real = tracing.Span.__init__

    def counting(self, *a, **kw):
        built.append(a[3] if len(a) > 3 else kw.get("name"))
        real(self, *a, **kw)

    got = {}
    root = tracing.start("root")          # unsampled
    assert not root.sampled
    prev = tracing.swap(root)
    monkeypatch.setattr(tracing.Span, "__init__", counting)
    try:
        for _ in range(3):
            with tracing.stage("ec.encode.write", got.__setitem__, "write"):
                assert tracing.current() is root
        tracing.restore(None)
        with tracing.stage("ec.encode.write", got.__setitem__, "write"):
            assert tracing.current() is None
    finally:
        tracing.restore(prev)
    assert built == [] and got["write"] >= 0.0


def test_stage_propagates_the_exception_and_still_counts():
    got = {}
    with pytest.raises(ValueError):
        with tracing.stage("ec.recover.fetch", got.__setitem__, "fetch"):
            raise ValueError("survivor gone")
    assert got["fetch"] >= 0.0


def test_stage_does_not_import_jax():
    """(g) a daemon that never touches the device must not pay for jax
    because it timed a stage."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from seaweedfs_tpu import tracing\n"
        "acc = {}\n"
        "for _ in range(3):\n"
        "    with tracing.stage('ec.encode.read', acc.__setitem__, 'read'):\n"
        "        pass\n"
        "assert 'read' in acc\n"
        "assert 'jax' not in sys.modules, 'stage() imported jax'\n"
        "assert tracing._trace_annotation is None\n"
        "print('ok')\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


def test_a_one_gib_seal_makes_fewer_than_300_stage_calls(tmp_path):
    """One span a unit and six a batch; never one a row."""
    base = str(tmp_path / "big")
    with open(base + ".dat", "wb") as f:
        f.truncate(1_006_723_848)         # sparse: planned, never read
    plan = be._plan_volume(base, 1 << 30, 1 << 20)
    units = be._make_units([plan], be._chunk_len(1 << 30, 1 << 20))
    batch = be.TARGET_BATCH_BYTES // (be.DATA_SHARDS * (1 << 20))
    calls = len(units) + 6 * -(-len(units) // batch)
    rows = sum(u.real_rows for u in units)
    assert calls < 300 <= rows, (calls, rows)


def test_small_seal_makes_one_span_a_unit_and_six_a_batch(tmp_path,
                                                          monkeypatch):
    names = []
    real = tracing.stage

    def counting(name, *a, **kw):
        names.append(name)
        return real(name, *a, **kw)

    monkeypatch.setattr(tracing, "stage", counting)
    st = _seal(_make_volume(tmp_path, count=30))
    per_batch = [n for n in names if n != "ec.encode.read"]
    assert set(names) == ENCODE_SPANS
    assert len(per_batch) == 6 * st["batches"]
    assert names.count("ec.encode.read") >= st["batches"]


# -- one root span for either pipeline ----------------------------------------

@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
def test_a_sampled_seal_has_one_root_with_its_stage_totals(tmp_path,
                                                           monkeypatch,
                                                           host):
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "1")
    tracing.RECORDER.reset()
    t_before = time.time()
    st = _seal(_make_volume(tmp_path, count=30),
               **({"host_codec": True} if host else {}))
    idx = [t for t in tracing.RECORDER.index() if
           t["root"] == "ec.encode_volumes"]
    assert len(idx) == 1
    tree = tracing.RECORDER.get(idx[0]["trace_id"])["tree"]
    assert len(tree) == 1
    root = tree[0]
    # a real span: it started when the seal did, not back-dated from the end
    assert t_before <= root["start"] <= time.time()
    # and it holds the pipeline's `wall` (a figure rounded to a ms)
    assert root["duration_ms"] >= st["wall"] * 1e3 - 1.0
    totals = {c["name"]: c for c in root["children"]
              if (c.get("tags") or {}).get("total")}
    keys = ("read", "encode_crc", "write", "flush") if host else \
        OLD_ENCODE_KEYS[:-1] + NEW_ENCODE_KEYS
    for key in keys:
        node = totals["ec.encode." + key]
        assert node["duration_ms"] == pytest.approx(st[key] * 1e3, abs=1.0)
        assert node["start"] == root["start"]
    # and the per-batch stages the seal paid for, under the same trace
    stages = [c for c in root["children"]
              if not (c.get("tags") or {}).get("total")]
    assert {c["name"] for c in stages} >= (
        HOST_ENCODE_SPANS if host else ENCODE_SPANS - {"ec.encode.h2d"})
    assert all(set(c["tags"]) == {"n", "bytes"} for c in stages)


def test_an_unsampled_fast_seal_leaves_no_trace(tmp_path, monkeypatch):
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    monkeypatch.setenv("WEED_TRACE_SLOW_MS", "600000")
    tracing.RECORDER.reset()
    _seal(_make_volume(tmp_path, count=20))
    assert tracing.RECORDER.index() == []


# -- the master's reaper and a machine that stood still -----------------------

def test_reaper_does_not_charge_nodes_for_its_own_stall():
    from seaweedfs_tpu.master.topology import Topology

    topo = Topology(pulse_seconds=1.0)
    hb = {"ip": "127.0.0.1", "port": 8080, "volumes": [
        {"id": 1, "collection": "bench", "size": 0}], "ec_shards": []}
    topo.process_heartbeat(hb)
    node = next(iter(topo.nodes.values()))
    node.last_seen -= 8.0      # the whole machine stood still for 8 s
    topo.forgive_silence(7.5)  # ... and the reaper woke that much late
    assert topo.reap_dead_nodes() == []
    assert topo.writable_count("bench", 0, 0) == 1
    # silence the master did hear through still reaps
    node.last_seen -= 4.0
    assert topo.reap_dead_nodes() == [node.id]
    assert topo.writable_count("bench", 0, 0) == 0


def test_forgiven_silence_never_moves_a_heartbeat_into_the_future():
    from seaweedfs_tpu.master.topology import Topology

    topo = Topology(pulse_seconds=1.0)
    topo.process_heartbeat({"ip": "127.0.0.1", "port": 1, "volumes": [],
                            "ec_shards": []})
    topo.forgive_silence(30.0)
    node = next(iter(topo.nodes.values()))
    assert node.last_seen <= time.time()


def test_master_reaper_loop_forgives_a_late_wake_up(monkeypatch):
    """The loop itself: a wake-up 8 s late (the machine stood still) must
    not unregister the volume server whose heartbeat is as old."""
    from seaweedfs_tpu.master import server as master_mod
    from seaweedfs_tpu.master.topology import Topology

    topo = Topology(pulse_seconds=0.2)
    topo.process_heartbeat({
        "ip": "127.0.0.1", "port": 8080, "ec_shards": [], "volumes": [
            {"id": 1, "collection": "bench", "size": 0}]})
    node = next(iter(topo.nodes.values()))
    clock = [1000.0]
    waits = iter([False, True])

    class StalledTime:
        """The server module's `time`, its monotonic clock ours."""
        monotonic = staticmethod(lambda: clock[0])

        def __getattr__(self, name):
            return getattr(time, name)

    class Stop:
        @staticmethod
        def wait(timeout):
            clock[0] += timeout + 8.0          # every wake-up 8 s late
            node.last_seen = time.time() - 8.0
            return next(waits)

    class Master:                              # what _reap_loop touches
        _stop = Stop()
        _drive_shard_resize = staticmethod(lambda: None)

    Master.topo = topo
    monkeypatch.setattr(master_mod, "time", StalledTime())
    master_mod.MasterServer._reap_loop(Master())
    assert node.id in topo.nodes
    assert topo.writable_count("bench", 0, 0) == 1


# -- (e) the device_touch shape, sealed twice under load ----------------------

TOUCH_OBJECTS = [(5, 4 << 20), (128, 32 << 10)]    # put-get-open's small seal


def test_small_seal_twice_under_put_get_load(tmp_path, monkeypatch):
    """A volume smaller than one compiled batch (5 x 4 MiB + 128 x 32 KiB)
    sealed twice in one process while 4 threads PUT and GET another
    collection: every new key comes back from both seals and from
    /admin/ec/recover_stats, no heartbeat fails, and the master never
    runs out of writable volumes for the collection under load."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.rpc.http_rpc import call
    from seaweedfs_tpu.volume_server.server import VolumeServer

    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    (tmp_path / "vs").mkdir()
    vs = VolumeServer([str(tmp_path / "vs")], master.address, port=0,
                      pulse_seconds=0.2, ec_encoder_backend="tpu",
                      max_volume_counts=[16])
    vs.start()
    vs.heartbeat_once()
    stop = threading.Event()
    errors, least = [], [99]
    try:
        call(master.address, "/vol/grow?collection=bench&count=2",
             method="POST")
        rng = np.random.default_rng(26)
        touch = []
        for _ in range(2):
            grown = call(master.address,
                         "/vol/grow?collection=touch&count=1", method="POST")
            assert grown["count"] == 1
        vs.heartbeat_once()
        st = call(master.address, "/dir/status")
        for layout in st["Topology"]["layouts"] if "Topology" in st \
                else st["layouts"]:
            if layout["collection"] == "touch":
                touch = list(layout["writables"])
        assert len(touch) == 2
        big = rng.bytes(4 << 20)
        for vid in touch:
            key = 1
            for count, size in TOUCH_OBJECTS:
                for _ in range(count):
                    call(vs.address, f"/{vid},{key:x}00000001",
                         raw=big[:size], method="POST")
                    key += 1

        def client(seed):
            r = np.random.default_rng(seed)
            mine = []
            try:
                while not stop.is_set():
                    a = call(master.address, "/dir/assign?collection=bench")
                    body = r.bytes(1024)
                    call(a["url"], "/" + a["fid"], raw=body, method="POST")
                    mine.append((a["fid"], body))
                    fid, want = mine[int(r.integers(len(mine)))]
                    assert call(vs.address, "/" + fid, parse=False) == want
            except Exception as e:     # raised on the test's thread
                errors.append(e)

        def watch():
            while not stop.is_set():
                least[0] = min(least[0],
                               master.topo.writable_count("bench", 0, 0))
                time.sleep(0.01)

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)] + [threading.Thread(target=watch)]
        for t in threads:
            t.start()
        failures = stats.VolumeServerHeartbeatFailures._values.get((), 0.0)
        replies = []
        for vid in touch:
            call(vs.address, "/admin/readonly",
                 {"volume": vid, "readonly": True})
            replies.append(call(vs.address, "/admin/ec/generate",
                                {"volume": vid}, timeout=300))
            call(vs.address, "/admin/ec/mount",
                 {"volume": vid, "collection": "touch",
                  "shard_ids": list(range(14))})
            call(vs.address, "/admin/delete_volume", {"volume": vid})
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(30)
        assert not errors, errors[:2]
        assert least[0] >= 1, "the master ran out of writable volumes"
        assert stats.VolumeServerHeartbeatFailures._values.get(
            (), 0.0) == failures
        for reply in replies:
            ss = reply["stage_stats"]
            assert reply["backend"].startswith("device-")
            # smaller than one round of the deal: at most a batch a lane
            assert ss["batches"] <= ss["devices"]
            assert sum(ss["device_batches"]) == ss["batches"]
            for key in OLD_ENCODE_KEYS + NEW_ENCODE_KEYS:
                assert isinstance(ss[key], float), key
            assert "kernel_cost" not in ss
            cap = ss["read"] * ss["read_workers"] + 0.002
            assert ss["read_dat"] + ss["read_data_write"] <= cap
            assert ss["read_worker_busy"] <= cap
        rs = call(vs.address, "/admin/ec/recover_stats")
        for key in OLD_RECOVER_KEYS + NEW_RECOVER_KEYS:
            assert key in rs, key
        text = call(vs.address, "/metrics", parse=False).decode()
        assert "SeaweedFS_volumeServer_heartbeat_max_gap_seconds" in text
        assert "SeaweedFS_volumeServer_heartbeat_failures_total" in text
    finally:
        stop.set()
        vs.stop()
        master.stop()


def test_heartbeat_max_gap_begins_again_once_the_device_is_up(
        tmp_path, monkeypatch):
    """The maximum runs from the first beat acknowledged after the server
    listens on and begins again once, at the first beat after the
    process initialised its device: the gap of that stall is not what
    the gauge holds hours later, a shorter gap after it is."""
    from seaweedfs_tpu.util import platform as platform_util
    from seaweedfs_tpu.volume_server.server import VolumeServer

    monkeypatch.setattr(platform_util, "_cache", {})
    assert not platform_util.device_asked()
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:1", port=0,
                      pulse_seconds=0.01)
    beats = []
    seen = []
    waits = {2: 0.25, 4: 0.08}     # the third beat is late for the init

    def beat():
        n = len(beats)
        seen.append(stats.VolumeServerHeartbeatMaxGap._values[()])
        if n in waits:
            time.sleep(waits[n])
            if n == 2:
                platform_util._cache["device"] = None
        beats.append(n)
        if n == 6:
            vs._stop.set()

    monkeypatch.setattr(vs, "heartbeat_once", beat)
    stats.VolumeServerHeartbeatMaxGap.set(0.0)
    vs.server.start()          # listening; the loop is run by hand
    try:
        vs._heartbeat_loop()
    finally:
        vs.stop()
    assert len(beats) == 7 and platform_util.device_asked()
    assert seen[2] > 0.0 and seen[3] == 0.0    # counted, then begun again
    gap = stats.VolumeServerHeartbeatMaxGap._values[()]
    assert 0.08 <= gap < 0.25
    assert "device" in stats.VolumeServerHeartbeatMaxGap.help
