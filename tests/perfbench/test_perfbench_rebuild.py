"""What PR 29 added to the benchmark as new files: the configuration
`warm-ec-rs10.4-1chip-4lost`, the cell `rebuild-4lost` with its driver,
the reconstruction reference, the rebuild step's roofline counts and the
eleven per-layer metrics.  REHEARSALS on the CPU backend (tiny volumes, no
chip, no timing assertion), the control, a survivor that went bad, and the
names a trace of the rebuild's program carries.  The structural checks of
the grown manifest are `test_perfbench_manifest.py`'s and
`test_perfbench_extend.py`'s, which read whatever the tree holds."""

import argparse
import functools
import importlib
import os
import re
import shutil
import sys
import tempfile

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import ROOT, check_result_line, run_cell  # noqa: E402

import reference  # noqa: E402
import reference_rebuild  # noqa: E402
from roofline import rs_rebuild  # noqa: E402

TREE = checks.Tree(ROOT)
LAYER = TREE.layer
CELL = "rebuild-4lost"
CONFIG = "warm-ec-rs10.4-1chip-4lost"

STAGE_METRICS = {
    "rebuild_read_s_per_gib": "stage_stats.read",
    "rebuild_dispatch_s_per_gib": "stage_stats.dispatch",
    "rebuild_crc_s_per_gib": "stage_stats.crc",
    "rebuild_write_wait_s_per_gib": "stage_stats.write_wait",
    "rebuild_write_s_per_gib": "stage_stats.write",
    "rebuild_d2h_wait_s_per_gib": "stage_stats.d2h_wait",
}
COUNTER_METRICS = {
    "rebuild_h2d_bytes_per_user_byte":
        "SeaweedFS_volumeServer_ec_device_h2d_bytes_total",
    "rebuild_d2h_bytes_per_user_byte":
        "SeaweedFS_volumeServer_ec_device_d2h_bytes_total",
    "device_init_s.rebuild-4lost": "SeaweedFS_volumeServer_startup_seconds",
}
TRACE_METRICS = ("rebuild_kernel_roofline", "rebuild_kernel_us")
ALL_METRICS = tuple(STAGE_METRICS) + tuple(COUNTER_METRICS) + TRACE_METRICS

COMPARED = (
    "rebuilds_not_on_device-apply-xla_x1",
    "setup_seal_not_on_device-pooled-swar_x1",   # the CPU's encode path
    "operations_failed", "h2d_bytes_short_of_survivor_bytes",
    "rebuilt_files_differ_from_the_sealed", "survivor_files_changed",
    "shard_crc32c_differ_from_vif", "parity_bytes_differ_from_reference",
    "data_shard_bytes_differ_from_dat", "stripe_sample_bytes_short",
    "rebuilt_bytes_differ_from_reconstruction",
    "reconstruction_sample_bytes_short", "objects_not_read_back",
    "objects_read_back_through_a_recover", "pristine_dat_changed",
)
# the benchmark's pattern, one all-data, one all-parity, a single loss
LOSSES = [(0, 3, 11, 13), (1, 4, 6, 8), (10, 11, 12, 13), (5,)]
ACCEPTED_PATTERNS = (r"^jit_step\(", r"^jit__fused\(",
                     r"^jit__apply_pallas\(")


def _spec(name):
    return TREE.load("perfbench", "layer_metrics", name + ".json")


# -- the manifest's new entries -----------------------------------------------

def test_the_cell_the_configuration_and_the_metrics_are_in_the_manifest():
    cell = TREE.cells[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": CELL, "chips": 1}
    assert TREE.ends_of(CELL) == {"bulk_rate", "setup_s"}
    assert TREE.layers_of(CELL) == set(ALL_METRICS)
    # appended after the accepted names, in the issue's order
    names = list(LAYER)
    assert names[len(checks.ACCEPTED_PER_LAYER):][:len(ALL_METRICS)] == [
        "rebuild_read_s_per_gib", "rebuild_dispatch_s_per_gib",
        "rebuild_crc_s_per_gib", "rebuild_write_wait_s_per_gib",
        "rebuild_write_s_per_gib", "rebuild_d2h_wait_s_per_gib",
        "rebuild_h2d_bytes_per_user_byte", "rebuild_d2h_bytes_per_user_byte",
        "rebuild_kernel_roofline", "rebuild_kernel_us",
        "device_init_s.rebuild-4lost"]
    assert sum(1 for w in TREE.cells.values() if w["chips"] == 4) == 1


def test_configuration_keeps_the_source_s_shape_and_states_what_it_adds():
    new = TREE.load("perfbench", "configs", CONFIG + ".json")
    old = TREE.load("perfbench", "configs", "warm-ec-rs10.4-1chip.json")
    for key in ("code", "volume_size_limit_mb", "volume_shape",
                "flush_policy", "daemons", "env", "rehearse_env", "chips"):
        assert new[key] == old[key], key
    assert old["guarantees"].items() <= new["guarantees"].items()
    assert "byte-identical" in new["guarantees"]["rebuild"]
    assert set(old["assumed"]) < set(new["assumed"])
    assert new["env"]["WEED_MAINT"] == "0"
    assert new["lost_shards"] == [0, 3, 11, 13]
    assert set(new["reduced"]) == {"volumes"}
    assert new["expect"] == {
        "platform": "tpu", "rebuild_backend": "device-apply-xla",
        "rebuild_devices": 1, "encode_backend": "device-words",
        "encode_devices": 1, "h2d_covers_survivors": True}
    # the volume is the one `seal` seals, object for object
    traffic = TREE.load("perfbench", "traffic", CELL + ".json")
    seal = TREE.load("perfbench", "traffic", "seal.json")
    assert traffic["volume"] == seal["volume"]
    assert traffic["rehearse"]["volume"] == seal["rehearse"]["volume"]
    assert traffic["readback_objects"] == 200


@pytest.mark.parametrize("name", ALL_METRICS)
def test_new_metric_lists_the_cell_and_reads_the_named_source(name):
    entry, spec = LAYER[name], _spec(name)
    assert entry["workloads"] == spec["workloads"] == [CELL]
    reader = spec["reader"]
    if name in STAGE_METRICS:
        assert reader == {"kind": "harness_record", "record": "rebuild",
                          "key": STAGE_METRICS[name], "per": "gib"}
    elif name in COUNTER_METRICS:
        assert reader["family"] == COUNTER_METRICS[name]
        assert reader["kind"] in ("prometheus", "prometheus_value")
    else:
        assert reader["kind"] == "trace"
        assert reader["patterns"] == [r"^jit_rebuild_apply\("]
    # data only: every kind of reader was there
    assert reader["kind"] in ("harness_record", "prometheus",
                              "prometheus_value", "trace")


def test_device_init_reads_what_the_accepted_metric_reads():
    assert _spec("device_init_s.rebuild-4lost")["reader"] == \
        _spec("device_init_s")["reader"]


# -- the rebuild's program in a trace -----------------------------------------

def _rebuild_step():
    import jax

    from seaweedfs_tpu.parallel import mesh as mesh_mod
    from seaweedfs_tpu.parallel.batched_encode import rebuild_matrix

    survivors, matrix = rebuild_matrix(
        [s for s in range(14) if s not in LOSSES[0]], list(LOSSES[0]))
    assert survivors == [1, 2, 4, 5, 6, 7, 8, 9, 10, 12]
    mesh = mesh_mod.make_ec_mesh(devices=jax.devices()[:1])
    return mesh_mod.make_sharded_apply(mesh, matrix)


def test_rebuild_program_keeps_a_name_of_its_own():
    """On the `XLA Modules` line the program is `jit_<Python name>(`: the
    new metrics find it, and no accepted pattern takes it for the encode
    step or the recover kernel."""
    step = _rebuild_step()
    assert step.__name__ == "rebuild_apply"
    module = "jit_" + step.__name__ + "(1234)"
    for name in TRACE_METRICS:
        assert any(re.search(p, module)
                   for p in _spec(name)["reader"]["patterns"])
    for pattern in ACCEPTED_PATTERNS:
        assert not re.search(pattern, module), pattern


@pytest.mark.parametrize("metric", ["encode_kernel_roofline",
                                    "recover_kernel_us"])
def test_accepted_trace_patterns_are_the_pinned_ones(metric):
    assert set(_spec(metric)["reader"]["patterns"]) <= set(ACCEPTED_PATTERNS)


def test_rebuild_scope_name_is_in_the_lowered_program():
    import jax
    import jax.numpy as jnp

    lowered = _rebuild_step().lower(
        jax.ShapeDtypeStruct((1, 10, 512), jnp.uint8))
    text = lowered.as_text(debug_info=True)
    assert "ec.rebuild.apply" in text and "ec.crc32c" in text
    assert "ec.encode.step" not in text


# -- the reconstruction reference ---------------------------------------------

def test_reference_rebuild_imports_nothing_of_the_program():
    with open(os.path.join(TREE.bench, "reference_rebuild.py")) as f:
        src = f.read()
    assert "seaweedfs_tpu" not in src
    imports = re.findall(r"^(?:import|from) (\S+)", src, re.M)
    assert set(imports) == {"__future__", "os", "random", "numpy",
                            "reference"}


def _stripes(seed, length=4096):
    data = np.random.default_rng(seed).integers(
        0, 256, (reference.DATA_SHARDS, length), dtype=np.uint8)
    parity = reference.gf_apply(reference.parity_matrix(), data)
    return np.concatenate([data, parity])


@pytest.mark.parametrize("loss", LOSSES, ids=str)
def test_reconstruction_gives_back_known_stripes(loss):
    rows = _stripes(29)
    survivors = [s for s in range(14) if s not in loss][:10]
    got = reference_rebuild.reconstruct(survivors, rows[survivors],
                                        list(loss))
    assert got.dtype == np.uint8 and got.shape == (len(loss), 4096)
    assert np.array_equal(got, rows[list(loss)])


@pytest.mark.parametrize("loss", LOSSES, ids=str)
def test_any_ten_survivors_give_the_same_answer_and_rs_numpy_agrees(loss):
    from seaweedfs_tpu.ops.rs_numpy import decode_rows

    rows = _stripes(30)
    left = [s for s in range(14) if s not in loss]
    last_ten = left[-10:]
    got = reference_rebuild.reconstruct(last_ten, rows[last_ten], list(loss))
    assert np.array_equal(got, rows[list(loss)])
    first_ten = left[:10]
    assert np.array_equal(
        reference_rebuild.reconstruction_matrix(first_ten, list(loss)),
        np.array(decode_rows(10, 14, first_ten, tuple(loss)), np.uint8))


def test_all_parity_lost_is_the_encode_matrix_again():
    assert np.array_equal(reference_rebuild.reconstruction_matrix(
        list(range(10)), [10, 11, 12, 13]), reference.parity_matrix())
    assert np.array_equal(reference_rebuild.encoding_matrix()[:10],
                          np.eye(10, dtype=np.uint8))


@pytest.mark.parametrize("survivors,lost", [
    (list(range(9)), [13]), (list(range(10)), [9, 13])],
    ids=["nine-survivors", "a-survivor-listed-as-lost"])
def test_reconstruction_refuses_what_cannot_be_a_rebuild(survivors, lost):
    with pytest.raises(ValueError):
        reference_rebuild.reconstruction_matrix(survivors, lost)


def _write_shards(base, rows):
    for sid, row in enumerate(rows):
        with open(base + reference.shard_ext(sid), "wb") as f:
            f.write(row.tobytes())


@pytest.mark.parametrize("loss", LOSSES, ids=str)
def test_check_rebuilt_sample_on_files(loss, tmp_path):
    rows = _stripes(31, length=3 * (64 << 10) + 1000)   # a short last piece
    base = str(tmp_path / "v_1")
    _write_shards(base, rows)
    whole = rows.shape[1] * len(loss)
    got = reference_rebuild.check_rebuilt_sample(base, list(loss), 5, whole)
    assert got == {"bytes_differ": 0, "bytes_compared": whole}
    # a sample is whole 64 KiB columns, at least one, drawn by the seed
    part = reference_rebuild.check_rebuilt_sample(base, list(loss), 5, 1)
    assert part["bytes_differ"] == 0
    assert 1000 * len(loss) <= part["bytes_compared"] <= (64 << 10) * len(loss)
    # one flipped byte of one rebuilt file
    path = base + reference.shard_ext(loss[-1])
    with open(path, "r+b") as f:
        f.seek(70_000)
        f.write(bytes([rows[loss[-1], 70_000] ^ 0x80]))
    assert reference_rebuild.check_rebuilt_sample(
        base, list(loss), 5, whole)["bytes_differ"] == 1
    # a rebuilt file cut short, and one that is gone
    os.truncate(path, 1000)
    short = reference_rebuild.check_rebuilt_sample(base, list(loss), 5, whole)
    assert short["bytes_differ"] >= rows.shape[1] - 1000
    os.remove(path)
    gone = reference_rebuild.check_rebuilt_sample(base, list(loss), 5, whole)
    assert gone["bytes_differ"] >= rows.shape[1]


# -- the roofline counts, on hand-worked shapes -------------------------------

def test_rebuild_roofline_arithmetic_on_known_shapes():
    # 6 units of 10 survivor rows in, 4 rebuilt rows out, 1 MiB each
    w = rs_rebuild.work(6, 4, 1 << 20)
    assert w["bytes"] == 6 * 14 * (1 << 20) == 88080384
    assert w["int_ops"] == 2 * 10 * 4 * 6 * (1 << 20) == 503316480
    # one lost shard: eleven rows move, ten products a rebuilt byte
    w = rs_rebuild.work(6, 1, 1 << 20)
    assert w["bytes"] == 6 * 11 * (1 << 20)
    assert w["int_ops"] == 2 * 10 * 6 * (1 << 20)
    # the bytes bound binds on a v5e: 107.5 us against 1.3 us
    assert 88080384 / 819e9 > 503316480 / 393e12


def test_rebuild_roofline_reads_the_last_kept_reply():
    # the 1,006,723,848 B volume: 97 rows in 17 batches of 6
    reply = {"batch_units": 6, "devices": 1, "batches": 17,
             "h2d_bytes": 17 * 6 * 10 * (1 << 20),
             "missing": [0, 3, 11, 13]}
    assert reply["h2d_bytes"] == 1_069_547_520
    ctx = {"records": {"rebuild": [{"stage_stats": {}},
                                   {"stage_stats": reply}]}}
    assert rs_rebuild.work_per_event(ctx) == rs_rebuild.work(6, 4, 1 << 20)
    # a batch of 8 over 4 chips is 2 units a chip; rehearsal-sized rows
    reply = {"batch_units": 8, "devices": 4, "batches": 3,
             "h2d_bytes": 3 * 8 * 10 * 4096, "missing": [5]}
    ctx = {"records": {"rebuild": [{"stage_stats": reply}]}}
    assert rs_rebuild.work_per_event(ctx) == rs_rebuild.work(2, 1, 4096)


@pytest.mark.parametrize("ctx", [
    {}, {"records": {}}, {"records": {"rebuild": []}},
    {"records": {"seal": [{"stage_stats": {"batch_units": 6}}]}},
    {"records": {"rebuild": [{"backend": "host-loop"}]}},
    {"records": {"rebuild": [{"stage_stats": {"backend": "host-loop"}}]}}],
    ids=["empty", "no-records", "no-rebuild", "a-seal", "no-stats",
         "host-loop"])
def test_rebuild_roofline_reads_nothing_and_never_raises(ctx):
    assert rs_rebuild.work_per_event(ctx) is None


def test_roofline_share_of_a_rebuild_step_stays_under_100():
    from readers import trace

    spec = _spec("rebuild_kernel_roofline")["reader"]
    logs = []
    ctx = {"trace": {"modules": [(0, "jit_rebuild_apply(7)", 0.1, 0.020),
                                 (0, "jit_rebuild_apply(7)", 0.2, 0.022),
                                 (0, "jit_step(3)", 0.3, 0.001)]},
           "peaks": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12},
           "log": logs.append,
           "records": {"rebuild": [{"stage_stats": {
               "batch_units": 6, "devices": 1, "batches": 17,
               "h2d_bytes": 1_069_547_520, "missing": [0, 3, 11, 13]}}]}}
    share = trace.read(spec, ctx)
    assert share == pytest.approx(2 * 88080384 / 819e9 / 0.042 * 100)
    assert 0 < share < 100 and "bytes bound binds" in logs[0]
    us = trace.read(_spec("rebuild_kernel_us")["reader"], ctx)
    assert us == pytest.approx(21000.0)


# -- laid over the parent's checkout ------------------------------------------

@pytest.mark.parametrize("name", ALL_METRICS)
def test_reader_of_a_new_metric_reads_nothing_from_a_parent(name):
    """The parent's rebuild reply has no stage seconds and its program is
    `jit_step`: every reader returns None there, none raises.  (The two
    byte counters and `device_init_s` were there: they read.)"""
    reader = _spec(name)["reader"]
    module = importlib.import_module("readers." + reader["kind"])
    parent_reply = {"backend": "device-apply-xla", "devices": 1,
                    "wall": 1.5, "batches": 17, "batch_units": 6,
                    "missing": [0, 3, 11, 13], "h2d_bytes": 1_069_547_520,
                    "d2h_bytes": 427_819_008}
    fam = "SeaweedFS_volumeServer_"
    ctx = {"records": {"rebuild": [{"gib": 0.9376,
                                    "stage_stats": parent_reply}]},
           "trace": {"modules": [(0, "jit_step(5)", 0.1, 0.02)]},
           "prom": [[(fam + "ec_device_h2d_bytes_total", {"device": "T"}, 0)],
                    [(fam + "ec_device_h2d_bytes_total", {"device": "T"},
                      2e9),
                     (fam + "ec_device_d2h_bytes_total", {"device": "T"},
                      8e8),
                     (fam + "startup_seconds", {"phase": "device_init"},
                      6.5)]],
           "counts": {"repaired_bytes": 2 * 1_006_723_848},
           "peaks": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12},
           "log": print}
    value = module.read(reader, ctx)
    if name in COUNTER_METRICS:
        assert value is not None and value > 0
    else:
        assert value is None
    assert module.read(reader, {}) is None


# -- rehearsals of the cell ----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _rehearsal(trace: int, control: str = ""):
    extra = ("--control", control) if control else ()
    proc, result = run_cell(CELL, "--trace", str(trace), *extra)
    return proc.returncode, proc.stdout, proc.stderr, result


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_exits_zero_with_a_whole_result_line(trace):
    code, out, err, result = _rehearsal(trace)
    assert code == 0, err[-2000:]
    assert "REHEARSAL" in out
    check_result_line(result, trace=bool(trace))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    if trace:
        assert set(result["metrics"]) <= TREE.layers_of(CELL)
        assert "bulk_rate" not in result["metrics"]
    else:
        assert set(result["metrics"]) == TREE.ends_of(CELL) \
            == {"bulk_rate", "setup_s"}
        assert result["metrics"]["bulk_rate"]["value"] > 0


@pytest.mark.parametrize("name", COMPARED)
def test_rehearsal_prints_every_number_compared_beside_its_limit(name):
    code, out, err, result = _rehearsal(0)
    assert result["compared"][name] == {"value": 0, "limit": 0}
    assert f'compared: {{"name": "{name}", "value": 0, "limit": 0' in out
    assert f"compared {name}: 0 (limit 0)" in err
    assert list(result["compared"]) == list(COMPARED)


def test_rehearsal_drives_the_shell_s_three_steps_and_keeps_the_last():
    code, out, err, result = _rehearsal(0)
    assert "warm-up rebuild took" in out and "as device-apply-xla" in out
    assert "(lookup, rebuild, mount: [" in out
    assert "0 refused" in out and "shard bytes rebuilt" in out
    assert re.search(r"read back \d+ of 133 objects: 0 wrong, 0 through a "
                     r"recover", out)
    # every rebuild's seven stage seconds are in its log line
    line = next(ln for ln in out.splitlines() if "] rebuild 1 sent" in ln)
    for key in ("read", "dispatch", "h2d", "d2h_wait", "crc", "write_wait",
                "write"):
        assert re.search(rf" {key} \d", line), key


@pytest.mark.parametrize("name", ALL_METRICS)
def test_traced_rehearsal_reports_the_metric_or_says_nothing_to_read(name):
    code, out, err, result = _rehearsal(1)
    assert code == 0, err[-2000:]
    if name in TRACE_METRICS:     # no TPU plane in a CPU trace
        assert name not in result["metrics"]
        assert f"per-layer {name}: nothing to read" in out
        return
    assert result["metrics"][name]["unit"] == LAYER[name]["unit"]
    assert result["metrics"][name]["value"] >= 0.0
    if name == "rebuild_h2d_bytes_per_user_byte":
        # ten survivor rows a unit, whole units: never under the volume
        assert result["metrics"][name]["value"] >= 1.0
    if name == "rebuild_d2h_bytes_per_user_byte":
        assert result["metrics"][name]["value"] == pytest.approx(
            0.4 * result["metrics"]["rebuild_h2d_bytes_per_user_byte"][
                "value"])


def test_flipped_byte_of_a_rebuilt_shard_makes_correct_false():
    """The control: one byte of rebuilt shard 11 altered where it lies,
    after the window's last rebuild, where `seal` flips it."""
    code, out, err, result = _rehearsal(0, "shard_file")
    assert code == 0, err[-2000:]
    assert "CONTROL: one byte of bench_1.ec11 flipped" in out
    assert result["correct"] is False
    assert result["compared"]["rebuilt_files_differ_from_the_sealed"] == {
        "value": 1, "limit": 0}
    assert result["compared"]["shard_crc32c_differ_from_vif"] == {
        "value": 1, "limit": 0}
    assert result["compared"]["survivor_files_changed"]["value"] == 0
    assert result["failed"] == 0


def test_a_rebuild_the_server_refuses_is_a_failed_operation(capsys):
    """A survivor goes bad under the harness: every rebuild is refused by
    the server (the rebuilt CRCs miss the `.vif` record), the driver
    counts each as failed, goes on, and the comparison against 0 fails.
    Driven in process: the harness has no such control."""
    import run as bench_run

    driver = importlib.import_module("drivers.rebuild_restore")
    loaded = bench_run.load_cell(ROOT, CELL, True)
    args = argparse.Namespace(workload=CELL, seed=2147483777, trace=0,
                              rehearse=True, control=None, seconds=0.5)
    workdir = tempfile.mkdtemp(prefix="perfbench_test_")
    run = bench_run.Run(args, loaded, workdir,
                        os.path.join(ROOT, ".jax_cache"))
    try:
        run.boot(1)
        state = driver.prepare(run)
        path = state.shard(state.survivors[1])
        with open(path, "r+b") as f:
            f.seek(4242)
            byte = f.read(1)
            f.seek(4242)
            f.write(bytes([byte[0] ^ 0x01]))
        run.window_open = True
        result = driver.window(run, state, args.seconds)
        compared = {c["name"]: c for c in driver.verify(run, state, result)}
    finally:
        if run._boot is not None:
            run._boot.join(300)
        run.cluster.daemons.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    out = capsys.readouterr().out
    assert result["failed"] == result["attempted"] >= 1
    assert result["end_to_end"]["bulk_rate"] == 0.0
    assert out.count("FAILED after") == result["failed"]
    assert "do not match the recorded CRCs" in out
    assert compared["operations_failed"]["value"] == result["failed"]
    assert compared["operations_failed"]["ok"] is False
    # the bad survivor is seen by the checks too, not only by the server
    assert compared["survivor_files_changed"]["value"] >= 1
    assert compared["objects_not_read_back"]["ok"] is False
