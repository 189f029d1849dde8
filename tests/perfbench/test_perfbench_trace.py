"""The reduction from a recorded v5e trace to numbers, the roofline
arithmetic on known shapes, the peaks table, and each kind of reader."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402
from readers import (admin_json, count, harness_record,  # noqa: E402
                     harness_span, prometheus, trace)
from roofline import rs_encode  # noqa: E402

FIX = os.path.join(BENCH, "fixtures")
# recorded on a TPU v5 lite by PR 23's builder (chiprun_out/r1), 5 s each:
# a put-get-open window with one small seal, and a degraded-get window
SEAL = os.path.join(FIX, "small_seal_v5e.xplane.pb")
DEGRADED = os.path.join(FIX, "degraded_get_v5e.xplane.pb")
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))


@pytest.fixture(scope="module")
def seal_trace():
    return trace_reduce.reduce_trace(SEAL)


@pytest.fixture(scope="module")
def degraded_trace():
    return trace_reduce.reduce_trace(DEGRADED, window_s=5.0, client_spans=[
        ("get_sealed", 0.0, 2.5), ("restore", 2.0, 2.5)])


def test_busy_is_the_union_of_device_ops(seal_trace):
    assert seal_trace["chips"] == [0]
    assert seal_trace["busy_s"] == pytest.approx(0.000352422, rel=1e-6)
    assert seal_trace["window_s"] == pytest.approx(4.793152719, rel=1e-6)
    assert 0 < seal_trace["busy_s"] < seal_trace["window_s"]
    (chip, name, start, seconds), = seal_trace["modules"]
    assert name.startswith("jit_step(") and chip == 0
    # a program's ops run inside it: the union cannot exceed its span
    assert seal_trace["busy_s"] <= seconds


def test_device_ops_ranked_and_capped(degraded_trace):
    ops = degraded_trace["device_ops"]
    assert 1 <= len(ops) <= 10
    assert ops == sorted(ops, key=lambda x: -x[1])
    assert ops[0][0].startswith("%_apply_pallas")
    assert sum(sec for _, sec in ops) == pytest.approx(
        degraded_trace["busy_s"], rel=1e-3)


def test_idle_gaps_cover_the_rest_of_the_window(degraded_trace):
    gaps = degraded_trace["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    assert all(isinstance(sec, float) for _, sec in gaps)
    assert sum(sec for _, sec in gaps) <= 5.0 - degraded_trace["busy_s"] + 1e-6
    labels = " ".join(name for name, _ in gaps)
    assert "client: get_sealed" in labels and "client: nothing" in labels
    assert "host: " in labels


def test_union_merges_overlaps():
    import numpy as np
    merged = trace_reduce._union(np.array(
        [[5., 7.], [0., 2.], [1., 3.], [6., 6.5], [10., 11.]]))
    assert merged.tolist() == [[0., 3.], [5., 7.], [10., 11.]]


def test_kernel_time_by_pattern(degraded_trace):
    spec = {"patterns": ["^jit__apply_pallas\\("], "stat": "mean_us"}
    events = trace.matching(spec, {"trace": degraded_trace})
    assert len(events) == 253
    assert trace.read(spec, {"trace": degraded_trace}) == pytest.approx(
        0.006980092 / 253 * 1e6, rel=1e-6)
    assert trace.read({"patterns": ["^jit_nothing\\("], "stat": "mean_us"},
                      {"trace": degraded_trace}) is None
    assert trace.read(spec, {"trace": None}) is None


def test_roofline_arithmetic_on_known_shapes():
    # 6 units of (10 + 4) x 1 MiB on one chip
    w = rs_encode.work(6)
    assert w["bytes"] == 6 * 14 * (1 << 20) == 88080384
    assert w["int_ops"] == 6 * (1 << 20) * 10 * 4 * 2 == 503316480
    # a batch of 8 over 4 chips is 2 units a chip
    ctx = {"records": {"seal": [{"stage_stats": {"batch_units": 8,
                                                 "devices": 4}}]}}
    assert rs_encode.work_per_event(ctx) == rs_encode.work(2)
    assert rs_encode.work_per_event({"records": {}}) is None


def test_roofline_share_of_the_recorded_step(seal_trace):
    logs = []
    ctx = {"trace": seal_trace, "peaks": PEAKS["TPU v5 lite"],
           "log": logs.append,
           "records": {"seal": [{"stage_stats": {"batch_units": 2,
                                                 "devices": 1}}]}}
    spec = {"patterns": ["^jit_step\\("], "stat": "roofline_pct",
            "roofline": "rs_encode"}
    share = trace.read(spec, ctx)
    least = 2 * 14 * (1 << 20) / 819e9
    assert share == pytest.approx(least / 0.000352752 * 100, rel=1e-6)
    assert 0 < share < 100
    assert "bytes bound binds" in logs[0]


def test_peaks_table_names_its_source_and_refuses_unknown_kinds(tmp_path):
    for kind, row in PEAKS.items():
        assert row["source"] and row["hbm_bytes_per_s"] > 0
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    import run as bench_run
    assert bench_run.peaks_for("TPU v5 lite") == PEAKS["TPU v5 lite"]
    with pytest.raises(bench_run.BenchFailure, match="no peaks for device"):
        bench_run.peaks_for("TPU v9000")  # an error, never a default


PROM = ([("x_seconds_sum", {"type": "read"}, 1.0),
         ("x_seconds_count", {"type": "read"}, 10.0),
         ("c_total", {"result": "hit"}, 5.0),
         ("c_total", {"result": "miss"}, 5.0),
         ("h2d", {"device": "host"}, 100.0), ("h2d", {"device": "TPU_0"}, 0.0)],
        [("x_seconds_sum", {"type": "read"}, 3.0),
         ("x_seconds_count", {"type": "read"}, 20.0),
         ("x_seconds_sum", {"type": "write"}, 9.0),
         ("c_total", {"result": "hit"}, 6.0),
         ("c_total", {"result": "miss"}, 14.0),
         ("h2d", {"device": "host"}, 900.0),
         ("h2d", {"device": "TPU_0"}, 220.0)])


@pytest.mark.parametrize("module,spec,ctx,want", [
    (prometheus, {"family": "x_seconds", "labels": {"type": "read"},
                  "stat": "histogram_mean", "scale": 1000},
     {"prom": PROM}, 200.0),
    (prometheus, {"family": "c_total", "labels": {"result": "hit"},
                  "stat": "delta_ratio", "over": {"family": "c_total"},
                  "scale": 100}, {"prom": PROM}, 10.0),
    (prometheus, {"family": "h2d", "labels_not": {"device": "host"},
                  "stat": "delta_ratio", "over": {"count": "sealed"}},
     {"prom": PROM, "counts": {"sealed": 200}}, 1.1),
    (prometheus, {"family": "absent", "stat": "histogram_mean"},
     {"prom": PROM}, None),
    (prometheus, {"family": "x_seconds", "stat": "histogram_mean"},
     {"prom": None}, None),
    (admin_json, {"path": "/p", "key": "decode_seconds", "per": "misses",
                  "scale": 1000},
     {"admin": {"/p": [{"decode_seconds": 1.0, "misses": 10},
                       {"decode_seconds": 1.5, "misses": 60}]}}, 10.0),
    (admin_json, {"path": "/p", "key": "k", "per": "misses"},
     {"admin": {"/p": [{"k": 1, "misses": 3}, {"k": 2, "misses": 3}]}}, None),
    (admin_json, {"path": "/other", "key": "k"}, {"admin": {}}, None),
    (harness_span, {"span": "assign", "stat": "mean", "scale": 1000},
     {"spans": {"assign": [(0.0, 0.001), (1.0, 1.003)]}}, 2.0),
    (harness_span, {"span": "put", "stat": "median", "scale": 1},
     {"spans": {"put": [(0, 1), (0, 2), (0, 9)]}}, 2.0),
    (harness_span, {"span": "none", "stat": "mean"}, {"spans": {}}, None),
    (harness_record, {"record": "seal", "key": "stage_stats.read",
                      "per": "gib"},
     {"records": {"seal": [{"stage_stats": {"read": 0.9}, "gib": 1.0},
                           {"stage_stats": {"read": 1.1}, "gib": 1.0}]}},
     1.0),
    (harness_record, {"record": "seal", "key": "stage_stats.read",
                      "per": "gib"},
     {"records": {"seal": [{"stage_stats": {}, "gib": 1.0}]}}, None),
    (count, {"count": "programs_built_in_window"},
     {"counts": {"programs_built_in_window": 2}}, 2.0),
    (harness_span, {"span": "get_sealed", "stat": "mean", "scale": 1000},
     {"spans": {"get_sealed": [(0.0, 0.002), (0.0, 0.008), (5.0, 5.110)]}},
     40.0),
    (count, {"count": "missing"}, {"counts": {}}, None),
])
def test_readers(module, spec, ctx, want):
    got = module.read(spec, ctx)
    if want is None:
        assert got is None  # nothing to read: the metric is left out
    else:
        assert got == pytest.approx(want)
