"""REHEARSALS of the `closed_loop_ops` driver on the CPU backend (tiny
sizes, no chip, no timing assertion), and a flipped byte of a GET body
making `correct` false."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import check_result_line, run_cell  # noqa: E402

TREE = checks.Tree(checks.ROOT)   # what a cell reports is read, not listed


def test_rehearse_degraded_get():
    proc, result = run_cell("degraded-get", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=False)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == TREE.ends_of("degraded-get") \
        >= {"op_p50_ms", "op_p95_ms", "setup_s"}
    assert "every extent holds a lost block" in proc.stdout
    # the warm-up reads back from JAX what it made the server build
    assert "stack warm-up:" in proc.stdout
    assert "programs built inside the window: 0 " in proc.stdout


def test_rehearse_degraded_get_traced():
    proc, result = run_cell("degraded-get", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=True)
    assert result["correct"] is True
    assert {"recover_decode_ms", "recover_cache_hit_share",
            "compiles_in_window"} <= set(result["metrics"])


def test_rehearse_put_get_open():
    proc, result = run_cell("put-get-open", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=False)
    assert result["correct"] is True and result["failed"] == 0
    # the cell's one piece of device work is in every window, traced or not
    assert '"device_touch_seals_missing_or_off_device", "value": 0' \
        in proc.stdout
    assert set(result["metrics"]) == TREE.ends_of("put-get-open") \
        >= {"op_p50_ms", "op_p95_ms", "goodput", "setup_s"}


def test_rehearse_put_get_open_traced():
    proc, result = run_cell("put-get-open", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=True)
    assert result["correct"] is True
    assert {"volume_get_ms", "assign_ms", "put_p50_ms",
            "get_p50_ms"} <= set(result["metrics"])


@pytest.mark.parametrize("cell", ["degraded-get", "put-get-open"])
def test_flipped_get_body_byte_makes_correct_false(cell):
    """The control: an answer altered where the client receives it."""
    proc, result = run_cell(cell, "--trace", "0", "--control", "get_body")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CONTROL: one byte of one GET body flipped" in proc.stdout
    assert result["correct"] is False
    assert result["failed"] >= 1
