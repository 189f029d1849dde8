"""REHEARSALS of the `seal_restore` driver on the CPU backend (tiny volumes,
no chip, no timing assertion): the flow, the result line's exact keys,
and a flipped shard byte making `correct` false."""

import os
import sys


sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_checks as checks  # noqa: E402
from bench_rehearsal import check_result_line, run_cell  # noqa: E402

TREE = checks.Tree(checks.ROOT)   # what a cell reports is read, not listed


def test_rehearse_seal_end_to_end_line():
    proc, result = run_cell("seal", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REHEARSAL" in proc.stdout
    check_result_line(result, trace=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == TREE.ends_of("seal") \
        >= {"bulk_rate", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    # every number compared is printed beside its limit
    assert proc.stdout.count("compared: {") >= 6


def test_rehearse_seal_traced_line():
    proc, result = run_cell("seal", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_result_line(result, trace=True)
    assert result["correct"] is True
    # per-layer metrics only; a reader with nothing to read (no TPU
    # plane in a CPU trace) leaves its metric out of the line
    assert "encode_read_s_per_gib" in result["metrics"]
    assert set(result["metrics"]) <= TREE.layers_of("seal")
    assert "bulk_rate" not in result["metrics"]
    assert "encode_kernel_roofline" not in result["metrics"]


def test_rehearse_seal_four_virtual_devices():
    proc, result = run_cell("seal-4chip", "--trace", "0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is True
    assert result["device"]["count"] == 4


def test_flipped_shard_byte_makes_correct_false():
    """The control: the harness's look for a chip skipped (rehearsal),
    the rest of a run driven, one byte of one shard file of the last
    seal altered where it lies."""
    proc, result = run_cell("seal", "--trace", "0", "--control", "shard_file")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CONTROL: one byte of" in proc.stdout
    assert result["correct"] is False
    assert '"shard_crc32c_differ_from_vif", "value": 1' in proc.stdout


def test_no_tpu_exits_non_zero_and_prints_no_result():
    proc, result = run_cell("put-get-open", "--trace", "0", rehearse=False)
    assert proc.returncode != 0
    assert result is None
    assert "no chip" in proc.stderr


def test_alone_in_a_directory_it_refuses(tmp_path):
    import shutil

    from bench_rehearsal import ROOT
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc, result = run_cell("seal", "--trace", "0", root=str(tmp_path),
                            rehearse=False)
    assert proc.returncode != 0 and result is None
    assert proc.stdout.strip() == ""
