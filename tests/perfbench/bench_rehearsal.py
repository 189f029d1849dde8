"""Shared by the rehearsal tests: one run of perfbench/run.py as a child
process, at the traffic file's `rehearse` size on the CPU backend.  A
rehearsal proves the flow, never the chip, and no test here asserts a
timing."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(cell, *extra, root=ROOT, seconds=2, seed=2147483777,
             rehearse=True):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["BENCH_RUN"] = "ignored-by-the-benchmark"
    argv = [sys.executable, os.path.join(root, "perfbench", "run.py"),
            "--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), *extra]
    if rehearse:
        argv.append("--rehearse")
    proc = subprocess.run(argv, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    return proc, result


def check_result_line(result, trace):
    keys = RESULT_KEYS | ({"breakdown"} if trace else set())
    assert set(result) == keys
    device = DEVICE_KEYS | ({"busy_s", "window_s"} if trace else set())
    assert set(result["device"]) == device
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # every number compared sits beside its limit, under the last key
    assert list(result)[-1] == "compared" and result["compared"]
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"}
    assert result["correct"] == all(
        c["value"] <= c["limit"] for c in result["compared"].values())
