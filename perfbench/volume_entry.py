#!/usr/bin/env python3
"""Launcher of the volume server for a benchmark run.

`python perfbench/volume_entry.py --control <socket> -- volume -port ...`
calls `weed.main([...])` in this process with exactly the arguments a user
passes to `weed.py`.  Only the process that holds the chip can trace it or
read its memory, so a helper thread answers on a Unix socket:
`trace_start`, `trace_stop` (jax.profiler around a slice of the window,
asked for in a `--trace 1` run only), `memory` (the fullest device's peak
bytes), `programs` (every executable JAX has built in this process,
compiled or loaded from the persistent cache, as JAX's own monitoring
events report them: what warm-up reached, and what was first built inside
the window) and `setenv` (one of the program's documented live settings,
which it reads from the environment at every call; a warm-up may set one
and puts it back before the window).  No program file changes; with
`--trace 0` the profiler is never started.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# JAX's event for one executable built: XLA compiled it, or the
# persistent cache gave it back.  Either way the caller waited for it.
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_built: list[list] = []    # [perf_counter at the end, name, seconds]


def _on_duration(event: str, seconds: float, **kwargs):
    if event == BUILD_EVENT:
        _built.append([time.perf_counter(), str(kwargs.get("fun_name")),
                       seconds])


def _memory() -> dict:
    import jax

    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"memory_peak_bytes": max(peaks), "per_device": peaks}


def _trace_start(logdir: str) -> dict:
    import jax

    options = jax.profiler.ProfileOptions()
    # the Python tracer hooks every call of a server that is mostly
    # Python; host TraceMe events and the device planes are what is read
    options.python_tracer_level = 0
    before = time.time_ns()
    jax.profiler.start_trace(logdir, profiler_options=options)
    return {"asked_ns": before, "started_ns": time.time_ns()}


def _trace_stop() -> dict:
    import jax

    before = time.time_ns()
    jax.profiler.stop_trace()
    return {"stopped_ns": before, "written_ns": time.time_ns()}


def _answer(msg: dict) -> dict:
    cmd = msg.get("cmd")
    if cmd == "memory":
        return _memory()
    if cmd == "trace_start":
        return _trace_start(msg["dir"])
    if cmd == "trace_stop":
        return _trace_stop()
    if cmd == "programs":
        return {"built": list(_built)}
    if cmd == "setenv":
        if msg.get("value") is None:
            os.environ.pop(msg["name"], None)
        else:
            os.environ[msg["name"]] = str(msg["value"])
        return {}
    return {"error": f"unknown command {cmd!r}"}


def _serve(path: str):
    srv = socket.socket(socket.AF_UNIX)
    srv.bind(path)
    srv.listen(4)
    while True:
        conn, _ = srv.accept()
        with conn:
            try:
                buf = b""
                while not buf.endswith(b"\n"):
                    chunk = conn.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
                reply = _answer(json.loads(buf))
            except Exception as e:  # the boundary: report, keep serving
                reply = {"error": f"{type(e).__name__}: {e}"}
            conn.sendall(json.dumps(reply).encode() + b"\n")


def main(argv: list[str]) -> int:
    if len(argv) < 4 or argv[0] != "--control" or argv[2] != "--":
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    sys.argv = [os.path.join(ROOT, "weed.py"), *argv[3:]]
    import jax.monitoring
    import weed

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    threading.Thread(target=_serve, args=(argv[1],), daemon=True,
                     name="perfbench-control").start()
    return weed.main(argv[3:]) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
