"""The daemons of one run and the client's side of them.

Copied from `chip_smoke.py` (PR 21): the child-process start/stop, the
stdlib keep-alive HTTP client and the Prometheus text scrape.  The volume
server is started through `perfbench/volume_entry.py`, which calls
`weed.main([...])` in-process with the arguments a user passes, so that
the one process that holds the chip can also trace it.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot produce a result (as opposed to `correct: false`)."""


_tls = threading.local()


def request(addr: str, method: str, path: str, body: bytes | None = None,
            timeout: float = 120.0) -> tuple[int, bytes]:
    """One request over a per-thread keep-alive connection; a dropped
    idle connection is reopened once."""
    conns = _tls.__dict__.setdefault("conns", {})
    for attempt in (0, 1):
        conn = conns.get(addr)
        if conn is None:
            host, port = addr.rsplit(":", 1)
            conn = conns[addr] = http.client.HTTPConnection(
                host, int(port), timeout=timeout)
        try:
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (http.client.HTTPException, OSError):
            conn.close()
            conns.pop(addr, None)
            if attempt:
                raise
    raise AssertionError("unreachable")


def call(addr: str, path: str, payload: dict | None = None,
         method: str | None = None, timeout: float = 600.0) -> dict:
    """JSON in, JSON out; POST when there is a payload."""
    body = None if payload is None else json.dumps(payload).encode()
    method = method or ("GET" if payload is None else "POST")
    status, data = request(addr, method, path, body, timeout)
    if status != 200:
        raise BenchFailure(f"{method} {addr}{path} -> {status}: {data[:300]!r}")
    return json.loads(data) if data else {}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_SAMPLE_RE = re.compile(r'^(\w+)(?:\{(.*)\})? (\S+)$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def scrape(addr: str) -> list[tuple[str, dict, float]]:
    """`/metrics` as (name, labels, value) samples."""
    status, body = request(addr, "GET", "/metrics")
    if status != 200:
        raise BenchFailure(f"/metrics -> {status}")
    out = []
    for line in body.decode().splitlines():
        m = _SAMPLE_RE.match(line)
        if m:
            out.append((m.group(1), dict(_LABEL_RE.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


class Daemons:
    """Child processes of one run; stop() ends every one and waits."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.procs: list[tuple[str, subprocess.Popen]] = []

    def start(self, name: str, argv: list[str], env: dict):
        with open(os.path.join(self.logdir, f"{name}.log"), "wb") as logf:
            proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=logf,
                                    stderr=subprocess.STDOUT)
        self.procs.append((name, proc))

    def check_alive(self):
        for name, proc in self.procs:
            if proc.poll() is not None:
                raise BenchFailure(
                    f"{name} exited early with code {proc.returncode}")

    def stop(self) -> list[str]:
        """SIGTERM every child; returns the names that had to be killed."""
        stubborn = []
        for _, proc in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc in reversed(self.procs):
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                stubborn.append(name)
                proc.kill()
                proc.wait(timeout=30)
        return stubborn

    def tails(self, lines: int = 40) -> str:
        out = []
        for name, _ in self.procs:
            try:
                with open(os.path.join(self.logdir, f"{name}.log"),
                          errors="replace") as f:
                    tail = f.readlines()[-lines:]
            except OSError:
                continue
            out.append(f"--- {name}.log (last {len(tail)} lines) ---\n"
                       + "".join(tail))
        return "\n".join(out)


def wait_until(what: str, fn, timeout: float, daemons: Daemons):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        daemons.check_alive()
        try:
            value = fn()
            if value:
                return value
        except (OSError, http.client.HTTPException, BenchFailure) as e:
            last = e
        time.sleep(0.1)
    raise BenchFailure(f"timed out after {timeout:.0f}s waiting for {what}"
                       + (f" (last error: {last})" if last else ""))


class Control:
    """The harness's end of `volume_entry.py`'s control socket: one JSON
    line out, one JSON line back."""

    def __init__(self, path: str):
        self.path = path

    def ask(self, timeout: float = 120.0, **msg) -> dict:
        with socket.socket(socket.AF_UNIX) as s:
            s.settimeout(timeout)
            s.connect(self.path)
            s.sendall(json.dumps(msg).encode() + b"\n")
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
        reply = json.loads(buf or b"{}")
        if reply.get("error"):
            raise BenchFailure(f"volume_entry {msg.get('cmd')}: "
                               f"{reply['error']}")
        return reply


class Cluster:
    """One master and one volume server, as the configuration file says."""

    def __init__(self, config: dict, workdir: str, cache_dir: str,
                 rehearse: bool):
        self.config = config
        self.workdir = workdir
        self.daemons = Daemons(workdir)
        self.master = f"127.0.0.1:{free_port()}"
        self.volume = f"127.0.0.1:{free_port()}"
        self.vol_dir = os.path.join(workdir, "vol")
        self.control = Control(os.path.join(workdir, "control.sock"))
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        env.update(config["env"])
        if rehearse:
            env.update(config.get("rehearse_env", {}))
        self.env = env

    def start(self):
        os.makedirs(os.path.join(self.workdir, "master"))
        os.makedirs(self.vol_dir)
        fill = {"master": self.master, "master_port": self.master.rsplit(
            ":", 1)[1], "volume_port": self.volume.rsplit(":", 1)[1],
            "master_dir": os.path.join(self.workdir, "master"),
            "volume_dir": self.vol_dir}
        for d in self.config["daemons"]:
            args = [a.format(**fill) for a in d["args"]]
            if d["name"] == "volume":
                argv = [sys.executable, os.path.join(HERE, "volume_entry.py"),
                        "--control", self.control.path, "--", *args]
            else:
                argv = [sys.executable, os.path.join(ROOT, "weed.py"), *args]
            self.daemons.start(d["name"], argv, self.env)
            addr = self.master if d["name"] == "master" else self.volume
            probe = ("/cluster/status" if d["name"] == "master"
                     else "/admin/status")
            wait_until(d["name"], lambda: call(addr, probe), 120,
                       self.daemons)

    def device(self) -> dict:
        """The device as the volume server's own JAX reports it."""
        info = call(self.volume, "/admin/ec/recover_stats",
                    timeout=600).get("device")
        if not info:
            raise BenchFailure("the volume server found no JAX backend")
        return info
