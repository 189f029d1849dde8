"""A `FileSlice` reply larger than the socket's send buffer.

`RpcServer`'s handlers carry a timeout, so their descriptors are
non-blocking, and `os.sendfile` on one raises `BlockingIOError` as soon as
the kernel's buffers are full.  `_reply_file` read that as a broken
transfer and cut the reply at the buffers' size: every needle above a few
MiB that a reader did not drain at once arrived short (PERF.md, PR 42).
It has to wait for the socket instead, for no longer than the handler's
timeout.  Driven over raw sockets with a small receive buffer, so that
what the kernel can hold for a reader that is not reading is far under
the needle's size on any host.
"""

import hashlib
import os
import socket
import time

import pytest

from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc.http_rpc import call
from seaweedfs_tpu.stats import metrics as stats
from seaweedfs_tpu.volume_server.server import VolumeServer

NEEDLE_BYTES = 8 << 20


@pytest.fixture
def served(tmp_path, monkeypatch):
    """A volume server with one 8 MiB needle on an open volume."""
    monkeypatch.delenv("WEED_SENDFILE", raising=False)
    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    vs = VolumeServer([str(tmp_path)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    vs.heartbeat_once()
    a = call(master.address, "/dir/assign")
    payload = os.urandom(NEEDLE_BYTES)      # incompressible: stored as is
    call(a["url"], "/" + a["fid"], raw=payload, method="POST",
         headers={"Content-Type": "application/octet-stream"})
    yield vs, a["url"], a["fid"], payload
    vs.stop()
    master.stop()


def _connect(addr: str, rcvbuf: int = 1 << 16) -> socket.socket:
    host, port = addr.rsplit(":", 1)
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(30)
    s.connect((host, int(port)))
    return s


def _ask(s: socket.socket, method: str, fid: str, extra: str = ""):
    s.sendall(f"{method} /{fid} HTTP/1.1\r\nHost: x\r\n{extra}\r\n".encode())


def _reply(s: socket.socket, has_body: bool = True):
    """(status, headers, body, whole): `whole` is false when the
    connection ended before Content-Length bytes had arrived."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        got = s.recv(1 << 16)
        if not got:
            return None, {}, buf, False
        buf += got
    head, _, body = buf.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    headers = {k.lower(): v.strip() for k, _, v in
               (line.partition(":") for line in lines[1:])}
    want = int(headers.get("content-length", 0)) if has_body else 0
    parts, have = [body], len(body)
    while have < want:
        got = s.recv(1 << 20)
        if not got:
            break
        parts.append(got)
        have += len(got)
    return int(lines[0].split()[1]), headers, b"".join(parts), have == want


def _counter(counter) -> float:
    return counter._values.get(("volume",), 0.0)


def test_slow_reader_gets_the_whole_needle_by_sendfile(served):
    vs, url, fid, payload = served
    sent0 = _counter(stats.GatewaySendfileBytesCounter)
    waits0 = _counter(stats.GatewaySendfileWaitsCounter)
    pread0 = _counter(stats.GatewayPreadBytesCounter)
    with _connect(url) as s:
        _ask(s, "GET", fid)
        time.sleep(0.2)         # the server fills every buffer meanwhile
        status, headers, body, whole = _reply(s)
        assert status == 200 and whole
        assert int(headers["content-length"]) == NEEDLE_BYTES
        assert hashlib.sha256(body).digest() == \
            hashlib.sha256(payload).digest()
        # the connection is still good: the same needle again, at once
        _ask(s, "GET", fid)
        status, _, body, whole = _reply(s)
        assert status == 200 and whole and body == payload
    assert _counter(stats.GatewaySendfileBytesCounter) - sent0 \
        == 2 * NEEDLE_BYTES
    assert _counter(stats.GatewaySendfileWaitsCounter) > waits0
    assert _counter(stats.GatewayPreadBytesCounter) == pread0


def test_pread_path_counts_its_bytes(served, monkeypatch):
    vs, url, fid, payload = served
    monkeypatch.setenv("WEED_SENDFILE", "0")
    # below WEED_SENDFILE_MIN or with sendfile off the volume server
    # answers from its buffered path, so ask _reply_file itself: a
    # FileSlice through a route of this server
    from seaweedfs_tpu.rpc.http_rpc import FileSlice, Response

    path = os.path.join(vs.store.locations[0].directory, "blob")
    with open(path, "wb") as f:
        f.write(payload)

    def route(req):
        return Response(FileSlice(os.open(path, os.O_RDONLY), 0,
                                  NEEDLE_BYTES, close_fd=True))

    vs.server.add("GET", "/test/blob", route)
    sent0 = _counter(stats.GatewaySendfileBytesCounter)
    pread0 = _counter(stats.GatewayPreadBytesCounter)
    with _connect(url) as s:
        _ask(s, "GET", "test/blob")
        time.sleep(0.2)
        status, _, body, whole = _reply(s)
    assert status == 200 and whole and body == payload
    assert _counter(stats.GatewayPreadBytesCounter) - pread0 == NEEDLE_BYTES
    assert _counter(stats.GatewaySendfileBytesCounter) == sent0


def test_reader_that_never_reads_is_cut_off_at_the_timeout(served):
    vs, url, fid, payload = served
    vs.server._handler_cls.timeout = 0.5    # connections made from now on
    httpd = vs.server.httpd
    with httpd._conns_lock:
        before = set(httpd._conns)
    stuck = _connect(url)
    _ask(stuck, "GET", fid)
    t0 = time.monotonic()
    # the handler gives up and its thread deregisters the connection
    mine = None
    while time.monotonic() - t0 < 20:
        time.sleep(0.1)
        with httpd._conns_lock:
            now = set(httpd._conns)
        if mine is None:
            mine = now - before or None
        elif not mine & now:
            break
    assert mine and time.monotonic() - t0 < 10, "the handler never let go"
    status, headers, body, whole = _reply(stuck)
    stuck.close()
    assert status == 200 and not whole
    assert 0 < len(body) < NEEDLE_BYTES
    assert payload.startswith(body)     # cut, never garbled
    # and the server serves the next request
    with _connect(url) as s:
        _ask(s, "GET", fid)
        status, _, body, whole = _reply(s)
    assert status == 200 and whole and body == payload


def test_head_and_range_of_the_same_needle_as_before(served):
    vs, url, fid, payload = served
    with _connect(url) as s:
        _ask(s, "HEAD", fid)
        status, headers, body, _ = _reply(s, has_body=False)
        assert status == 200 and body == b""
        assert int(headers["content-length"]) == NEEDLE_BYTES
        lo, hi = 1_000_003, 6_000_000       # 5 MB: above the buffers too
        _ask(s, "GET", fid, f"Range: bytes={lo}-{hi}\r\n")
        time.sleep(0.2)
        status, headers, body, whole = _reply(s)
        assert status == 206 and whole
        assert headers["content-range"] == \
            f"bytes {lo}-{hi}/{NEEDLE_BYTES}"
        assert body == payload[lo:hi + 1]
        _ask(s, "GET", fid, "Range: bytes=-70000\r\n")
        status, _, body, whole = _reply(s)
        assert status == 206 and whole and body == payload[-70000:]
