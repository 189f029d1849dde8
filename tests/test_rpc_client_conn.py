"""call()'s own HTTP/1.1 client (rpc/http_rpc._Connection): what it puts
on the wire, how it reads every framing of a reply, which connections go
back to the pool, and the one retry call() allows — against a scripted
raw-socket peer, and against RpcServer."""

import hashlib
import json
import socket
import threading
import time

import pytest

from seaweedfs_tpu.rpc import http_rpc
from seaweedfs_tpu.rpc.http_rpc import (Response, RpcError, RpcServer,
                                        _Connection, _ConnPool, _HttpError,
                                        _PeerClosed, call)
from seaweedfs_tpu.stats import metrics

CLOSE = object()    # a step of a script: close the connection
HOLD = object()     # a step of a script: answer nothing, keep it open


def reply(body: bytes = b"", status: str = "200 OK", headers=(),
          length: bool = True, version: str = "HTTP/1.1") -> bytes:
    lines = [f"{version} {status}"]
    if length:
        lines.append(f"Content-Length: {len(body)}")
    lines.extend(headers)
    return "\r\n".join(lines).encode() + b"\r\n\r\n" + body


class Peer:
    """A raw-socket server that reads whole requests and plays a script.

    `script(conn_no, req_no, request_bytes)` returns a list of steps: a
    bytes segment to send (segments leave 5 ms apart, so the client sees
    them in reads of their own), CLOSE, or HOLD."""

    def __init__(self, script):
        self.script = script
        self.requests = []          # (conn_no, request bytes), in order
        self.listener = socket.socket()
        self.listener.bind(("127.0.0.1", 0))
        self.listener.listen(16)
        self.addr = "127.0.0.1:%d" % self.listener.getsockname()[1]
        self.conns = []
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                conn, _ = self.listener.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.conns.append(conn)
            threading.Thread(target=self._serve, daemon=True,
                             args=(conn, len(self.conns) - 1)).start()

    @staticmethod
    def _read_request(conn):
        buf = b""
        while b"\r\n\r\n" not in buf:
            more = conn.recv(1 << 20)
            if not more:
                return None
            buf += more
        head, _, body = buf.partition(b"\r\n\r\n")
        need = 0
        for line in head.split(b"\r\n")[1:]:
            key, _, value = line.partition(b":")
            if key.lower() == b"content-length":
                need = int(value)
        while len(body) < need:
            more = conn.recv(1 << 20)
            if not more:
                return None
            body += more
        return head + b"\r\n\r\n" + body

    def _serve(self, conn, conn_no):
        req_no = 0
        try:
            while True:
                request = self._read_request(conn)
                if request is None:
                    return
                self.requests.append((conn_no, request))
                steps = self.script(conn_no, req_no, request)
                req_no += 1
                for i, step in enumerate(steps):
                    if step is CLOSE:
                        return
                    if step is HOLD:
                        time.sleep(3600)
                    if i:
                        time.sleep(0.005)
                    conn.sendall(step)
        except OSError:
            pass
        finally:
            conn.close()

    def close(self):
        self.listener.close()
        for conn in self.conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


@pytest.fixture
def peer_of():
    peers = []

    def make(script):
        if not callable(script):
            steps = script
            script = lambda conn_no, req_no, request: steps  # noqa: E731
        peers.append(Peer(script))
        return peers[-1]
    yield make
    for p in peers:
        p.close()


@pytest.fixture(autouse=True)
def own_pool(monkeypatch):
    """Every test has the pool to itself."""
    pool = _ConnPool()
    monkeypatch.setattr(http_rpc, "_POOL", pool)
    yield pool
    with pool._lock:
        idle = [c for lst in pool._idle.values() for c, _ in lst]
    for conn in idle:
        conn.close()


def pooled(pool, addr) -> int:
    with pool._lock:
        return len(pool._idle.get(addr, ()))


def exchange(addr, method="GET", path="/x", body=None, headers=None):
    """One request over a connection of its own, closed afterwards."""
    conn = _Connection(addr, 5.0)
    try:
        conn.request(method, path, body=body, headers=headers)
        return conn.getresponse()
    finally:
        conn.close()


# -- a counted body ------------------------------------------------------------

BIG = bytes(range(256)) * (4 << 12)     # 4 MiB, no two neighbours alike


@pytest.mark.parametrize("body", [b"", b"0123456789" * 110, BIG],
                         ids=["empty", "1100B", "4MiB"])
def test_a_counted_body_in_one_write_of_the_peer(peer_of, own_pool, body):
    peer = peer_of([reply(body, headers=["Content-Type: text/plain"])])
    assert call(peer.addr, "/x", parse=False) == body
    assert pooled(own_pool, peer.addr) == 1


def test_a_4_mib_body_in_many_segments(peer_of, own_pool):
    msg = reply(BIG)
    cuts = list(range(0, len(msg), 300_000)) + [len(msg)]
    peer = peer_of([msg[a:b] for a, b in zip(cuts, cuts[1:])])
    got = call(peer.addr, "/x", parse=False)
    assert type(got) is bytes
    assert hashlib.blake2b(got).digest() == hashlib.blake2b(BIG).digest()
    assert pooled(own_pool, peer.addr) == 1


SPLIT_MSG = reply(b"0123456789", headers=["Content-Type: text/plain",
                                         "X-One: 1"])


@pytest.mark.parametrize("cut", range(1, len(SPLIT_MSG)))
def test_head_and_body_split_at_every_boundary(peer_of, cut):
    peer = peer_of([SPLIT_MSG[:cut], SPLIT_MSG[cut:]])
    r = exchange(peer.addr)
    assert (r.status, r.read(), r.will_close) == (200, b"0123456789", False)
    assert r.headers["X-One"] == "1"
    assert r.headers.get("content-type") == "text/plain"


def test_a_counted_body_cut_short_is_an_error(peer_of):
    peer = peer_of([reply(b"x" * 100)[:-40], CLOSE])
    with pytest.raises(_HttpError, match="cut short"):
        exchange(peer.addr)


@pytest.mark.parametrize("msg", [
    b"HTTP/1.1 200 OK\r\nContent-Len", b"garbage\r\n\r\n",
    b"HTTP/2 200\r\n\r\n", b"HTTP/1.1 20 OK\r\n\r\n",
    b"HTTP/1.1 200 OK\r\nContent-Length: ten\r\n\r\n",
    b"HTTP/1.1 2\xb90 OK\r\nContent-Length: 0\r\n\r\n",
    b"HTTP/1.1 200 OK\r\nContent-Length: \xb2\r\n\r\n",
    b"HTTP/1.1 200 OK\r\nContent-Length: -1\r\n\r\n",
    b"HTTP/1.1 200 OK\r\nContent-Length: +3\r\n\r\nabc"],
    ids=["head_cut", "no_status_line", "version", "status", "length",
         "latin1_digit_in_status", "latin1_digit_in_length",
         "negative_length", "signed_length"])
def test_what_is_no_reply_is_an_error(peer_of, msg):
    peer = peer_of([msg, CLOSE])
    with pytest.raises(_HttpError):
        exchange(peer.addr)
    # and call() makes a transport failure of it, as of any other
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x")
    assert e.value.status == 503 and e.value.transport


def test_eof_before_a_reply_is_the_peer_s_close(peer_of):
    peer = peer_of([CLOSE])
    with pytest.raises(_PeerClosed):
        exchange(peer.addr)


# -- the other framings --------------------------------------------------------

CHUNKED = (b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n"
           b"Content-Type: application/octet-stream\r\n\r\n"
           b"5\r\nhello\r\n"
           b"1A;name=value\r\n" + b"abcdefghijklmnopqrstuvwxyz" + b"\r\n"
           b"0\r\nX-Trailer: t\r\n\r\n")


@pytest.mark.parametrize("cut", [None, 70, 93, 96, 100, 118, 135, 140,
                                 len(CHUNKED) - 3])
def test_a_chunked_reply(peer_of, own_pool, cut):
    steps = [CHUNKED] if cut is None else [CHUNKED[:cut], CHUNKED[cut:]]
    peer = peer_of(steps)
    assert call(peer.addr, "/x") == b"hello" + b"abcdefghijklmnopqrstuvwxyz"
    assert pooled(own_pool, peer.addr) == 1


def test_a_chunked_reply_of_large_chunks(peer_of):
    half = len(BIG) // 2
    msg = (b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n"
           + b"%x\r\n" % half + BIG[:half] + b"\r\n"
           + b"%X\r\n" % half + BIG[half:] + b"\r\n0\r\n\r\n")
    peer = peer_of([msg])
    assert call(peer.addr, "/x") == BIG


@pytest.mark.parametrize("tail", [b"5\r\nhel", b"5\r\nhello\r\n0\r\n",
                                  b"zz\r\nhello\r\n0\r\n\r\n",
                                  b"-1\r\nhello\r\n0\r\n\r\n",
                                  b"+5\r\nhello\r\n0\r\n\r\n",
                                  b"0x5\r\nhello\r\n0\r\n\r\n",
                                  b"\r\nhello\r\n0\r\n\r\n"],
                         ids=["in_a_chunk", "before_the_blank_line",
                              "no_size", "negative_size", "signed_size",
                              "prefixed_size", "empty_size"])
def test_a_chunked_reply_cut_short_is_an_error(peer_of, tail):
    peer = peer_of([b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n"
                    + tail, CLOSE])
    with pytest.raises(_HttpError):
        exchange(peer.addr)
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x")
    assert e.value.status == 503 and e.value.transport


@pytest.mark.parametrize("cut", [5000, 19, 17],
                         ids=["in_the_body", "behind_the_head",
                              "in_the_head"])
def test_a_body_that_ends_with_the_connection(peer_of, own_pool, cut):
    msg = reply(b"to the end " * 1000, length=False)
    peer = peer_of([msg[:cut], msg[cut:], CLOSE])
    assert call(peer.addr, "/x") == b"to the end " * 1000
    assert pooled(own_pool, peer.addr) == 0     # not pooled again


@pytest.mark.parametrize("method,status", [
    ("HEAD", "200 OK"), ("GET", "204 No Content"),
    ("GET", "304 Not Modified")])
def test_replies_that_carry_no_body(peer_of, own_pool, method, status):
    """A HEAD's Content-Length counts nothing: the next reply on the
    same connection must be read from its own first byte."""
    steps = [[b"HTTP/1.1 " + status.encode()
              + b"\r\nContent-Length: 1000\r\nETag: \"e\"\r\n\r\n"],
             [reply(b"second")]]
    peer = peer_of(lambda conn_no, req_no, request: steps[req_no])
    assert call(peer.addr, "/x", method=method) == b""
    assert pooled(own_pool, peer.addr) == 1
    assert call(peer.addr, "/x") == b"second"
    assert [c for c, _ in peer.requests] == [0, 0]    # one connection


@pytest.mark.parametrize("status", ["101 Switching Protocols",
                                    "102 Processing", "103 Early Hints"])
def test_an_interim_reply_handed_back_is_never_pooled(peer_of, own_pool,
                                                      status):
    """Only `100 Continue` is read past (as http.client does): another
    1xx comes back without a body, and the connection that may still
    carry the reply behind it is not used again."""
    steps = [[b"HTTP/1.1 " + status.encode()
              + b"\r\nContent-Length: 1000\r\n\r\n"],
             [reply(b"second")]]
    peer = peer_of(lambda conn_no, req_no, request: steps[conn_no])
    assert call(peer.addr, "/x") == b""
    assert pooled(own_pool, peer.addr) == 0
    assert call(peer.addr, "/x") == b"second"
    assert [c for c, _ in peer.requests] == [0, 1]    # a new connection


# -- 100 Continue is no reply --------------------------------------------------

CONTINUE = b"HTTP/1.1 100 Continue\r\n\r\n"
CONTINUE_MSG = CONTINUE + reply(b"the reply", headers=["X-Real: yes"])


@pytest.mark.parametrize("cut", [None, 10, len(CONTINUE) - 2, len(CONTINUE),
                                 len(CONTINUE) + 9, len(CONTINUE_MSG) - 4],
                         ids=["one_segment", "in_the_100", "in_its_blank",
                              "behind_the_100", "in_the_status_line",
                              "in_the_body"])
def test_a_100_continue_is_read_past(peer_of, own_pool, cut):
    """The reply is the one behind the interim `100 Continue`, whether
    both come in one segment or the 100 comes alone; the connection is
    left at a message's end and serves the next call."""
    first = [CONTINUE_MSG] if cut is None else \
        [CONTINUE_MSG[:cut], CONTINUE_MSG[cut:]]
    steps = [first, [reply(b"second")]]
    peer = peer_of(lambda conn_no, req_no, request: steps[req_no])
    conn = own_pool.get(peer.addr, 5.0)
    conn.request("PUT", "/x", body=b"b", headers={"Expect": "100-continue"})
    r = conn.getresponse()
    assert (r.status, r.read(), r.will_close) == (200, b"the reply", False)
    assert r.headers["X-Real"] == "yes"
    own_pool.put(peer.addr, conn)
    assert call(peer.addr, "/x") == b"second"
    assert [c for c, _ in peer.requests] == [0, 0]    # one connection


def test_two_100_continues_and_headers_of_their_own(peer_of):
    peer = peer_of([b"HTTP/1.1 100 Continue\r\nX-Interim: 1\r\n\r\n",
                    CONTINUE + reply(b"the reply")])
    r = exchange(peer.addr)
    assert (r.status, r.read()) == (200, b"the reply")
    assert "X-Interim" not in r.headers


def test_a_100_continue_and_nothing_behind_it_is_an_error(peer_of):
    """Not _PeerClosed: the peer has read the request, so call() must
    not take the close for an idle connection's and send it again."""
    peer = peer_of([CONTINUE, CLOSE])
    with pytest.raises(_HttpError, match="cut short") as e:
        exchange(peer.addr)
    assert not isinstance(e.value, OSError)
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x")
    assert e.value.status == 503 and e.value.transport


@pytest.mark.parametrize("msg", [
    reply(b"bye", headers=["Connection: close"]),
    reply(b"bye", headers=["connection: Close"]),
    reply(b"bye", version="HTTP/1.0")],
    ids=["close", "close_in_other_case", "http_1_0"])
def test_a_reply_that_ends_the_connection(peer_of, own_pool, msg):
    peer = peer_of([msg])
    assert exchange(peer.addr).will_close
    assert call(peer.addr, "/x") == b"bye"
    assert pooled(own_pool, peer.addr) == 0


def test_bytes_beyond_the_body_drop_the_connection(peer_of, own_pool):
    peer = peer_of([reply(b"body") + b"HTTP/1.1 200 OK\r\n"])
    assert call(peer.addr, "/x") == b"body"
    assert pooled(own_pool, peer.addr) == 0


def test_bytes_beyond_a_body_read_past_the_head_drop_it(peer_of, own_pool):
    msg = reply(b"b" * 20_000)
    peer = peer_of([msg[:100], msg[100:] + b"stray"])
    assert call(peer.addr, "/x") == b"b" * 20_000
    assert pooled(own_pool, peer.addr) == 0


def test_bytes_that_come_later_are_seen_before_reuse(peer_of, own_pool):
    steps = [[reply(b"one"), b"stray"], [reply(b"two")]]
    peer = peer_of(lambda conn_no, req_no, request: steps[conn_no])
    assert call(peer.addr, "/x") == b"one"
    time.sleep(0.1)
    assert call(peer.addr, "/x") == b"two"
    assert [c for c, _ in peer.requests] == [0, 1]    # a new connection


# -- headers -------------------------------------------------------------------

def test_duplicate_and_mixed_case_headers(peer_of):
    peer = peer_of([reply(b"{}", headers=[
        "content-TYPE:   application/json  ", "X-Dup: first",
        "x-dup: second", "X-Dup: third", "X-Folded: a", "\t b",
        "no colon here", "X-Empty:"])])
    r = exchange(peer.addr)
    h = r.headers
    assert h.get("Content-Type") == "application/json"
    assert h["CONTENT-type"] == "application/json"
    assert "content-length" in h and "X-Missing" not in h
    assert h.get("X-Missing", "d") == "d"
    # wire casing is kept, the first of a repeated name wins, as on the
    # server's side
    assert h.get("X-Dup") == "first" and dict.get(h, "x-dup") == "second"
    assert h["x-folded"] == "a b" and h["X-Empty"] == ""
    assert ("content-TYPE", "application/json") in r.getheaders()
    assert len(r.getheaders()) == 6


# -- what a request puts on the wire -------------------------------------------

class CountingSock:
    """The connection's socket with its sends recorded."""

    def __init__(self, sock):
        self._sock = sock
        self.sent = []

    def sendall(self, data):
        self.sent.append(data)
        self._sock.sendall(data)

    def __getattr__(self, name):
        return getattr(self._sock, name)


def counting(addr):
    conn = _Connection(addr, 5.0)
    conn.connect()
    conn.sock = CountingSock(conn.sock)
    return conn


def test_a_body_under_64_kib_leaves_with_its_head_in_one_send(peer_of):
    peer = peer_of([reply(b"ok")])
    conn = counting(peer.addr)
    sock = conn.sock
    body = b"r" * 60_000
    conn.request("POST", "/up?x=1", body=body,
                 headers={"Content-Type": "application/octet-stream",
                          "X-Deadline": "12.5"})
    assert conn.getresponse().read() == b"ok"
    conn.close()
    (_, seen), = peer.requests
    assert sock.sent == [seen]
    head, _, got = seen.partition(b"\r\n\r\n")
    assert got == body
    assert head.split(b"\r\n") == [
        b"POST /up?x=1 HTTP/1.1", b"Host: " + peer.addr.encode(),
        b"Accept-Encoding: identity", b"Content-Length: 60000",
        b"Content-Type: application/octet-stream", b"X-Deadline: 12.5"]


@pytest.mark.parametrize("size,sends", [(0, 1), (1100, 1), (60_000, 1),
                                        (70_000, 2), (4 << 20, 2)])
def test_how_many_sends_a_request_is(peer_of, size, sends):
    peer = peer_of([reply(b"ok")])
    conn = counting(peer.addr)
    sock = conn.sock
    body = BIG[:size]
    conn.request("POST", "/up", body=body)
    assert conn.getresponse().status == 200
    conn.close()
    assert len(sock.sent) == sends
    if sends == 2:
        assert sock.sent[1] is body     # the body itself, not a copy
    assert peer.requests[0][1].endswith(b"\r\n\r\n" + body)


@pytest.mark.parametrize("method,expected", [
    ("GET", None), ("HEAD", None), ("DELETE", None),
    ("POST", b"0"), ("PUT", b"0"), ("PATCH", b"0")])
def test_a_request_without_a_body_counts_one_where_http_client_does(
        peer_of, method, expected):
    peer = peer_of([reply(b"")])
    exchange(peer.addr, method=method)
    lines = peer.requests[0][1].split(b"\r\n")
    length = [l.partition(b": ")[2] for l in lines
              if l.startswith(b"Content-Length")]
    assert length == ([] if expected is None else [expected])


def test_the_caller_s_own_host_encoding_and_length_stand(peer_of):
    peer = peer_of([reply(b"")])
    exchange(peer.addr, method="POST", body=b"abc", headers={
        "host": "elsewhere", "ACCEPT-ENCODING": "gzip",
        "content-length": "3"})
    head = peer.requests[0][1].partition(b"\r\n\r\n")[0]
    assert head.split(b"\r\n") == [
        b"POST /x HTTP/1.1", b"host: elsewhere", b"ACCEPT-ENCODING: gzip",
        b"content-length: 3"]


@pytest.mark.parametrize("kwargs", [
    {"path": "/a b"}, {"path": "/a\r\nX-Injected: 1"}, {"path": "/é"},
    {"method": "GE T"},
    {"headers": {"X-A": "1\r\nX-Injected: 1"}},
    {"headers": {"X-A\nX-Injected": "1"}}, {"headers": {"X-A": "a\rb"}},
    {"headers": {"X-A": "€"}}],
    ids=["space", "crlf_in_path", "non_ascii_path", "method", "crlf_in_value", "lf_in_name", "cr_in_value",
         "unencodable_value"])
def test_a_message_that_could_carry_another_is_not_sent(peer_of, kwargs):
    peer = peer_of([reply(b"")])
    with pytest.raises(_HttpError):
        exchange(peer.addr, **kwargs)
    with pytest.raises(RpcError) as e:
        call(peer.addr, kwargs.get("path", "/x"),
             method=kwargs.get("method"), headers=kwargs.get("headers"))
    assert e.value.status == 503
    time.sleep(0.05)
    assert peer.requests == []


# -- call(): the one retry, and what never retries -----------------------------

def closes_on_the_second_request(answer_on_new=b"again"):
    """The first connection answers once and closes at the next request,
    unanswered, as a server that reaped it a moment before; every later
    connection answers."""
    def script(conn_no, req_no, request):
        if conn_no == 0:
            return [reply(b"first")] if req_no == 0 else [CLOSE]
        return [reply(answer_on_new)]
    return script


def methods_seen(peer):
    return [(c, r.split(b" ", 1)[0].decode()) for c, r in peer.requests]


def test_a_get_on_a_pooled_connection_the_peer_closed_goes_again_once(
        peer_of):
    peer = peer_of(closes_on_the_second_request())
    assert call(peer.addr, "/x") == b"first"
    assert call(peer.addr, "/x") == b"again"
    assert methods_seen(peer) == [(0, "GET"), (0, "GET"), (1, "GET")]


def test_a_post_that_may_have_been_delivered_is_never_sent_again(peer_of):
    peer = peer_of(closes_on_the_second_request())
    assert call(peer.addr, "/x") == b"first"
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x", raw=b"mutation")
    assert (e.value.status, e.value.transport) == (503, True)
    assert methods_seen(peer) == [(0, "GET"), (0, "POST")]


class BrokenSendSock(CountingSock):
    def sendall(self, data):
        raise BrokenPipeError(32, "Broken pipe")


@pytest.mark.parametrize("method", ["POST", "DELETE", "GET"])
def test_a_request_that_failed_in_its_send_goes_again_once(
        peer_of, own_pool, method):
    """A pooled connection whose send fails never delivered the request:
    any method may go again, on a connection outside the pool."""
    peer = peer_of([reply(b"done")])
    conn = _Connection(peer.addr, 5.0)
    conn.connect()
    conn.sock = BrokenSendSock(conn.sock)
    own_pool.put(peer.addr, conn)
    assert call(peer.addr, "/x", method=method,
                raw=b"m" if method == "POST" else None) == b"done"
    assert [m for _, m in methods_seen(peer)] == [method]
    assert conn.sock is None    # the broken one was closed


def test_a_new_connection_is_never_retried(peer_of):
    peer = peer_of([CLOSE])
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x")
    assert (e.value.status, e.value.transport) == (503, True)
    assert methods_seen(peer) == [(0, "GET")]


def test_the_retry_itself_is_not_retried(peer_of):
    def script(conn_no, req_no, request):
        return [reply(b"first")] if (conn_no, req_no) == (0, 0) else [CLOSE]
    peer = peer_of(script)
    assert call(peer.addr, "/x") == b"first"
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x")
    assert e.value.transport
    assert methods_seen(peer) == [(0, "GET"), (0, "GET"), (1, "GET")]


@pytest.mark.parametrize("method", ["GET", "POST"])
def test_a_timeout_is_a_transport_failure_and_never_retried(
        peer_of, own_pool, method):
    def script(conn_no, req_no, request):
        return [reply(b"first")] if req_no == 0 else [HOLD]
    peer = peer_of(script)
    assert call(peer.addr, "/x") == b"first"
    t0 = time.monotonic()
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/slow", method=method, timeout=0.2)
    assert 0.15 < time.monotonic() - t0 < 2.0
    assert (e.value.status, e.value.transport) == (503, True)
    assert (e.value.addr, e.value.route) == (peer.addr, "/slow")
    assert methods_seen(peer) == [(0, "GET"), (0, method)]
    assert pooled(own_pool, peer.addr) == 0


def test_nobody_listening_is_a_transport_failure():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        addr = "127.0.0.1:%d" % s.getsockname()[1]
    with pytest.raises(RpcError) as e:
        call(addr, "/x", timeout=2.0)
    assert (e.value.status, e.value.transport) == (503, True)


# -- call(): what it makes of a reply -------------------------------------------

def test_an_error_reply_carries_its_message_and_hints(peer_of, own_pool):
    peer = peer_of([reply(json.dumps({"error": "not the leader"}).encode(),
                          status="503 Service Unavailable", headers=[
                              "Content-Type: application/json",
                              "Retry-After: 2",
                              "X-Raft-Leader: 10.0.0.7:9333",
                              "X-Other: dropped"])])
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/cluster/x")
    err = e.value
    assert (str(err), err.status, err.transport) == \
        ("not the leader", 503, False)
    assert err.headers == {"Retry-After": "2",
                           "X-Raft-Leader": "10.0.0.7:9333"}
    assert (err.addr, err.route) == (peer.addr, "/cluster/x")
    assert pooled(own_pool, peer.addr) == 1     # an answer, not a failure


@pytest.mark.parametrize("status,body,message", [
    ("404 Not Found", b'{"error": "no such needle"}', "no such needle"),
    ("400 Bad Request", b"<Error>plain</Error>", "<Error>plain</Error>"),
    ("500 Internal Server Error", b'{"other": 1}', '{"other": 1}'),
    ("429 Too Many Requests", b"", "")])
def test_an_error_reply_s_message(peer_of, status, body, message):
    peer = peer_of([reply(body, status=status)])
    with pytest.raises(RpcError) as e:
        call(peer.addr, "/x")
    assert (str(e.value), e.value.status) == (message, int(status[:3]))
    assert e.value.headers == {}


@pytest.mark.parametrize("ctype,parse,expected", [
    ("application/json", True, {"a": 1}),
    ("application/json; charset=utf-8", True, {"a": 1}),
    ("application/json", False, b'{"a": 1}'),
    ("application/octet-stream", True, b'{"a": 1}'),
    (None, True, b'{"a": 1}')])
def test_json_is_parsed_unless_the_caller_wants_the_bytes(
        peer_of, ctype, parse, expected):
    headers = [f"Content-Type: {ctype}"] if ctype else []
    peer = peer_of([reply(b'{"a": 1}', headers=headers)])
    assert call(peer.addr, "/x", parse=parse) == expected


def test_an_empty_json_reply_is_an_empty_object(peer_of):
    peer = peer_of([reply(b"", headers=["Content-Type: application/json"])])
    assert call(peer.addr, "/x") == {}


# -- against RpcServer -----------------------------------------------------------

@pytest.fixture
def server():
    s = RpcServer(port=0, service_name="conn-test")
    s.add("GET", "/json", lambda req: {"path": req.path, "q": req.query})
    s.add("POST", "/echo", lambda req: Response(
        req.body, content_type=req.headers.get("Content-Type")
        or "application/octet-stream"))
    s.add("POST", "/digest", lambda req: {
        "size": len(req.body),
        "blake2b": hashlib.blake2b(req.body).hexdigest()})
    s.add("GET", "/big", lambda req: Response(BIG))
    s.add("GET", "/stream", lambda req: Response(
        iter([b"one-", b"two-", BIG[:100_000], b"-end"])))
    s.add("GET", "/empty", lambda req: Response(b"", 204))
    s.add("HEAD", "/big", lambda req: Response(
        b"", headers={"X-Size": str(len(BIG))}))

    def shed(req):
        raise RpcError("busy", 503, headers={"Retry-After": "7"})
    s.add("GET", "/shed", shed)
    s.add("POST", "/headers", lambda req: dict(req.headers))
    s.start()
    yield s
    s.stop()


def test_json_both_ways(server, own_pool):
    assert call(server.address, "/json?a=1") == {"path": "/json",
                                                 "q": {"a": "1"}}
    assert call(server.address, "/echo", {"k": [1, 2]}) == {"k": [1, 2]}
    assert call(server.address, "/echo", {"k": 1}, parse=False) == \
        b'{"k": 1}'
    assert pooled(own_pool, server.address) == 1


@pytest.mark.parametrize("size", [0, 1100, 65_000, 66_000, 4 << 20])
def test_a_body_up_and_down(server, size):
    body = BIG[:size]
    assert call(server.address, "/digest", raw=body) == {
        "size": size, "blake2b": hashlib.blake2b(body).hexdigest()}
    assert call(server.address, "/echo", raw=body, parse=False) == body


def test_a_4_mib_reply_and_its_head(server, own_pool):
    assert call(server.address, "/big", parse=False) == BIG
    assert call(server.address, "/big", method="HEAD") == b""
    assert call(server.address, "/empty") == b""
    assert call(server.address, "/json") == {"path": "/json", "q": {}}
    assert pooled(own_pool, server.address) == 1    # one connection did all


def test_a_streamed_reply_is_decoded(server, own_pool):
    assert call(server.address, "/stream") == \
        b"one-two-" + BIG[:100_000] + b"-end"
    assert pooled(own_pool, server.address) == 1


def test_the_server_s_error_headers_arrive(server):
    with pytest.raises(RpcError) as e:
        call(server.address, "/shed")
    assert (str(e.value), e.value.status) == ("busy", 503)
    assert e.value.headers == {"Retry-After": "7"}
    with pytest.raises(RpcError) as e:
        call(server.address, "/no/such/route")
    assert e.value.status == 404 and not e.value.transport


def test_what_the_server_reads_of_a_request_s_head(server):
    seen = call(server.address, "/headers", raw=b"xyz",
                headers={"X-Custom": "v", "Content-Type": "text/x"})
    assert seen["Host"] == server.address
    assert seen["Accept-Encoding"] == "identity"
    assert seen["Content-Length"] == "3"
    assert (seen["X-Custom"], seen["Content-Type"]) == ("v", "text/x")


def raw_exchange(server, request: bytes) -> bytes:
    """A request as written, and the reply to the connection's end."""
    host, port = server.address.split(":")
    with socket.create_connection((host, int(port)), 5.0) as s:
        s.sendall(request)
        parts = []
        while more := s.recv(65536):
            parts.append(more)
    return b"".join(parts)


def test_the_server_splits_a_head_as_the_client_does(server):
    """One splitter (_lean_headers) on both sides: first of a repeated
    name, wire casing, an obs-fold joined, a line without a name
    skipped."""
    out = raw_exchange(
        server, b"POST /headers HTTP/1.1\r\nhost: h\r\nContent-Length: 0\r\n"
        b"X-Dup: first\r\nx-dup: second\r\nX-Dup: third\r\nX-Folded: a\r\n"
        b"\t b\r\nno colon here\r\n: no name\r\nX-Empty:\r\n"
        b"Connection: close\r\n\r\n")
    assert out.startswith(b"HTTP/1.1 200 OK\r\n")
    seen = json.loads(out.split(b"\r\n\r\n", 1)[1])
    assert seen == {"host": "h", "Content-Length": "0", "X-Dup": "first",
                    "x-dup": "second", "X-Folded": "a b", "X-Empty": "",
                    "Connection": "close"}


@pytest.mark.parametrize("count,status", [(100, b"200"), (101, b"431")])
def test_the_server_reads_a_hundred_header_lines(server, count, status):
    lines = [b"Content-Length: 0", b"Connection: close"]
    lines += [b"X-%d: v" % i for i in range(count - len(lines))]
    out = raw_exchange(server, b"POST /headers HTTP/1.1\r\n"
                       + b"\r\n".join(lines) + b"\r\n\r\n")
    assert out.split(b" ", 2)[1] == status


def test_the_server_refuses_a_header_line_over_64_kib(server):
    out = raw_exchange(server, b"POST /headers HTTP/1.1\r\nX-Long: "
                       + b"v" * 65536 + b"\r\nContent-Length: 0\r\n\r\n")
    assert out.split(b" ", 2)[1] == b"431"


def test_the_server_s_100_continue_is_read_past(server, own_pool):
    """RpcServer answers `Expect: 100-continue` with an interim 100 of
    its own segment: the reply is the one behind it, and the connection
    goes back to the pool at a message's end."""
    for i in range(3):
        body = b"%d" % i * 1100
        assert call(server.address, "/echo", raw=body, parse=False,
                    headers={"Expect": "100-continue"}) == body
        assert call(server.address, "/json") == {"path": "/json", "q": {}}
    assert pooled(own_pool, server.address) == 1    # one connection did all


def test_the_server_s_100_continue_leaves_before_the_body_comes(server):
    """curl and boto hold a body back until the 100 (curl for a second):
    it must not wait in the handler's write buffer for the reply."""
    host, port = server.address.split(":")
    with socket.create_connection((host, int(port)), 5.0) as s:
        s.sendall(b"POST /echo HTTP/1.1\r\nHost: x\r\nContent-Length: 4\r\n"
                  b"Expect: 100-continue\r\n\r\n")
        assert s.recv(65536) == CONTINUE
        s.sendall(b"body")
        buf = b""
        while not buf.endswith(b"\r\n\r\nbody"):
            more = s.recv(65536)
            assert more, buf
            buf += more
        assert buf.startswith(b"HTTP/1.1 200 OK\r\n")


@pytest.mark.parametrize("expect", ["Expect", "expect"])
def test_a_prefork_relay_of_a_put_that_expects_100(server, own_pool,
                                                   expect):
    """A worker relays a curl / boto PUT: it has answered the client's
    Expect itself, so the header stays behind, the parent's reply is
    the PUT's own, and the sideband connection serves the next relay."""
    from seaweedfs_tpu.rpc import prefork
    server.add("PUT", "/headers", lambda req: Response(
        json.dumps(dict(req.headers, body=req.body.decode())).encode(),
        201, "application/json", {"ETag": '"e"'}))
    group = prefork.PreforkGroup(None, 2)
    for i in range(2):
        r = group.proxy(server.address, "PUT", "/headers?n=%d" % i,
                        b"payload-%d" % i,
                        http_rpc._LeanHeaders({
                            expect: "100-continue", "X-Amz-Date": "d",
                            "Content-Length": "9", "Host": "client-facing"}))
        seen = json.loads(r.body)
        assert (r.status, r.headers["ETag"]) == (201, '"e"')
        assert seen["body"] == "payload-%d" % i
        assert (seen["X-Amz-Date"], seen[prefork.FWD_HEADER]) == ("d", "1")
        assert not {"expect", "Expect"} & set(seen)
        assert seen["Host"] == server.address
    assert pooled(own_pool, server.address) == 1


def test_sixteen_callers_share_the_pool(server, own_pool):
    errors = []

    def caller(c):
        try:
            for i in range(50):
                body = b"%d-%d" % (c, i) * 50
                assert call(server.address, "/echo", raw=body,
                            parse=False) == body
        except Exception as e:     # reported on the test's thread
            errors.append(e)
    threads = [threading.Thread(target=caller, args=(c,))
               for c in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors and not any(t.is_alive() for t in threads)
    assert 1 <= pooled(own_pool, server.address) <= 16


def test_a_server_that_went_away_and_came_back(own_pool):
    """The pooled connection of a stopped server is seen closed before
    reuse (or retried once): the next call reaches the new server."""
    s = RpcServer(port=0)
    s.add("GET", "/who", lambda req: {"n": 1})
    s.start()
    addr = s.address
    assert call(addr, "/who") == {"n": 1}
    s.stop()
    with pytest.raises(RpcError) as e:
        call(addr, "/who", timeout=2.0)
    assert e.value.transport
    s2 = RpcServer(port=int(addr.rsplit(":", 1)[1]))
    s2.add("GET", "/who", lambda req: {"n": 2})
    s2.start()
    try:
        assert call(addr, "/who") == {"n": 2}
    finally:
        s2.stop()


# -- the counter ------------------------------------------------------------------

def calls_by_conn() -> dict:
    out = {}
    for line in metrics.REGISTRY.expose().splitlines():
        if line.startswith("SeaweedFS_rpc_client_calls_total{"):
            out[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
    return out


def test_the_counter_is_there_before_any_call():
    assert set(calls_by_conn()) == {"new", "reused", "retried"}


def test_new_once_then_reused(server):
    before = calls_by_conn()
    call(server.address, "/json")
    call(server.address, "/json")
    call(server.address, "/echo", raw=b"x")
    after = calls_by_conn()
    assert {k: after[k] - before[k] for k in after} == \
        {"new": 1, "reused": 2, "retried": 0}


def test_retried_when_the_pooled_socket_was_closed_between_two_calls(
        peer_of, monkeypatch):
    """The peer closes after its first answer; the peek before reuse is
    held off (it would see the close and open a new connection, which
    counts `new`), as when the close arrives between the peek and the
    send."""
    def script(conn_no, req_no, request):
        return [reply(b"first"), CLOSE] if conn_no == 0 else \
            [reply(b"again")]
    peer = peer_of(script)
    before = calls_by_conn()
    assert call(peer.addr, "/x") == b"first"
    time.sleep(0.1)
    monkeypatch.setattr(_ConnPool, "_dropped", staticmethod(lambda c: False))
    assert call(peer.addr, "/x") == b"again"
    after = calls_by_conn()
    assert {k: after[k] - before[k] for k in after} == \
        {"new": 1, "reused": 0, "retried": 1}


def test_a_close_seen_by_the_peek_counts_a_new_connection(peer_of):
    def script(conn_no, req_no, request):
        return [reply(b"first"), CLOSE] if conn_no == 0 else \
            [reply(b"again")]
    peer = peer_of(script)
    before = calls_by_conn()
    assert call(peer.addr, "/x") == b"first"
    time.sleep(0.1)
    assert call(peer.addr, "/x") == b"again"
    after = calls_by_conn()
    assert {k: after[k] - before[k] for k in after} == \
        {"new": 2, "reused": 0, "retried": 0}


def test_a_call_that_fails_is_counted_once(peer_of):
    peer = peer_of([CLOSE])
    before = calls_by_conn()
    with pytest.raises(RpcError):
        call(peer.addr, "/x")
    after = calls_by_conn()
    assert {k: after[k] - before[k] for k in after} == \
        {"new": 1, "reused": 0, "retried": 0}
