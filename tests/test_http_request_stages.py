"""A request's life around its handler, as `RpcServer` times it: the
stages `http.read` / `http.handle` / `http.reply` of a timed request (a
sampled one, or any under a profiler session) as counters, as child spans
and as annotations, and the one count an untimed request costs.  No test
here asserts a speed: where a delay is put in by hand, the assertion is
on which timer holds it."""

import io
import itertools
import socket
import time

import pytest

from seaweedfs_tpu import tracing
from seaweedfs_tpu.rpc import http_rpc, prefork
from seaweedfs_tpu.rpc.http_rpc import UNROUTED, Response, RpcServer
from seaweedfs_tpu.stats import metrics as stats

SERVICE = "stages-under-test"
_serial = itertools.count()
DELAY = 0.08
STAGE_KEYS = ("read_seconds", "handle_seconds", "reply_seconds",
              "request_seconds")


@pytest.fixture
def server(monkeypatch):
    """Two routes, two methods; nothing is sampled unless a request
    brings the header.  The counters are the process's, keyed by
    service: each test's server has a name of its own."""
    monkeypatch.setenv("WEED_TRACE_SAMPLE", "0")
    tracing.RECORDER.reset()
    srv = RpcServer(service_name=f"{SERVICE}-{next(_serial)}")

    def get_obj(req):
        with tracing.span("needle.read"):
            return Response(bytes(int(req.param("n") or 10)))

    srv.add("GET", "/obj", get_obj)
    srv.add("POST", "/obj", lambda req: {"n": len(req.body)})
    srv.add("GET", "/other", lambda req: {"ok": True})
    srv.add("POST", "/other", lambda req: {"ok": True})
    srv.start()
    yield srv
    srv.stop()


def _exchange(srv, head: bytes, body: bytes = b"", pause: float = 0.0
              ) -> bytes:
    """One request on a connection of its own, the body `pause` seconds
    behind the head; returns the whole reply."""
    with socket.create_connection((srv.host, srv.port), timeout=10) as s:
        s.sendall(head)
        if body:
            time.sleep(pause)
            s.sendall(body)
        reply = b""
        while True:
            chunk = s.recv(1 << 16)
            if not chunk:
                return reply
            reply += chunk


def _request(srv, method: str, path: str, body: bytes = b"",
             trace_id: str = "", pause: float = 0.0) -> bytes:
    head = f"{method} {path} HTTP/1.1\r\nHost: x\r\nConnection: close\r\n"
    if body:
        head += f"Content-Length: {len(body)}\r\n"
    if trace_id:
        head += f"X-Trace-Id: {trace_id}\r\nX-Trace-Sampled: 1\r\n"
    reply = _exchange(srv, (head + "\r\n").encode(), body, pause)
    assert reply.startswith(b"HTTP/1.1 200"), reply[:200]
    return reply


def _snap(srv) -> dict:
    """{(route, method): counters} of the server's service."""
    return {key[1:]: row
            for key, row in http_rpc.REQUEST_STAGES.snapshot().items()
            if key[0] == srv.service_name}


def _row(srv, route: str, method: str) -> dict:
    """The counters of one key once the request's thread has added them
    (the reply reaches the client before `http.reply` ends)."""
    return _snap(srv).get(
        (route, method), dict.fromkeys(("requests", "timed_requests")
                                       + STAGE_KEYS, 0))


def _wait_timed(srv, route: str, method: str, timed: int) -> dict:
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        row = _row(srv, route, method)
        if row["timed_requests"] >= timed:
            return row
        time.sleep(0.005)
    raise AssertionError(f"{method} {route}: {_row(srv, route, method)}")


def _tree(trace_id: str, spans: int) -> dict:
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        tree = tracing.RECORDER.get(trace_id)
        if tree is not None and tree["spans"] >= spans:
            return tree
        time.sleep(0.005)
    raise AssertionError(tracing.RECORDER.get(trace_id))


# -- (a) the spans of a sampled request ---------------------------------------

def test_sampled_get_has_its_three_stages_under_the_server_span(server):
    _request(server, "GET", "/obj", trace_id="a" * 16)
    tree = _tree("a" * 16, 5)
    (root,) = tree["tree"]
    assert root["name"] == "GET /obj"
    assert root["service"] == server.service_name
    kids = {c["name"]: c for c in root["children"]}
    assert set(kids) == {"http.read", "http.handle", "http.reply"}
    # the handler's own span hangs under http.handle, not the server span
    assert [c["name"] for c in kids["http.handle"]["children"]] == \
        ["needle.read"]
    assert kids["http.read"]["children"] == []
    assert kids["http.reply"]["children"] == []
    # one measurement, two uses: the spans' durations are the counters
    row = _wait_timed(server, "/obj", "GET", 1)
    for stage in ("read", "handle", "reply"):
        assert kids["http." + stage]["duration_ms"] == pytest.approx(
            row[stage + "_seconds"] * 1e3, abs=2e-3)
    assert kids["http.read"]["start"] <= kids["http.handle"]["start"] \
        <= kids["http.reply"]["start"]
    # no fourth, hidden stage: what lies between them is statements
    parts = sum(row[k] for k in STAGE_KEYS[:3])
    assert parts <= row["request_seconds"] <= parts + 0.01


# -- (b) what an untimed request costs ----------------------------------------

def test_unsampled_request_is_counted_and_not_timed(server, monkeypatch):
    built = []
    real_stage = tracing.stage.__init__
    real_clock = http_rpc._TimedRequest.__init__

    def stage_init(self, name, *a, **kw):
        built.append(name)
        real_stage(self, name, *a, **kw)

    def clock_init(self, *a, **kw):
        built.append("_TimedRequest")
        real_clock(self, *a, **kw)

    monkeypatch.setattr(tracing.stage, "__init__", stage_init)
    monkeypatch.setattr(http_rpc._TimedRequest, "__init__", clock_init)
    _request(server, "GET", "/obj", trace_id="b" * 16)
    before = _wait_timed(server, "/obj", "GET", 1)
    del built[:]
    for _ in range(3):
        _request(server, "GET", "/obj")
    after = _row(server, "/obj", "GET")
    assert after["requests"] == before["requests"] + 3
    assert after["timed_requests"] == before["timed_requests"]
    for key in STAGE_KEYS:
        assert after[key] == before[key], key
    assert built == []


def test_a_look_at_the_count_does_not_count(server):
    _request(server, "GET", "/other")
    assert [_row(server, "/other", "GET")["requests"] for _ in range(3)] \
        == [1, 1, 1]


# -- (c) which timers hold the reply's send -----------------------------------

class _SlowSocket(io.RawIOBase):
    """The handler's socket, every send `DELAY` seconds late."""

    def __init__(self, sock):
        self._sock = sock

    def writable(self):
        return True

    def write(self, data):
        time.sleep(DELAY)
        self._sock.sendall(data)
        return len(data)


def _hop_sum(srv, route: str) -> float:
    return stats.RpcHopHistogram._sums.get(
        ("client", srv.service_name, route), 0.0)


@pytest.mark.parametrize("nbytes,in_hop", [(32 << 10, False),
                                           (100 << 10, True)],
                         ids=["under_the_buffer", "over_the_buffer"])
def test_the_reply_s_send_is_in_http_reply_and_in_the_hop_only_when_large(
        server, monkeypatch, nbytes, in_hop):
    handler = server._handler_cls
    real_setup = handler.setup

    def setup(self):
        real_setup(self)
        self.wfile = io.BufferedWriter(_SlowSocket(self.connection),
                                       self.wbufsize)

    monkeypatch.setattr(handler, "setup", setup)
    hop0 = _hop_sum(server, "/obj")
    reply = _request(server, "GET", f"/obj?n={nbytes}", trace_id="c" * 16)
    assert len(reply.split(b"\r\n\r\n", 1)[1]) == nbytes
    row = _wait_timed(server, "/obj", "GET", 1)
    assert row["reply_seconds"] >= DELAY
    assert row["request_seconds"] >= row["reply_seconds"]
    assert row["handle_seconds"] < DELAY
    hop = _hop_sum(server, "/obj") - hop0
    if in_hop:      # written through: the hop's timer holds the send too
        assert hop >= DELAY
    else:           # buffered: the send is the flush behind _dispatch
        assert hop < DELAY


# -- (d) a PUT's body ---------------------------------------------------------

def test_a_put_s_body_read_is_inside_http_read(server):
    _request(server, "POST", "/obj", body=b"y" * 4096, trace_id="d" * 16,
             pause=DELAY)
    row = _wait_timed(server, "/obj", "POST", 1)
    assert row["read_seconds"] >= DELAY * 0.9
    assert row["handle_seconds"] < DELAY * 0.5
    kids = {c["name"]: c for c in
            _tree("d" * 16, 4)["tree"][0]["children"]}
    assert kids["http.read"]["duration_ms"] >= DELAY * 900


# -- (e) the annotations of a profiler session --------------------------------

class _Annotation:
    names: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        _Annotation.names.append(self.name)
        return self

    def __exit__(self, *exc):
        _Annotation.names.append("/" + self.name)
        return False


def test_a_session_annotates_read_and_reply_and_never_handle(server,
                                                             monkeypatch):
    got = {}

    def staged(req):
        with tracing.stage("ec.read.shard", got.__setitem__, "shard"):
            return {"ok": True}

    server.add("GET", "/staged", staged)
    monkeypatch.setattr(tracing, "_trace_annotation", _Annotation)
    _Annotation.names = []
    _request(server, "GET", "/staged")   # unsampled: the session times it
    row = _wait_timed(server, "/staged", "GET", 1)
    assert row["requests"] == 1
    # each opened and closed in its turn; the handler's own stage between
    assert _Annotation.names == [
        "http.read", "/http.read", "ec.read.shard", "/ec.read.shard",
        "http.reply", "/http.reply"]
    assert "shard" in got
    # unsampled: counters and annotations, no span of the request kept
    assert tracing.RECORDER.index() == []


def test_a_request_that_fails_under_a_session_leaves_no_annotation_open(
        server, monkeypatch):
    monkeypatch.setattr(tracing, "_trace_annotation", _Annotation)
    _Annotation.names = []
    # an HTTP/0.9 line: the stdlib answers with the error's body alone
    assert b"Error code: 400" in _exchange(server, b"GARBAGE\r\n\r\n")
    deadline = time.monotonic() + 5
    while _Annotation.names != ["http.read", "/http.read"] and \
            time.monotonic() < deadline:
        time.sleep(0.005)
    assert _Annotation.names == ["http.read", "/http.read"]


# -- (f) the keys -------------------------------------------------------------

def test_routes_and_methods_have_their_own_keys(server, monkeypatch):
    for i, (method, path) in enumerate([("GET", "/obj"), ("POST", "/obj"),
                                        ("GET", "/other"),
                                        ("POST", "/other")]):
        _request(server, method, path,
                 body=b"z" if method == "POST" else b"",
                 trace_id=f"f{i}".ljust(16, "0"))
        _wait_timed(server, path, method, 1)
    snap = _snap(server)
    assert set(snap) == {("/obj", "GET"), ("/obj", "POST"),
                         ("/other", "GET"), ("/other", "POST")}
    for row in snap.values():
        assert row["requests"] == row["timed_requests"] == 1
        assert row["request_seconds"] > 0

    # a request line nobody can parse: counted, in no route's mean
    assert b"Error code: 400" in _exchange(server, b"GARBAGE\r\n\r\n")
    # a method no route table has
    assert b" 501 " in _exchange(
        server, b"BREW /obj HTTP/1.1\r\nHost: x\r\n"
                b"Connection: close\r\n\r\n")[:40]

    # a prefork worker's mutation goes to the parent: counted only
    class Group:
        def forward_to_parent(self, method, path, body, headers):
            return Response(b"forwarded")

    monkeypatch.setattr(server, "_prefork", Group())
    monkeypatch.setattr(prefork, "is_worker", lambda: True)
    assert _request(server, "POST", "/obj", body=b"z",
                    trace_id="f" * 16).endswith(b"forwarded")
    monkeypatch.undo()

    snap = _snap(server)
    assert snap[(UNROUTED, UNROUTED)]["requests"] == 2
    assert snap[(UNROUTED, "POST")]["requests"] == 1
    for key in ((UNROUTED, UNROUTED), (UNROUTED, "POST")):
        assert snap[key]["timed_requests"] == 0
        assert all(snap[key][k] == 0 for k in STAGE_KEYS)
    assert snap[("/obj", "POST")]["requests"] == 1


def test_the_scrape_brings_the_families_up(server):
    """Two scrapes around two requests."""
    server.add("GET", "/metrics", stats.metrics_handler)
    service = server.service_name

    def scrape() -> dict:
        text = _request(server, "GET", "/metrics").decode()
        assert "ends before the reply's flush" in text  # rpc_hop_seconds
        return {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
                for ln in text.splitlines()
                if f'service="{service}"' in ln and 'route="/obj"' in ln}

    before = scrape()
    _request(server, "GET", "/obj", trace_id="e" * 16)
    _request(server, "GET", "/obj")
    _wait_timed(server, "/obj", "GET", 1)
    after = scrape()
    key = f'{{service="{service}",route="/obj",method="GET",'
    reqs = "SeaweedFS_rpc_server_requests_total" + key
    secs = "SeaweedFS_rpc_server_stage_seconds" + key

    def grew(name: str) -> float:
        return after[name] - before.get(name, 0.0)

    assert grew(reqs + 'requests="all"}') == 2
    assert grew(reqs + 'requests="timed"}') == 1
    assert {k for k in after if k.startswith(secs)} == {
        secs + f'stage="{s}"}}' for s in ("read", "handle", "reply",
                                          "request")}
    assert grew(secs + 'stage="request"}') > 0


def test_servers_of_one_service_count_into_one_row():
    """Daemons of one name in one process, one after another (tests) or
    side by side: one row, and a stopped server's requests stay in it,
    so nothing exported runs backwards."""
    service = f"{SERVICE}-{next(_serial)}"
    counted = []
    for _ in range(2):
        srv = RpcServer(service_name=service)
        srv.add("GET", "/obj", lambda req: b"x")
        srv.start()
        _request(srv, "GET", "/obj")
        srv.stop()
        http_rpc.REQUEST_STAGES.export()
        counted.append(stats.RpcServerRequestsCounter._values[
            (service, "/obj", "GET", "all")])
    assert counted == [1, 2]
