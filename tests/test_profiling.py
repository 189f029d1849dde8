"""Continuous-profiling acceptance: the folded-stack engine produces
flamegraph.pl-parseable collapsed stacks with thread and route tags,
every daemon type serves them on /debug/pprof, the heap endpoint arms
and reports tracemalloc on demand, the device timeline is queryable,
and `weed.py profile` merges a live cluster into one profile."""

import contextlib
import io
import re
import sys
import threading
import time

import pytest

from seaweedfs_tpu import profiling, tracing
from seaweedfs_tpu.rpc.http_rpc import call
from seaweedfs_tpu.stats import metrics as stats

# flamegraph.pl's line shape: anything, space, trailing integer count
FOLDED_RE = re.compile(r"^(.+) (\d+)$")


def parse_folded(text):
    """{stack: count} with every line strictly validated."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        match = FOLDED_RE.match(line)
        assert match, f"unparseable folded line: {line!r}"
        out[match.group(1)] = out.get(match.group(1), 0) \
            + int(match.group(2))
    return out


@contextlib.contextmanager
def spinner(name="prof-spin"):
    """A busy worker thread whose frames the sampler must catch."""
    stop = threading.Event()

    def _spin_marker_frame():
        while not stop.is_set():
            sum(i * i for i in range(500))

    t = threading.Thread(target=_spin_marker_frame, name=name)
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join()


class TestStackSampler:
    def test_burst_collects_folded_stacks(self):
        with spinner():
            text = profiling.profile_burst(
                0.3, 200, exclude={threading.get_ident()})
        stacks = parse_folded(text)
        assert stacks, "burst collected nothing"
        # full call stacks, not leaf frames: the worker's stack folds
        # its run() chain above the marker function
        marker = [s for s in stacks if "_spin_marker_frame" in s]
        assert marker, f"marker frame missing: {sorted(stacks)[:5]}"
        assert any(";" in s for s in marker), "no caller context folded"
        # thread-name tag leads the stack
        assert any(s.startswith("prof-spin;") for s in marker)

    def test_samples_tagged_with_active_route(self):
        sp = tracing.from_headers("GET /prof/route", "filer", {})
        stop = threading.Event()

        def routed_worker():
            prev = tracing.swap(sp)
            try:
                while not stop.is_set():
                    sum(i * i for i in range(500))
            finally:
                tracing.restore(prev)

        t = threading.Thread(target=routed_worker, name="routed")
        t.start()
        try:
            text = profiling.profile_burst(
                0.3, 200, exclude={threading.get_ident()})
        finally:
            stop.set()
            t.join()
        assert "routed;GET /prof/route;" in text, text[:500]
        # the route slot survives the swap/restore pair
        assert tracing.span_for_thread(t.ident) is None

    def test_child_spans_inherit_route(self):
        parent = tracing.from_headers("PUT /b/o", "s3", {})
        prev = tracing.swap(parent)
        try:
            child = tracing.start("s3.put_object")
        finally:
            tracing.restore(prev)
        assert child.route == "PUT /b/o"
        assert parent.route == "PUT /b/o"

    def test_stack_table_bounded(self):
        sampler = profiling.StackSampler(hz=100)
        sampler.samples = {f"stack-{i}": 1
                           for i in range(profiling.max_stacks())}
        sampler._sample_once(0)  # current threads all map to overflow
        assert len(sampler.samples) <= profiling.max_stacks() + 1
        assert sampler.truncated > 0
        assert profiling._TRUNCATED in sampler.samples

    def test_overhead_is_measured_not_guessed(self):
        sampler = profiling.StackSampler(hz=50)
        sampler.start()
        time.sleep(0.3)
        assert sampler.stop()
        assert sampler.total > 0
        assert 0.0 < sampler.overhead_ratio() <= 1.0  # busy lies in wall

    def test_gil_gauge_one_observation_a_tick(self):
        """The always-on kind probes the GIL every tick (a checksum in C
        that gives it up) and records what the probe took over the least
        it ever has: one observation a tick, none below zero, in the
        histogram and in snapshot()."""
        hist = stats.ProfilerGilWaitHistogram
        before = sum(hist._counts.get((), []))
        sampler = profiling.StackSampler(hz=200, publish=True)
        sampler.start()
        time.sleep(0.2)
        assert sampler.stop()
        assert sampler.gil_count == sampler._ticks > 0
        assert 0.0 <= sampler.gil_sum <= sampler.gil_count * \
            sampler.gil_max
        # the process's own always-on sampler may add its ticks too
        assert sum(hist._counts[()]) - before >= sampler.gil_count
        wait = sampler.snapshot()["gil_wait"]
        assert wait["count"] == sampler.gil_count
        assert 0.0 <= wait["mean"] <= wait["max"] == \
            round(sampler.gil_max, 9)
        # the floor is the probe alone: some tick sat on it, observed 0
        assert 0.0 < wait["floor"] == round(sampler.gil_floor, 9) < 0.1
        assert hist.buckets[0] == 10e-6 and hist.buckets[-1] == 0.1

    def test_burst_sampler_probes_nothing(self):
        hist = stats.ProfilerGilWaitHistogram
        running = profiling.profiler()   # 5 ticks a second, if mounted
        before = sum(hist._counts.get((), []))
        sampler = profiling.StackSampler(hz=200, publish=False)
        sampler.start()
        time.sleep(0.1)
        assert sampler.stop()
        assert sampler._ticks > 0 and sampler.gil_count == 0
        assert "gil_wait" not in sampler.snapshot()
        added = sum(hist._counts.get((), [])) - before
        assert added <= (2 if running is not None else 0)

    def test_gil_gauge_holds_the_wait_for_the_gil(self, monkeypatch):
        """Not a timing of the host: a thread that spins in pure Python
        takes the GIL while the probe checksums and gives it up only
        when asked, and a waiter asks after one switch interval.  So a
        probe beside it takes longer than the floor found alone by most
        of that interval, whatever else the host runs (a loaded host
        makes it longer still).  The probe is made long here (32 MiB,
        milliseconds) so that the spinner is sure to wake inside it:
        inside the 70 us of 256 KiB that is a race on a small host, as
        it is for any short blocking call."""
        monkeypatch.setattr(profiling, "_GIL_PROBE", bytes(32 << 20))
        sampler = profiling.StackSampler(hz=100, publish=True)
        sampler.start()
        # this thread sleeps through both phases: a poll would compete
        # for the GIL and be the one the probe hands it to
        time.sleep(0.3)             # alone: the floor is found
        alone = sampler.gil_count
        interval = sys.getswitchinterval()
        sys.setswitchinterval(0.1)
        try:
            with spinner():
                time.sleep(1.5)
        finally:
            sys.setswitchinterval(interval)
        assert sampler.stop(timeout=5)
        assert alone >= 3 and sampler.gil_count >= alone + 3
        assert sampler.gil_floor < 0.05
        assert sampler.gil_max >= 0.05
        assert sampler.overhead_ratio() < 0.5    # the wait is not `busy`

    def test_merge_folded_prefixes_and_sums(self):
        merged = parse_folded(profiling.merge_folded({
            "volume 127.0.0.1:8080": "main;read 3\n# comment\n",
            "filer 127.0.0.1:8888": "main;read 4\nmain;write 1\n",
        }))
        assert merged["volume 127.0.0.1:8080;main;read"] == 3
        assert merged["filer 127.0.0.1:8888;main;read"] == 4
        assert merged["filer 127.0.0.1:8888;main;write"] == 1


@pytest.fixture
def cluster(tmp_path):
    from seaweedfs_tpu.filer.server import FilerServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.s3api.server import S3ApiServer
    from seaweedfs_tpu.volume_server.server import VolumeServer

    master = MasterServer(port=0, pulse_seconds=0.2)
    master.start()
    d = tmp_path / "v0"
    d.mkdir()
    vs = VolumeServer([str(d)], master.address, port=0,
                      pulse_seconds=0.2)
    vs.start()
    vs.heartbeat_once()
    filer = FilerServer(master.address, port=0, chunk_size=1024)
    filer.start()
    s3 = S3ApiServer(filer, port=0)
    s3.start()
    # membership registration is asynchronous; the profile fan-out
    # discovers daemons via the master, so wait for both announcements
    deadline = time.time() + 5.0
    while time.time() < deadline:
        kinds = {k: call(master.address,
                         f"/cluster/nodes?type={k}")["cluster_nodes"]
                 for k in ("filer", "s3")}
        if all(kinds.values()):
            break
        time.sleep(0.05)
    yield master, vs, filer, s3
    s3.stop()
    filer.stop()
    vs.stop()
    master.stop()


class TestPprofEndpoints:
    def test_every_daemon_serves_parseable_profiles(self, cluster):
        """The tentpole acceptance bar: folded-stack profiles
        retrievable from all four daemon types."""
        master, vs, filer, s3 = cluster
        addrs = (master.address, vs.store.url, filer.address, s3.address)
        with spinner():
            for addr in addrs:
                raw = call(addr, "/debug/pprof/profile?seconds=0.3&hz=100",
                           parse=False)
                stacks = parse_folded(raw.decode())
                assert stacks, f"{addr}: empty profile"
                # each daemon's own threads are visible by name
                assert any(";" in s for s in stacks), addr

    def test_pprof_index_reports_always_on_state(self, cluster):
        master = cluster[0]
        idx = call(master.address, "/debug/pprof")
        assert "/debug/pprof/heap" in str(idx["endpoints"])
        assert idx["hz"] == profiling.prof_hz()
        assert idx["always_on"] is not None  # mount() started it
        # the process's GIL gauge rides the same reply
        assert set(idx["always_on"]["gil_wait"]) == {
            "floor", "count", "mean", "max"}

    def test_heap_arms_reports_and_disarms(self, cluster):
        import tracemalloc

        master = cluster[0]
        if tracemalloc.is_tracing():  # a prior test left it armed
            tracemalloc.stop()
        try:
            first = call(master.address, "/debug/pprof/heap",
                         parse=False).decode()
            assert "armed" in first
            blob = [bytes(1000) for _ in range(100)]
            report = call(master.address, "/debug/pprof/heap",
                          parse=False).decode()
            assert "allocation sites" in report
            assert re.search(r"size=\d", report), report[:300]
            del blob
        finally:
            last = call(master.address, "/debug/pprof/heap?stop=1",
                        parse=False).decode()
        assert "disarmed" in last
        assert not tracemalloc.is_tracing()

    def test_device_endpoint_shape(self, cluster):
        vs = cluster[1]
        profiling.record_device_batch(0.0123, units=4, k=7)
        dev = call(vs.store.url, "/debug/pprof/device")
        # XLA's cost estimate ("kernel_cost") left with PR 26: nothing
        # read it, and the roofline counts its own operations and bytes
        assert set(dev) == {"timeline", "pool"}
        batch = dev["timeline"][-1]
        assert batch["dispatch_ready_ms"] == pytest.approx(12.3)
        assert batch["units"] == 4 and batch["k"] == 7

    def test_weed_profile_merges_live_cluster(self, cluster):
        import weed

        master = cluster[0]
        out = io.StringIO()
        with spinner():
            with contextlib.redirect_stdout(out):
                weed.main(["profile", "-master", master.address,
                           "-seconds", "0.3", "-hz", "100"])
        text = out.getvalue()
        assert "# cluster cpu profile: 4/4 daemons" in text, \
            text.splitlines()[:3]
        stacks = parse_folded(text)
        prefixes = {s.split(";", 1)[0].split(" ")[0] for s in stacks}
        assert {"master", "volume", "filer", "s3"} <= prefixes, prefixes
