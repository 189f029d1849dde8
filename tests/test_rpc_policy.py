"""Unified outbound RPC policy: idempotency classes, jittered backoff,
retry budget, circuit breakers, deadline propagation, hedging, and the
MasterClient failover order — all on fake clocks / injected faults, no
real sleeps."""

import http.client
import threading
import time

import pytest

from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.rpc import policy
from seaweedfs_tpu.rpc.http_rpc import (DEADLINE_HEADER, RpcError, call,
                                        current_deadline, deadline_scope)
from seaweedfs_tpu.util import faults
from seaweedfs_tpu.volume_server.server import _RequestShedder
from seaweedfs_tpu.wdclient.masterclient import MasterClient


@pytest.fixture(autouse=True)
def clean_state():
    faults.REGISTRY.clear()
    policy.BREAKERS.reset()
    yield
    faults.REGISTRY.clear()
    policy.BREAKERS.reset()


@pytest.fixture
def no_sleep(monkeypatch):
    """Record every backoff the policy layer would take, sleep never."""
    slept = []
    monkeypatch.setattr(policy, "sleep", slept.append)
    monkeypatch.setattr(faults.REGISTRY, "sleep", lambda s: None)
    return slept


@pytest.fixture
def master():
    m = MasterServer(port=0, pulse_seconds=0.2)
    m.start()
    yield m
    m.stop()


class TestClassification:
    def test_idempotency(self):
        assert policy.is_idempotent("GET", "/3,0101f0")
        assert policy.is_idempotent("HEAD", "/3,0101f0")
        assert not policy.is_idempotent("POST", "/3,0101f0")
        assert not policy.is_idempotent("DELETE", "/3,0101f0")
        # replication replays dedup on the far side -> safe to resend
        assert policy.is_idempotent("POST", "/3,0101f0?type=replicate")
        assert policy.is_idempotent("POST", "/dir/lookup?volumeId=3")
        assert not policy.is_idempotent("POST", "/dir/assign")

    def test_retryable(self):
        assert policy.retryable(RpcError("x", 503))
        assert policy.retryable(RpcError("x", 429))
        assert policy.retryable(RpcError("x", 200, transport=True))
        assert not policy.retryable(RpcError("x", 404))
        assert not policy.retryable(RpcError("x", 403))
        assert not policy.retryable(ValueError("x"))


class TestBackoffAndBudget:
    def test_full_jitter_backoff(self):
        up = lambda: 1.0
        assert policy.backoff_delay(1, base=0.1, cap=9, rand=up) == 0.1
        assert policy.backoff_delay(3, base=0.1, cap=9, rand=up) == \
            pytest.approx(0.4)
        assert policy.backoff_delay(9, base=0.1, cap=2.0, rand=up) == 2.0
        assert policy.backoff_delay(5, base=0.1, cap=9,
                                    rand=lambda: 0.0) == 0.0

    def test_retry_budget_bucket(self):
        b = policy.RetryBudget(ratio=0.5, cap=2.0)
        assert b.try_spend() and b.try_spend()  # starts full
        assert not b.try_spend()                # dry
        b.on_request()
        assert not b.try_spend()                # 0.5 token: still < 1
        b.on_request()
        assert b.try_spend()

    def test_budget_capped(self):
        b = policy.RetryBudget(ratio=1.0, cap=2.0)
        for _ in range(100):
            b.on_request()
        assert b.tokens == 2.0


class TestBreaker:
    def test_state_machine_on_fake_clock(self, monkeypatch):
        clock = [1000.0]
        monkeypatch.setattr(policy, "now", lambda: clock[0])
        br = policy.Breaker("a:1", failures=2, open_secs=5.0)
        assert br.allow() and br.state == policy.CLOSED
        br.on_failure()
        assert br.allow()  # one failure: still closed
        br.on_failure()
        assert br.state == policy.OPEN
        assert not br.allow()  # fail fast, no socket
        clock[0] += 5.1
        assert br.allow()       # this caller is the half-open probe
        assert br.state == policy.HALF_OPEN
        assert not br.allow()   # one probe at a time
        br.on_failure()         # probe failed: back to open
        assert br.state == policy.OPEN and not br.allow()
        clock[0] += 5.1
        assert br.allow()
        br.on_success()
        assert br.state == policy.CLOSED and br.allow()

    def test_success_resets_failure_streak(self, monkeypatch):
        monkeypatch.setattr(policy, "now", lambda: 0.0)
        br = policy.Breaker("a:1", failures=3)
        br.on_failure()
        br.on_failure()
        br.on_success()
        br.on_failure()
        br.on_failure()
        assert br.state == policy.CLOSED


class TestCallPolicy:
    def test_retries_through_transient_injected_errors(self, master,
                                                       no_sleep):
        faults.REGISTRY.configure(
            "error,status=503,times=2,side=client,route=/dir/status*")
        r = policy.call_policy(master.address, "/dir/status",
                               method="GET")
        assert isinstance(r, dict)
        assert len(no_sleep) == 2  # two backoffs, zero real sleeps

    def test_permanent_error_never_retries(self, master, no_sleep):
        faults.REGISTRY.configure(
            "error,status=404,side=client,route=/dir/status*")
        with pytest.raises(RpcError) as e:
            policy.call_policy(master.address, "/dir/status",
                               method="GET")
        assert e.value.status == 404
        assert no_sleep == []
        assert faults.REGISTRY.rules[0].fires == 1

    def test_dry_budget_stops_retries(self, master, no_sleep):
        faults.REGISTRY.configure(
            "error,status=503,side=client,route=/dir/status*")
        with pytest.raises(RpcError) as e:
            policy.call_policy(
                master.address, "/dir/status", method="GET",
                budget=policy.RetryBudget(ratio=0.0, cap=0.0))
        assert e.value.status == 503
        assert no_sleep == []  # budget is checked before any backoff
        assert faults.REGISTRY.rules[0].fires == 1

    def test_breaker_opens_and_fails_fast(self, no_sleep):
        dst = "127.0.0.1:45678"
        faults.REGISTRY.configure(f"reset,dst={dst}")
        for _ in range(5):  # default WEED_BREAKER_FAILURES
            with pytest.raises(RpcError):
                policy.call_policy(dst, "/x", method="GET", retries=0)
        assert policy.BREAKERS.get(dst).state == policy.OPEN
        with pytest.raises(RpcError) as e:
            policy.call_policy(dst, "/x", method="GET", retries=0)
        assert "circuit open" in str(e.value)
        assert faults.REGISTRY.rules[0].fires == 5  # no sixth attempt


class TestDeadline:
    def test_scope_never_extends_inherited(self):
        with deadline_scope(timeout=1.0):
            outer = current_deadline()
            with deadline_scope(timeout=100.0):
                assert current_deadline() == outer
        assert current_deadline() is None

    def test_client_refuses_expired_deadline(self):
        with deadline_scope(absolute=time.time() - 1):
            with pytest.raises(RpcError) as e:
                call("127.0.0.1:1", "/x")
        assert e.value.status == 504

    def test_server_rejects_expired_work(self, master):
        with pytest.raises(RpcError) as e:
            call(master.address, "/dir/status",
                 headers={DEADLINE_HEADER: f"{time.time() - 5:.6f}"})
        assert e.value.status == 504
        assert "deadline exceeded before" in str(e.value)

    def test_live_deadline_still_serves(self, master):
        with deadline_scope(timeout=30.0):
            assert isinstance(call(master.address, "/dir/status"), dict)


class TestHedging:
    def test_single_attempt_runs_inline(self):
        assert policy.hedged("/k", [lambda: 41 + 1]) == 42

    def test_no_attempts_rejected(self):
        with pytest.raises(ValueError):
            policy.hedged("/k", [])

    def test_failed_primary_fires_hedge_immediately(self):
        def boom():
            raise RpcError("down", 503)

        assert policy.hedged("/k", [boom, lambda: "ok"]) == "ok"

    def test_all_fail_raises_last(self):
        def boom():
            raise RpcError("down", 503)

        with pytest.raises(RpcError):
            policy.hedged("/k", [boom, boom])

    def test_slow_primary_loses_to_hedge(self):
        answered = threading.Event()

        def slow():  # still out when the hedge's answer is returned
            assert answered.wait(30)
            return "slow"

        try:
            assert policy.hedged("/k", [slow, lambda: "fast"]) == "fast"
        finally:
            answered.set()

    def test_adaptive_delay_is_p95(self):
        t = policy.HedgeTracker()
        for ms in range(1, 101):
            t.observe("/k", ms / 1000.0)
        # ring keeps the last 64 samples (37..100 ms); p95 near the top
        assert 0.09 <= t.delay("/k") <= 0.1
        assert t.delay("/cold") == \
            pytest.approx(0.025)  # floor for unseen routes


class TestMasterFailover:
    """Satellite: failover order and backoff on injected faults with a
    fake clock — no real masters die, no real sleeps happen."""

    def test_failover_order_and_round_backoff(self, no_sleep):
        m1, m2 = "127.0.0.1:18801", "127.0.0.1:18802"
        faults.REGISTRY.configure(f"reset,dst={m1};reset,dst={m2}")
        with pytest.raises(RpcError) as e:
            policy.failover_call([m1, m2], "/dir/status", method="GET",
                                 rounds=2)
        assert e.value.transport
        order = [ev["dst"] for ev in faults.REGISTRY.snapshot()["log"]]
        assert order == [m1, m2, m1, m2]  # strict preference order
        assert len(no_sleep) == 1  # one jittered backoff between rounds

    def test_masterclient_fails_over_and_sticks(self, master, no_sleep):
        dead = "127.0.0.1:18809"
        faults.REGISTRY.configure(f"reset,dst={dead}")
        mc = MasterClient([dead, master.address])
        assert mc.current_master == dead
        r = mc._call_any("/dir/status")
        assert isinstance(r, dict)
        assert mc.current_master == master.address
        assert no_sleep == []  # secondary reached within the first round
        # subsequent calls go straight to the live master
        mc._call_any("/dir/status")
        dead_attempts = [ev for ev in faults.REGISTRY.snapshot()["log"]
                         if ev["dst"] == dead]
        assert len(dead_attempts) == 1

    def test_masterclient_skips_open_breaker(self, master, no_sleep):
        dead = "127.0.0.1:18809"
        faults.REGISTRY.configure(f"reset,dst={dead}")
        for _ in range(5):
            policy.BREAKERS.get(dead).on_failure()
        assert policy.BREAKERS.get(dead).state == policy.OPEN
        mc = MasterClient([dead, master.address])
        mc._call_any("/dir/status")
        assert mc.current_master == master.address
        # the open breaker meant the dead master was never dialed
        assert faults.REGISTRY.snapshot()["log"] == []


class TestLoadShedding:
    def test_shedder_bounds_inflight(self):
        s = _RequestShedder(1)
        assert s.try_acquire()
        assert not s.try_acquire()
        s.release()
        assert s.try_acquire()
        s.release()

    def test_zero_limit_means_off(self):
        s = _RequestShedder(0)
        for _ in range(100):
            assert s.try_acquire()

    def test_env_overrides_limit(self, monkeypatch):
        s = _RequestShedder(1)
        monkeypatch.setenv("WEED_VS_MAX_INFLIGHT", "2")
        assert s.try_acquire() and s.try_acquire()
        assert not s.try_acquire()

    def test_assign_drought_is_503_with_retry_after(self, master):
        # no volume servers registered: assignment must shed retryably
        host, port = master.address.split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=10)
        try:
            conn.request("GET", "/dir/assign")
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 503
            assert resp.getheader("Retry-After") is not None
        finally:
            conn.close()

    def test_s3_slowdown_carries_retry_after(self):
        from seaweedfs_tpu.s3api.server import _error_xml

        resp = _error_xml("SlowDown", "busy", 503,
                          headers={"Retry-After": "1"})
        assert resp.status == 503
        assert resp.headers["Retry-After"] == "1"
