"""Request-level resilience for the serving plane (ISSUE 14): deadline
propagation and sweeps, per-replica circuit breakers, hedged dispatch
under a token-bucket retry budget, criticality-band shedding, and
Retry-After backpressure — the deterministic core drills on a manual
clock, the replica-side slot-cancel zero-leak proof, and the
serve_bench --resilience ratchet contract."""

import json
import threading

import pytest
from conftest import answer_tokens, slot_decoder

from kubeflow_tpu.obs import trace as obs_trace
from kubeflow_tpu.runtime.metrics import MetricsRegistry
from kubeflow_tpu.serving.router import (
    BAND_CRITICAL, BAND_DEFAULT, BAND_SHEDDABLE, BREAKER_CLOSED,
    BREAKER_HALF_OPEN, BREAKER_OPEN, HEADER_DEADLINE, DeadlineExceeded,
    Member, ResilienceConfig, RouterBusy, RouterFrontend, TokenRouter,
    TransportError,
)

pytestmark = pytest.mark.serving


class ManualClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _router(**kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("prom_sink", False)
    kw.setdefault("tracer", obs_trace.Tracer())
    kw.setdefault("resilience", ResilienceConfig())
    return TokenRouter(service="svc", namespace="ns", **kw)


def _members(r, n):
    r.set_members([Member(name=f"r{i}") for i in range(n)])


def _seed_latency(router, clock, n=20, latency=1.0, tokens=1):
    """Complete ``n`` requests at a fixed latency so the hedge quantile
    has samples (and every replica has EWMA history)."""
    for _ in range(n):
        t = router.submit(tokens)
        assert t.member is not None
        clock.advance(latency)
        router.complete(t)


# -- deadlines ---------------------------------------------------------------


class TestDeadlines:
    def test_dead_on_arrival_raises_without_queueing(self):
        clock = ManualClock(100.0)
        r = _router(clock=clock)
        _members(r, 1)
        with pytest.raises(DeadlineExceeded):
            r.submit(8, deadline=99.0)
        assert r.queue_depth() == 0
        assert 'outcome="deadline"' in r.registry.render()

    def test_queued_ticket_swept_at_deadline_before_dispatch(self):
        clock = ManualClock()
        r = _router(clock=clock, replica_token_budget=10)
        _members(r, 1)
        t1 = r.submit(8)                      # occupies the replica
        t2 = r.submit(8, deadline=5.0)        # queued behind it
        assert t1.member is not None and t2.member is None
        clock.advance(6.0)                    # past t2's deadline
        dispatched = r.complete(t1)           # capacity appears too late
        assert dispatched == []               # t2 was swept, not served
        assert t2.dropped_reason == "deadline"
        assert t2.done.is_set()               # a parked shell wakes up
        assert r.queue_depth() == 0

    def test_sweep_fires_on_submit_too(self):
        clock = ManualClock()
        r = _router(clock=clock, replica_token_budget=10)
        _members(r, 1)
        r.submit(8)
        stale = r.submit(8, deadline=2.0)
        clock.advance(3.0)
        fresh = r.submit(8, deadline=20.0)    # admission sweeps the queue
        assert stale.dropped_reason == "deadline"
        assert fresh.member is None and r.queue_depth() == 1

    def test_fail_past_deadline_drops_instead_of_retrying(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 2)
        t = r.submit(8, deadline=5.0)
        clock.advance(6.0)
        r.fail(t, requeue=True)               # transport died after the dl
        assert t.member is None
        assert t.dropped_reason == "deadline"


# -- circuit breakers --------------------------------------------------------


class TestCircuitBreaker:
    def test_opens_after_consecutive_failures(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 1)
        t = r.submit(8)
        for _ in range(3):                    # breaker_failures = 3
            assert t.member is not None
            redispatched = r.fail(t, requeue=True)
            if redispatched:
                t = redispatched[0]
        assert r.breaker_states()["r0"] == BREAKER_OPEN

    def _opened(self, clock):
        r = _router(clock=clock)
        _members(r, 1)
        t = r.submit(8)
        for _ in range(3):
            redispatched = r.fail(t, requeue=True)
            t = redispatched[0] if redispatched else t
        assert r.breaker_states()["r0"] == BREAKER_OPEN
        # flush the wedged ticket so later asserts see a clean queue
        r.fail(t, requeue=False)
        return r

    def test_open_breaker_receives_no_work(self):
        clock = ManualClock()
        r = self._opened(clock)
        t = r.submit(8)
        assert t.member is None               # queued: r0 is ineligible

    def test_cooloff_half_opens_with_a_single_probe(self):
        clock = ManualClock()
        r = self._opened(clock)
        clock.advance(5.5)                    # past breaker_cooloff_s
        probe = r.submit(8)
        assert probe.member is not None       # the probe dispatch
        assert r.breaker_states()["r0"] == BREAKER_HALF_OPEN
        second = r.submit(8)
        assert second.member is None          # one probe at a time

    def test_probe_success_recloses(self):
        clock = ManualClock()
        r = self._opened(clock)
        clock.advance(5.5)
        probe = r.submit(8)
        clock.advance(0.2)
        r.complete(probe)
        assert r.breaker_states()["r0"] == BREAKER_CLOSED

    def test_probe_failure_reopens(self):
        clock = ManualClock()
        r = self._opened(clock)
        clock.advance(5.5)
        probe = r.submit(8)
        r.fail(probe, requeue=False)          # the probe dies
        assert r.breaker_states()["r0"] == BREAKER_OPEN

    def test_slow_replica_drains_by_latency_score(self):
        """EWMA latency scales the pick key: the browned-out (10x slow)
        replica loses a dispatch that raw least-tokens would hand it."""
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 2)
        for _ in range(6):                    # r0 fast, r1 slow
            a = r.submit(1)
            b = r.submit(1)
            fast = a if a.member.name == "r0" else b
            slow = b if fast is a else a
            clock.advance(0.1)
            r.complete(fast)
            clock.advance(0.9)
            r.complete(slow)
        t1 = r.submit(8)
        t2 = r.submit(4)
        assert t1.member.name == "r0" and t2.member.name == "r1"
        # r0 carries MORE tokens (8 vs 4) — raw least-outstanding would
        # pick r1 — but r1's 10x latency multiplier prices it out
        t3 = r.submit(4)
        assert t3.member.name == "r0"


# -- criticality bands -------------------------------------------------------


class TestBandShedding:
    def _full(self, clock, band):
        r = _router(clock=clock, max_queue=2)
        _members(r, 0)                        # no capacity: all queue
        queued = [r.submit(8, band=band) for _ in range(2)]
        return r, queued

    def test_critical_arrival_evicts_newest_sheddable(self):
        clock = ManualClock()
        r, queued = self._full(clock, BAND_SHEDDABLE)
        crit = r.submit(8, band=BAND_CRITICAL)
        victim = queued[1]                    # NEWEST lower-band ticket
        assert victim.dropped_reason == "shed_band"
        assert victim.retry_after >= 1.0
        assert victim.done.is_set()
        assert crit.member is None and r.queue_depth() == 2
        assert 'band="sheddable"' in r.registry.render()

    def test_no_lower_band_rejects_the_arrival(self):
        clock = ManualClock()
        r, queued = self._full(clock, BAND_CRITICAL)
        with pytest.raises(RouterBusy) as exc:
            r.submit(8, band=BAND_SHEDDABLE)
        assert exc.value.retry_after >= 1.0
        assert all(t.dropped_reason is None for t in queued)

    def test_equal_band_rejects_the_arrival(self):
        clock = ManualClock()
        r, queued = self._full(clock, BAND_DEFAULT)
        with pytest.raises(RouterBusy):
            r.submit(8, band=BAND_DEFAULT)

    def test_drain_serves_critical_before_older_sheddable(self):
        clock = ManualClock()
        r = _router(clock=clock, replica_token_budget=10)
        _members(r, 1)
        blocker = r.submit(8)
        shed = r.submit(8, band=BAND_SHEDDABLE)   # queued FIRST
        crit = r.submit(8, band=BAND_CRITICAL)    # queued second
        dispatched = r.complete(blocker)
        assert dispatched == [crit]               # band beats FIFO
        assert shed.member is None

    def test_legacy_router_keeps_fifo_drain(self):
        r = _router(resilience=None, replica_token_budget=10)
        _members(r, 1)
        blocker = r.submit(8)
        first = r.submit(8, band=BAND_SHEDDABLE)
        r.submit(8, band=BAND_CRITICAL)
        assert r.complete(blocker) == [first]     # strict FIFO


# -- retry budget ------------------------------------------------------------


class TestRetryBudget:
    def test_exhausted_budget_drops_with_reason(self):
        clock = ManualClock()
        cfg = ResilienceConfig(retry_budget_cap=1.0, retry_budget_ratio=0.0)
        r = _router(clock=clock, resilience=cfg)
        _members(r, 1)
        t = r.submit(8)
        redispatched = r.fail(t, requeue=True)    # spends the last token
        t = redispatched[0]
        assert t.dropped_reason is None
        r.fail(t, requeue=True)                   # budget is dry now
        assert t.dropped_reason == "retry_budget"
        assert t.retry_after >= 1.0
        assert t.member is None and r.queue_depth() == 0

    def test_admissions_refill_the_bucket(self):
        clock = ManualClock()
        cfg = ResilienceConfig(retry_budget_cap=2.0, retry_budget_ratio=0.5)
        r = _router(clock=clock, resilience=cfg)
        _members(r, 1)
        t = r.submit(8)
        r.fail(t, requeue=True)                   # 2.0 + 0.5 - 1.0 = 1.5
        before = r.retry_budget()
        for _ in range(4):
            r.complete(r.submit(1))               # +0.5 each, capped at 2
        assert r.retry_budget() == pytest.approx(
            min(before + 4 * 0.5, 2.0))


class TestTenantRetryIsolation:
    def test_storm_exhausts_only_the_noisy_tenants_bucket(self):
        """ISSUE 20 satellite: retry/hedge tokens are bucketed PER
        TENANT — tenant A's retry storm drains A's bucket to zero while
        tenant B seeds its own bucket from the pool headroom and its
        retries still spend."""
        clock = ManualClock()
        cfg = ResilienceConfig(retry_budget_cap=2.0,
                               retry_budget_ratio=0.0)
        r = _router(clock=clock, resilience=cfg)
        _members(r, 2)
        assert r.retry_budget(tenant="A") == 0.0  # unseen: no bucket yet
        ta = r.submit(8, tenant="A")
        assert r.retry_budget(tenant="A") == pytest.approx(2.0)
        for _ in range(2):                        # A's retry storm
            ta = r.fail(ta, requeue=True)[0]
            assert ta.dropped_reason is None
        r.fail(ta, requeue=True)                  # A's bucket is dry
        assert ta.dropped_reason == "retry_budget"
        assert r.retry_budget(tenant="A") == pytest.approx(0.0)
        # B seeds its OWN bucket from the headroom A never consumed —
        # the storm next door did not spend B's tokens
        tb = r.submit(8, tenant="B")
        assert r.retry_budget(tenant="B") == pytest.approx(2.0)
        redispatched = r.fail(tb, requeue=True)
        survivor = redispatched[0] if redispatched else tb
        assert survivor.dropped_reason is None    # B's retry still spends
        assert r.retry_budget(tenant="B") == pytest.approx(1.0)
        assert r.retry_budget(tenant="A") == pytest.approx(0.0)
        assert r.retry_budget() == pytest.approx(1.0)
        assert 'tenant="A"' in r.registry.render()


# -- hedging -----------------------------------------------------------------


class TestHedging:
    def test_hedge_delay_needs_samples_then_tracks_quantile(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 2)
        assert r.hedge_delay() is None
        _seed_latency(r, clock, n=20, latency=1.0)
        assert r.hedge_delay() == pytest.approx(1.0)

    def test_try_hedge_charges_both_replicas_and_budget(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 2)
        _seed_latency(r, clock, n=20, latency=1.0)
        budget0 = r.retry_budget()
        t = r.submit(8)
        primary = t.member.name
        hedge = r.try_hedge(t)
        assert hedge is not None and hedge.name != primary
        assert r.inflight_tokens(primary) == 8
        assert r.inflight_tokens(hedge.name) == 8
        assert r.retry_budget() == pytest.approx(budget0 - 1.0)
        assert r.try_hedge(t) is None             # one hedge per ticket

    def test_hedge_winner_releases_both_legs(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 2)
        _seed_latency(r, clock, n=20, latency=1.0)
        t = r.submit(8)
        hedge = r.try_hedge(t)
        clock.advance(0.5)
        r.complete(t, winner=hedge.name)
        assert r.inflight_tokens() == 0
        assert t.hedge_member is None
        assert 'outcome="won"' in r.registry.render()

    def test_primary_win_cancels_the_hedge_leg(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 2)
        _seed_latency(r, clock, n=20, latency=1.0)
        t = r.submit(8)
        r.try_hedge(t)
        r.complete(t)                             # primary answered
        assert r.inflight_tokens() == 0
        assert 'outcome="canceled"' in r.registry.render()

    def test_no_distinct_replica_means_no_hedge(self):
        clock = ManualClock()
        r = _router(clock=clock)
        _members(r, 1)
        _seed_latency(r, clock, n=20, latency=1.0)
        t = r.submit(8)
        assert r.try_hedge(t) is None

    def test_hedge_denied_past_deadline_or_without_budget(self):
        clock = ManualClock()
        cfg = ResilienceConfig(retry_budget_cap=0.5,
                               retry_budget_ratio=0.0)
        r = _router(clock=clock, resilience=cfg)
        _members(r, 2)
        t = r.submit(8, deadline=clock.t + 10.0)
        assert r.try_hedge(t) is None             # budget below 1.0
        r2 = _router(clock=clock)
        _members(r2, 2)
        t2 = r2.submit(8, deadline=clock.t + 1.0)
        clock.advance(2.0)
        assert r2.try_hedge(t2) is None           # deadline passed


# -- Retry-After propagation -------------------------------------------------


class TestRetryAfter:
    def test_router_busy_carries_drain_rate_estimate(self):
        clock = ManualClock()
        r = _router(clock=clock, max_queue=3, replica_token_budget=10)
        _members(r, 1)
        for _ in range(5):                        # 1 completion per second
            t = r.submit(8)
            clock.advance(1.0)
            r.complete(t)
        r.submit(8)                               # occupies the replica
        for _ in range(3):
            r.submit(8)
        with pytest.raises(RouterBusy) as exc:
            r.submit(8)
        # depth 3 + the arrival, at ~1/s -> ~4s, clamped to [1, 120]
        assert 1.0 <= exc.value.retry_after <= 10.0

    def test_http_transport_parses_retry_after_header(self, monkeypatch):
        import io
        import urllib.error
        import urllib.request

        from kubeflow_tpu.serving.router import HttpTransport

        def boom(req, timeout=None):
            raise urllib.error.HTTPError(
                req.full_url, 429, "Too Many Requests",
                {"Retry-After": "7"}, io.BytesIO(b"{}"))

        monkeypatch.setattr(urllib.request, "urlopen", boom)
        tr = HttpTransport("http://replica.invalid")
        with pytest.raises(TransportError) as exc:
            tr.predict("lm", b"{}")
        assert exc.value.status == 429
        assert exc.value.retry_after == 7.0

    def test_frontend_backoff_floor_honors_retry_after(self):
        """A replica's Retry-After beats the frontend's exponential
        backoff schedule: the first retry waits the FLOOR, not 50ms."""
        clock = ManualClock()
        r = _router(clock=clock)
        sleeps: list = []

        class FlakyTransport:
            calls = 0

            def predict(self, model, body, headers=None):
                FlakyTransport.calls += 1
                if FlakyTransport.calls == 1:
                    raise TransportError(503, "overloaded",
                                         retry_after=2.0)
                return json.dumps({"predictions": [[1]]}).encode()

        r.set_members([Member(name="r0", transport=FlakyTransport())])
        fe = RouterFrontend(r, max_new_tokens=4, sleep=sleeps.append)
        fe.hedging = False
        req = _FakeReq({"instances": [{"tokens": [1, 2]}]})
        out = fe.predict(req)
        assert out == {"predictions": [[1]]}
        assert sleeps and sleeps[0] == pytest.approx(2.0)

    def test_drop_reasons_map_to_http_statuses(self):
        from kubeflow_tpu.serving.router import Ticket

        t = Ticket(tokens=1)
        t.dropped_reason = "deadline"
        assert RouterFrontend._drop_error(t).status == 504
        t.dropped_reason = "shed_band"
        t.retry_after = 3.0
        err = RouterFrontend._drop_error(t)
        assert err.status == 429
        assert err.headers["Retry-After"] == "3"
        t.dropped_reason = "retry_budget"
        assert RouterFrontend._drop_error(t).status == 503

    def test_frontend_shrinks_deadline_header_replica_ward(self):
        """The replica sees the REMAINING budget, not the original."""
        clock = ManualClock(10.0)
        r = _router(clock=clock)
        seen: list = []

        class Capture:
            def predict(self, model, body, headers=None):
                seen.append(headers or {})
                clock.advance(1.0)
                return json.dumps({"predictions": [[1]]}).encode()

        r.set_members([Member(name="r0", transport=Capture())])
        fe = RouterFrontend(r, max_new_tokens=4, sleep=lambda s: None)
        fe.hedging = False
        req = _FakeReq({"instances": [{"tokens": [1]}]},
                       headers={HEADER_DEADLINE: "8.0"})
        fe.predict(req)
        assert float(seen[0][HEADER_DEADLINE]) == pytest.approx(8.0)

    def test_empty_deadline_header_means_no_deadline(self):
        """The REAL shell's HttpReq.header returns "" (not None) for a
        missing header — it must read as 'no deadline', not 400. Pinned
        live by tests/test_router_live.py; this is the fast repro."""
        r = _router()

        class Ok:
            def predict(self, model, body, headers=None):
                assert not (headers or {}).get(HEADER_DEADLINE)
                return json.dumps({"predictions": [[1]]}).encode()

        r.set_members([Member(name="r0", transport=Ok())])
        fe = RouterFrontend(r, max_new_tokens=4, sleep=lambda s: None)
        fe.hedging = False

        class _ShellReq(_FakeReq):
            def header(self, name, default=None):
                # the httpd shell's semantics: default is ""
                return self._headers.get(name.lower(), "")

        out = fe.predict(_ShellReq({"instances": [{"tokens": [1]}]}))
        assert out == {"predictions": [[1]]}


class _FakeReq:
    """The slice of HttpReq the frontend touches."""

    def __init__(self, body_obj, headers=None, model="lm"):
        self.body = json.dumps(body_obj).encode()
        self.params = {"model": model}
        self._headers = {k.lower(): v for k, v in (headers or {}).items()}

    def json(self):
        return json.loads(self.body)

    def header(self, name, default=None):
        return self._headers.get(name.lower(), default)


# -- replica-side overload gate ----------------------------------------------


class TestServerOverload:
    def test_max_inflight_429_carries_retry_after(self):
        from kubeflow_tpu.serving.server import REPLICA_METER, ServedModel
        from kubeflow_tpu.utils.httpd import ApiHttpError

        m = ServedModel(name="overload-test", predict_fn=lambda b: b,
                        pad_batches=False, max_inflight=1)
        REPLICA_METER.enter("overload-test", 1)   # a stuck peer request
        try:
            with pytest.raises(ApiHttpError) as exc:
                m.predict([[1, 2]])
            assert exc.value.status == 429
            assert int(exc.value.headers["Retry-After"]) >= 1
        finally:
            REPLICA_METER.exit("overload-test")
        assert m.predict([[1, 2], [3, 4]]) == [[1, 2], [3, 4]]


# -- the replica-side slot cancel (zero-leak contract) -----------------------


class _AfterAdmitClock:
    """0.0 until the decoder has admitted a request, then just past the
    500.0 deadline: the round-boundary sweep right after admission sees
    the deadline expired — a deterministic mid-flight cancel, no
    sleeps. Deliberately INSIDE the waiter's +30s wedge-guard grace
    (submit_padded polls the same clock while the first decode round
    jit-compiles; jumping past deadline+30 would let that poll raise
    before the loop's cancel is recorded)."""

    def __init__(self):
        self.dec = None

    def __call__(self) -> float:
        if self.dec is not None and self.dec.stats()["admitted"] >= 1:
            return 501.0
        return 0.0


def _pool_is_whole(dec) -> None:
    """Every page is back (a dense cache has no pool to leak from)."""
    if dec.paged:
        st = dec.stats()
        assert st["kv_pages_free"] == st["kv_pages_total"]
        dec.alloc.check()


# the loop's sweep and gate are one; what a cancel zeroes is the step's
@pytest.mark.parametrize(
    "mode", ["token-paged", "block", "spec-paged", "spec-dense"])
class TestSlotDecoderDeadline:
    def test_queue_side_gate_cancels_before_prefill(self, mode):
        clock = ManualClock(50.0)
        dec = slot_decoder(mode, slots=2, max_new_tokens=4, clock=clock)
        try:
            with pytest.raises(DeadlineExceeded):
                dec.submit([1, 2, 3], deadline=49.0)   # already past
            st = dec.stats()
            assert st["deadline_canceled"] == 1
            assert st["admitted"] == 0                 # never cost a slot
            _pool_is_whole(dec)
        finally:
            dec.close()

    def test_mid_decode_cancel_frees_slot_and_pages(self, mode):
        clock = _AfterAdmitClock()
        # prefix_cache off: the LRU prefix index retaining prompt pages
        # across frees is reuse, not the leak this test guards against
        dec = slot_decoder(mode, slots=2, max_new_tokens=12, clock=clock,
                           prefix_cache=False)
        clock.dec = dec
        try:
            with pytest.raises(DeadlineExceeded):
                dec.submit([1, 2, 3], max_new=12, deadline=500.0)
            st = dec.stats()
            assert st["admitted"] == 1                 # it DID hold a slot
            assert st["deadline_canceled"] == 1
            assert st["completed"] == 0
            # the cancel returned every page: zero-leak contract
            _pool_is_whole(dec)
            assert dec.active_slots == 0
            # the decoder is still healthy after the cancel
            assert len(answer_tokens(dec.submit([4, 5], max_new=2))) == 2
        finally:
            dec.close()

    def test_no_deadline_requests_are_untouched(self, mode):
        clock = ManualClock(1e9)                       # far future always
        dec = slot_decoder(mode, slots=2, max_new_tokens=4, clock=clock)
        try:
            assert len(answer_tokens(dec.submit([1, 2, 3]))) == 4
            assert dec.stats()["deadline_canceled"] == 0
        finally:
            dec.close()


# -- the serve_bench --resilience contract -----------------------------------


def _bench():
    import os
    import sys

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(here, "tools"))
    try:
        import serve_bench as sb
    finally:
        sys.path.pop(0)
    return sb


@pytest.mark.usefixtures("virtual_time_guard")
class TestResilienceBenchContract:
    def test_banked_results_satisfy_acceptance(self):
        """BENCH_SERVE_r03.json is the PR's acceptance artifact: the
        resilient arm shelters critical-band goodput through the
        brownout while the control arm degrades, hedges actually rescue
        work, no critical request is ever shed, the breaker completes
        its round trip, and the KV cancel drill recovered every page."""
        import os

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(here, "BENCH_SERVE_r03.json")) as fh:
            banked = json.load(fh)
        sec = banked["resilience"]
        cmp_ = sec["comparison"]
        assert cmp_["critical_goodput_resilient"] >= 0.9
        assert cmp_["critical_goodput_control"] < 0.7
        assert cmp_["hedge_wins"] >= 1
        assert cmp_["critical_sheds"] == 0
        assert cmp_["breaker_round_trip"] is True
        assert cmp_["replay_identical"] is True
        drill = sec["kv_drill"]
        assert drill["pages_recovered"] is True
        assert drill["invariant_clean"] is True
        assert drill["mid_flight_frees"] > 0

    def test_same_seed_replays_byte_identical(self):
        import random

        sb = _bench()
        cfg = dict(sb.RES_CONFIG)
        trace = sb.build_res_trace(cfg, random.Random(cfg["seed"]))
        a = sb.run_resilience_arm("resilient", cfg, trace)
        b = sb.run_resilience_arm("resilient", cfg, trace)
        assert a["decision_fingerprint"] == b["decision_fingerprint"]
        assert a == b

    def test_check_gate_round_trip(self, tmp_path):
        """--check passes against a just-banked run and fails loudly on
        a poisoned decision fingerprint or a KV drill regression — the
        ratchet has teeth."""
        sb = _bench()
        banked = {"resilience": sb.run_resilience_bench(
            dict(sb.RES_CONFIG))}
        ok = tmp_path / "bank_ok.json"
        ok.write_text(json.dumps(banked))
        assert sb.check_resilience_bench(str(ok)) == 0
        bad = json.loads(ok.read_text())
        bad["resilience"]["resilient"]["decision_fingerprint"] = "deadbeef"
        bad_path = tmp_path / "bank_bad.json"
        bad_path.write_text(json.dumps(bad))
        assert sb.check_resilience_bench(str(bad_path)) == 1
        empty = tmp_path / "bank_empty.json"
        empty.write_text(json.dumps({"router": {}}))
        assert sb.check_resilience_bench(str(empty)) == 2


# -- chaos-parameterized brownout reruns -------------------------------------


from conftest import CHAOS_SEEDS  # noqa: E402


@pytest.mark.chaos
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_brownout_drill_invariants_hold_across_seeds(seed):
    """The resilience drill's INVARIANTS (not its tuned thresholds) must
    hold for any fault schedule: deterministic replay, zero critical
    sheds, and the resilient arm never WORSE than the control arm on
    critical-band goodput through the brownout."""
    import random

    sb = _bench()
    cfg = dict(sb.RES_CONFIG)
    cfg["seed"] = seed
    trace = sb.build_res_trace(cfg, random.Random(seed))
    resilient = sb.run_resilience_arm("resilient", cfg, trace)
    control = sb.run_resilience_arm("control", cfg, trace)
    replay = sb.run_resilience_arm("resilient", cfg, trace)
    assert resilient["decision_fingerprint"] == \
        replay["decision_fingerprint"]
    assert resilient["sheds"][BAND_CRITICAL] == 0
    assert resilient["brownout_goodput"]["critical"] >= \
        control["brownout_goodput"]["critical"]
    assert resilient["breaker_opened"] and resilient["breaker_reclosed"]
