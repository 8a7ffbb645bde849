"""Serving-plane resilience tests (ISSUE-4 acceptance surface).

Covers: bounded admission (`ServingOverloadError` / HTTP 503 +
Retry-After), deadline propagation with doomed-work shedding before
dispatch (`DeadlineExceededError` / 504), the submit-timeout race
(abandoned requests' rows excluded from the dispatch), poison-request
bisection (co-batched requests byte-identical to sequential, exactly the
poison request fails), the circuit breaker lifecycle (open after N
consecutive whole-dispatch failures -> fast-fail -> half-open probe ->
closed, with `/readyz` flipping), graceful drain (admission stops,
in-flight completes, stats snapshot), the overload-storm ledger
(`requests + rejected + shed == submitted`), and the chaos-injected
breaker scenario end-to-end over HTTP — all deterministic on CPU.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork, iris_mlp
from deeplearning4j_tpu.resilience import (
    InjectedDispatchFault,
    ServingChaosConfig,
    chaos_dispatch,
)
from deeplearning4j_tpu.serving import (
    BucketLadder,
    CircuitBreaker,
    CircuitOpenError,
    ContinuousLMServer,
    DeadlineExceededError,
    MicroBatcher,
    ServingEngine,
    ServingMetrics,
    ServingOverloadError,
    ServingUnavailableError,
)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


def _mlp():
    return MultiLayerNetwork(iris_mlp()).init()


class _GatedDispatch:
    """Dispatch that blocks until released — deterministic queue
    build-up without wall-clock races."""

    def __init__(self):
        self.release = threading.Event()
        self.started = threading.Event()
        self.dispatched = []   # row counts per dispatch

    def __call__(self, x, mask, n):
        self.started.set()
        assert self.release.wait(30), "test gate never released"
        self.dispatched.append(np.asarray(x).copy())
        return np.asarray(x)


# ---------------------------------------------------------------------------
# Circuit breaker unit behavior


class TestCircuitBreaker:
    def test_lifecycle_with_fake_clock(self):
        now = [0.0]
        states = []
        br = CircuitBreaker(failure_threshold=3, cooldown_s=10.0,
                            clock=lambda: now[0],
                            on_transition=states.append)
        assert br.state == "closed" and not br.rejecting()
        br.record_failure()
        br.record_failure()
        assert br.state == "closed"          # below threshold
        br.record_failure()                  # third consecutive: trips
        assert br.state == "open" and br.rejecting()
        assert br.opens == 1
        assert not br.allow_dispatch()       # inside the cooldown
        now[0] = 10.5                        # cooldown elapsed
        assert not br.rejecting()            # admission resumes
        assert br.allow_dispatch()           # the half-open probe
        assert not br.allow_dispatch()       # only ONE probe in flight
        br.record_success()
        assert br.state == "closed"
        assert states == ["open", "half_open", "closed"]

    def test_probe_failure_reopens_with_fresh_cooldown(self):
        now = [0.0]
        br = CircuitBreaker(failure_threshold=2, cooldown_s=5.0,
                            clock=lambda: now[0])
        br.record_failure()
        br.record_failure()
        now[0] = 6.0
        assert br.allow_dispatch()           # probe
        br.record_failure()                  # probe fails: re-open
        assert br.state == "open" and br.opens == 2
        assert not br.allow_dispatch()       # fresh cooldown from t=6
        now[0] = 11.5
        assert br.allow_dispatch()

    def test_success_resets_consecutive_count(self):
        br = CircuitBreaker(failure_threshold=2, cooldown_s=1.0)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == "closed"          # never 2 CONSECUTIVE

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(failure_threshold=0)
        with pytest.raises(ValueError):
            CircuitBreaker(cooldown_s=-1)


# ---------------------------------------------------------------------------
# Bounded admission


class TestAdmissionControl:
    def test_overflow_submit_is_rejected_typed(self):
        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=1, max_wait_ms=0.0,
                         max_queue_depth=1)
        t1 = threading.Thread(target=lambda: b.submit(
            np.zeros((1, 2), np.float32)))
        t1.start()
        assert gate.started.wait(10)         # worker busy in dispatch
        t2 = threading.Thread(target=lambda: b.submit(
            np.ones((1, 2), np.float32)))
        t2.start()
        for _ in range(200):                 # wait until t2 is queued
            with b._cond:
                if len(b._queue) == 1:
                    break
            time.sleep(0.005)
        with pytest.raises(ServingOverloadError) as exc:
            b.submit(np.full((1, 2), 2.0, np.float32))
        assert exc.value.retry_after_s > 0
        gate.release.set()
        t1.join(timeout=10)
        t2.join(timeout=10)
        b.stop()
        snap = b.metrics.snapshot()
        assert snap["rejected"] == 1
        assert snap["requests"] == 2         # the two admitted completed
        assert len(gate.dispatched) == 2     # rejection never dispatched

    def test_queue_depth_validation(self):
        with pytest.raises(ValueError, match="max_queue_depth"):
            MicroBatcher(lambda x, m, n: x, max_queue_depth=0)
        cfg, params = _lm()
        with pytest.raises(ValueError, match="max_queue_depth"):
            ContinuousLMServer(cfg, params, max_queue_depth=0)

    def test_lm_overflow_is_rejected_typed(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1, max_queue_depth=1)
        t1 = threading.Thread(
            target=lambda: srv.generate([1, 2], 10, timeout=120))
        t1.start()
        for _ in range(400):                 # slot occupied
            if srv.stats()["active_slots"] == 1:
                break
            time.sleep(0.005)
        t2 = threading.Thread(
            target=lambda: srv.generate([3], 2, timeout=120))
        t2.start()
        for _ in range(400):                 # follower queued
            if srv.stats()["queue_depth"] == 1:
                break
            time.sleep(0.005)
        if srv.stats()["queue_depth"] == 1:  # not yet admitted
            with pytest.raises(ServingOverloadError):
                srv.generate([4], 2)
        t1.join(timeout=120)
        t2.join(timeout=120)
        srv.stop()

    def test_stop_fails_queued_with_typed_unavailable(self):
        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=1, max_wait_ms=0.0)
        errs = {}

        def client(tag, x):
            try:
                b.submit(x)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs[tag] = e

        t1 = threading.Thread(target=client,
                              args=("a", np.zeros((1, 2), np.float32)))
        t1.start()
        assert gate.started.wait(10)
        t2 = threading.Thread(target=client,
                              args=("b", np.ones((1, 2), np.float32)))
        t2.start()
        for _ in range(200):
            with b._cond:
                if len(b._queue) == 1:
                    break
            time.sleep(0.005)
        gate.release.set()
        # stop() races the worker for "b": it either completes (worker
        # grabbed it) or fails TYPED — never a bare RuntimeError 500
        b.stop()
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert "a" not in errs
        if "b" in errs:
            assert isinstance(errs["b"], ServingUnavailableError)


# ---------------------------------------------------------------------------
# Deadlines + the submit-timeout race


class TestDeadlines:
    def test_expired_queue_item_is_shed_before_dispatch(self):
        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=1, max_wait_ms=0.0)
        t1 = threading.Thread(target=lambda: b.submit(
            np.zeros((1, 2), np.float32)))
        t1.start()
        assert gate.started.wait(10)         # worker busy: B will queue
        errs = {}

        def doomed():
            try:
                b.submit(np.full((1, 2), 5.0, np.float32),
                         deadline_s=0.05)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs["b"] = e

        t2 = threading.Thread(target=doomed)
        t2.start()
        time.sleep(0.15)                     # let B's deadline pass
        gate.release.set()                   # worker frees, sheds B
        t1.join(timeout=10)
        t2.join(timeout=10)
        b.stop()
        assert isinstance(errs["b"], DeadlineExceededError)
        # B's rows (value 5.0) never reached the device
        for batch in gate.dispatched:
            assert not np.any(batch == 5.0)
        snap = b.metrics.snapshot()
        assert snap["deadline_missed"] == 1
        assert snap["shed"] == 1
        assert snap["queue_depth"] == 0

    def test_default_deadline_applies(self):
        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=1, max_wait_ms=0.0,
                         default_deadline_s=0.05)
        t1 = threading.Thread(target=lambda: b.submit(
            np.zeros((1, 2), np.float32), deadline_s=60))
        t1.start()
        assert gate.started.wait(10)
        errs = {}

        def doomed():
            try:
                # no explicit deadline: the batcher default (50ms)
                # applies and the WORKER sheds it — no client timeout
                b.submit(np.full((1, 2), 5.0, np.float32))
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs["b"] = e

        t2 = threading.Thread(target=doomed)
        t2.start()
        time.sleep(0.15)
        gate.release.set()
        t1.join(timeout=10)
        t2.join(timeout=10)
        b.stop()
        assert isinstance(errs["b"], DeadlineExceededError)
        for batch in gate.dispatched:
            assert not np.any(batch == 5.0)

    def test_abandoned_item_rows_never_dispatch(self):
        """The worker-side half of the timeout race: an item marked
        abandoned (its client gave up) is dropped before the dispatch
        group forms, whether it is still queued or freshly popped."""
        from deeplearning4j_tpu.serving.batcher import _Pending

        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=4, max_wait_ms=0.0)
        t1 = threading.Thread(target=lambda: b.submit(
            np.zeros((1, 2), np.float32)))
        t1.start()
        assert gate.started.wait(10)
        # stage the race's outcome directly: a queued item whose client
        # already timed out and marked it (the removal race was lost)
        zombie = _Pending(np.full((1, 2), 9.0, np.float32), None)
        zombie.abandoned = True
        with b._cond:
            b._queue.append(zombie)
            b._cond.notify_all()
        gate.release.set()
        t1.join(timeout=10)
        out = b.submit(np.ones((1, 2), np.float32), timeout=10)
        b.stop()
        np.testing.assert_array_equal(out, 1.0)
        for batch in gate.dispatched:
            assert not np.any(batch == 9.0)      # zombie rows excluded
        assert b.metrics.snapshot()["shed"] == 1

    def test_timeout_race_marks_abandoned_and_excludes_rows(self):
        """The satellite race: an item the worker popped concurrently
        with its client timing out is marked abandoned and its rows are
        dropped before the dispatch group forms."""
        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=4, max_wait_ms=0.0)
        t1 = threading.Thread(target=lambda: b.submit(
            np.zeros((1, 2), np.float32)))
        t1.start()
        assert gate.started.wait(10)
        # queue an item, then mark it abandoned exactly as the timed-out
        # client would (the client-side removal already raced and lost)
        errs = {}

        def client_b():
            try:
                b.submit(np.full((1, 2), 9.0, np.float32), timeout=0.05)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs["b"] = e

        t2 = threading.Thread(target=client_b)
        t2.start()
        t2.join(timeout=10)                  # client timed out already
        assert isinstance(errs["b"], DeadlineExceededError)
        gate.release.set()
        t1.join(timeout=10)
        # one more request proves the worker survived and no 9.0 zombie
        # rows ever dispatched
        out = b.submit(np.ones((1, 2), np.float32), timeout=10)
        np.testing.assert_array_equal(out, 1.0)
        b.stop()
        for batch in gate.dispatched:
            assert not np.any(batch == 9.0)
        snap = b.metrics.snapshot()
        assert snap["queue_depth"] == 0
        assert snap["shed"] == 1             # removed from the queue
        # a bare client-wait timeout is NOT a server-side deadline miss
        assert snap["deadline_missed"] == 0

    def test_lm_expired_request_shed_at_admitter(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1)
        srv.generate([9], 1, timeout=300)    # compile first
        # deadline_s=0: expired on arrival.  However fast the admitter
        # gets to it — slot busy or idle — it must shed the request
        # before it occupies a decode lane, never serve it.
        with pytest.raises(DeadlineExceededError):
            srv.generate([3, 4], 2, deadline_s=0.0, timeout=60)
        snap = srv.stats()
        assert snap["deadline_missed"] == 1
        assert snap["shed"] == 1
        # and the pool still serves live requests afterwards
        out = srv.generate([1, 2], 3, timeout=300)
        srv.stop()
        assert len(out) == 5


# ---------------------------------------------------------------------------
# Poison isolation (the acceptance scenario)


def _lm(max_len=24):
    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=max_len)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class TestPoisonIsolation:
    def test_cobatched_requests_survive_poison_byte_identical(self):
        """ISSUE-4 acceptance: one injected poison request co-batched
        among K good ones — the K good requests return what sequential
        execution gives and ONLY the poison request errors.  "What
        sequential execution gives" is held to 2 ULP: the bisection's
        padded buckets and a request alone are different batch shapes,
        so different XLA programs, and XLA on a CPU does not promise
        them the same last bit."""
        net = _mlp()
        rng = np.random.default_rng(3)
        good = [rng.normal(size=(1, 4)).astype(np.float32)
                for _ in range(6)]
        poison = np.full((1, 4), 7.0, np.float32)
        sequential = [np.asarray(net.output(x)) for x in good]

        engine = ServingEngine(net, ladder=BucketLadder((1, 8)),
                               max_wait_ms=150.0)
        engine.warmup(np.zeros((4,), np.float32))
        wrapped = chaos_dispatch(engine._dispatch,
                                 ServingChaosConfig(poison_value=7.0))
        engine.batcher._dispatch = wrapped
        # prime the worker thread so the storm hits an IDLE worker (the
        # max_wait coalescing window) and all 7 requests share one group
        engine.predict_proba(good[0], timeout=60)

        results = [None] * len(good)
        poison_err = {}
        barrier = threading.Barrier(len(good) + 1)

        def good_client(i):
            barrier.wait()
            results[i] = engine.predict_proba(good[i], timeout=60)

        def poison_client():
            barrier.wait()
            try:
                engine.predict_proba(poison, timeout=60)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                poison_err["e"] = e

        threads = ([threading.Thread(target=good_client, args=(i,))
                    for i in range(len(good))]
                   + [threading.Thread(target=poison_client)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stats = engine.stats()
        engine.stop()
        assert isinstance(poison_err["e"], InjectedDispatchFault)
        for want, got in zip(sequential, results):
            assert got is not None
            np.testing.assert_array_max_ulp(got, want, maxulp=2)
        assert stats["poison_isolated"] == 1
        assert wrapped.calls > 1            # bisection actually dispatched
        # isolated poison leaves the serving plane healthy: breaker closed
        assert stats["breaker_state"] == "closed"

    def test_all_poison_group_fails_wholesale(self):
        gate_cfg = ServingChaosConfig(poison_value=7.0)
        dispatch = chaos_dispatch(lambda x, m, n: x, gate_cfg)
        b = MicroBatcher(dispatch, max_batch=8, max_wait_ms=100.0)
        errs = [None, None]
        barrier = threading.Barrier(2)

        def client(i):
            barrier.wait()
            try:
                b.submit(np.full((1, 3), 7.0, np.float32), timeout=30)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        snap = b.metrics.snapshot()
        b.stop()
        assert all(isinstance(e, InjectedDispatchFault) for e in errs)
        assert snap["poison_isolated"] == 0   # nothing was salvageable

    def test_bisect_depth_zero_disables_isolation(self):
        dispatch = chaos_dispatch(lambda x, m, n: x,
                                  ServingChaosConfig(poison_value=7.0))
        b = MicroBatcher(dispatch, max_batch=8, max_wait_ms=100.0,
                         max_bisect_depth=0)
        errs = [None, None]
        barrier = threading.Barrier(2)
        xs = [np.ones((1, 3), np.float32),
              np.full((1, 3), 7.0, np.float32)]

        def client(i):
            barrier.wait()
            try:
                b.submit(xs[i], timeout=30)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        b.stop()
        # with bisection off the whole group fails together IF the two
        # requests shared a dispatch; a lone good dispatch succeeds
        if errs[0] is not None:
            assert isinstance(errs[0], InjectedDispatchFault)
        assert isinstance(errs[1], InjectedDispatchFault)


# ---------------------------------------------------------------------------
# Circuit breaker on the dispatch path (chaos-injected, deterministic)


class TestBreakerScenario:
    def test_batcher_breaker_opens_fast_fails_and_recovers(self):
        wrapped = chaos_dispatch(
            lambda x, m, n: np.asarray(x),
            ServingChaosConfig(fail_dispatch_steps=(0, 1, 2)))
        breaker = CircuitBreaker(failure_threshold=3, cooldown_s=0.2)
        metrics = ServingMetrics()
        b = MicroBatcher(wrapped, max_batch=4, max_wait_ms=0.0,
                         metrics=metrics, breaker=breaker)
        x = np.ones((1, 2), np.float32)
        for _ in range(3):                   # N consecutive failures
            with pytest.raises(InjectedDispatchFault):
                b.submit(x, timeout=30)
        assert breaker.state == "open"
        assert metrics.snapshot()["breaker_state"] == "open"
        with pytest.raises(CircuitOpenError) as exc:
            b.submit(x, timeout=30)          # fast-fail, no dispatch
        assert exc.value.retry_after_s > 0
        assert wrapped.calls == 3            # the fast-fail never dispatched
        time.sleep(0.25)                     # cooldown elapses
        out = b.submit(x, timeout=30)        # half-open probe succeeds
        np.testing.assert_array_equal(out, 1.0)
        assert breaker.state == "closed"
        snap = metrics.snapshot()
        b.stop()
        assert snap["breaker_state"] == "closed"
        assert snap["breaker_opens"] == 1
        assert snap["rejected"] == 1

    def test_lm_breaker_opens_and_recovers(self):
        cfg, params = _lm()
        breaker = CircuitBreaker(failure_threshold=2, cooldown_s=0.2)
        srv = ContinuousLMServer(cfg, params, slots=2, breaker=breaker)
        assert srv.generate([1, 2], 2, timeout=120)   # healthy + compiled
        real_step = srv._step

        def exploding(*a, **kw):
            raise InjectedDispatchFault("chaos: injected decode fault")

        srv._step = exploding
        for _ in range(2):
            with pytest.raises(InjectedDispatchFault):
                srv.generate([3, 4], 2, timeout=120)
        assert breaker.state == "open"
        assert not srv.ready()
        with pytest.raises(CircuitOpenError):
            srv.generate([5, 6], 2, timeout=120)
        srv._step = real_step
        time.sleep(0.25)
        out = srv.generate([1, 2], 3, timeout=120)    # probe closes it
        assert breaker.state == "closed" and srv.ready()
        snap = srv.stats()
        srv.stop()
        assert len(out) == 5
        assert snap["breaker_opens"] == 1


# ---------------------------------------------------------------------------
# Graceful drain


class TestDrain:
    def test_drain_completes_in_flight_and_stops_admission(self):
        gate = _GatedDispatch()
        b = MicroBatcher(gate, max_batch=1, max_wait_ms=0.0)
        got = {}
        t1 = threading.Thread(target=lambda: got.setdefault(
            "a", b.submit(np.ones((1, 2), np.float32))))
        t1.start()
        assert gate.started.wait(10)
        b.begin_drain()
        with pytest.raises(ServingUnavailableError):
            b.submit(np.zeros((1, 2), np.float32))
        rejected = b.metrics.snapshot()["rejected"]
        gate.release.set()
        assert b.drain(grace_s=10) is True
        t1.join(timeout=10)
        np.testing.assert_array_equal(got["a"], 1.0)
        assert rejected == 1

    def test_drain_grace_expiry_fails_leftovers_typed(self):
        gate = _GatedDispatch()                    # never released in time
        b = MicroBatcher(gate, max_batch=1, max_wait_ms=0.0)
        errs = {}

        def client(tag, x):
            try:
                b.submit(x)
            except BaseException as e:  # noqa: BLE001 — collected for asserts
                errs[tag] = e

        t1 = threading.Thread(target=client,
                              args=("a", np.zeros((1, 2), np.float32)))
        t1.start()
        assert gate.started.wait(10)
        t2 = threading.Thread(target=client,
                              args=("b", np.ones((1, 2), np.float32)))
        t2.start()
        for _ in range(200):
            with b._cond:
                if len(b._queue) == 1:
                    break
            time.sleep(0.005)
        # release AFTER the grace expires so stop() can join the worker
        threading.Timer(0.3, gate.release.set).start()
        assert b.drain(grace_s=0.05) is False      # grace expired
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert isinstance(errs["b"], ServingUnavailableError)

    def test_lm_drain(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1)
        got = {}
        t1 = threading.Thread(target=lambda: got.setdefault(
            "a", srv.generate([1, 2], 4, timeout=120)))
        t1.start()
        for _ in range(400):
            if srv.stats()["active_slots"] == 1:
                break
            time.sleep(0.005)
        srv.begin_drain()
        with pytest.raises(ServingUnavailableError):
            srv.generate([3], 2)
        assert srv.drain(grace_s=60) is True
        t1.join(timeout=10)
        assert len(got["a"]) == 6


# ---------------------------------------------------------------------------
# Overload storm (the satellite test)


class TestOverloadStorm:
    def test_ledger_balances_and_no_request_hangs(self):
        """Concurrency >> max_queue_depth with injected slow dispatches:
        every client resolves (no hang), the shed/rejected counters add
        up to submitted - completed, and the batcher survives."""
        net = _mlp()
        engine = ServingEngine(net, ladder=BucketLadder((1, 8)),
                               max_wait_ms=1.0, max_queue_depth=4,
                               default_deadline_s=2.0)
        engine.warmup(np.zeros((4,), np.float32))
        engine.batcher._dispatch = chaos_dispatch(
            engine._dispatch,
            ServingChaosConfig(slow_dispatch_steps=tuple(range(0, 200, 2)),
                               slow_seconds=0.02))
        n_clients, per_client = 32, 4
        submitted = n_clients * per_client
        outcomes = {"ok": 0, "rejected": 0, "shed": 0}
        lock = threading.Lock()
        barrier = threading.Barrier(n_clients)

        def client(cid):
            rng = np.random.default_rng(cid)   # per-thread: rng isn't
            barrier.wait()                     # thread-safe
            for _ in range(per_client):
                x = rng.normal(size=(1, 4)).astype(np.float32)
                try:
                    engine.predict_proba(x, timeout=30)
                    key = "ok"
                except ServingOverloadError:
                    key = "rejected"
                except DeadlineExceededError:
                    key = "shed"
                with lock:
                    outcomes[key] += 1

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        elapsed = time.perf_counter() - t0
        assert not any(t.is_alive() for t in threads), \
            f"clients hung after {elapsed:.1f}s"
        stats = engine.stats()
        # the batcher thread survived the storm: one more request serves
        out = engine.predict_proba(np.zeros((1, 4), np.float32),
                                   timeout=30)
        engine.stop()
        assert out.shape == (1, 3)
        assert sum(outcomes.values()) == submitted
        assert outcomes["ok"] == stats["requests"]
        assert stats["rejected"] + stats["shed"] \
            == submitted - outcomes["ok"]
        # the queue bound actually bit (32 clients vs depth 4)
        assert outcomes["rejected"] > 0


# ---------------------------------------------------------------------------
# HTTP surface: status mapping, healthz/readyz, breaker over HTTP, drain


def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


class TestHTTPResilience:
    def test_healthz_readyz_and_drain_flip(self):
        from deeplearning4j_tpu.ui.server import UiServer

        net = _mlp()
        srv = UiServer(port=0).serve_model(
            net, max_batch=8, ladder=BucketLadder((1, 8)),
            warmup_example=np.zeros((4,), np.float32)).start()
        try:
            assert _get(srv.url + "/healthz") == {"ok": True}
            assert _get(srv.url + "/readyz") == {"ready": True}
            x = [[0.1, 0.2, 0.3, 0.4]]
            assert len(_post(srv.url + "/model/predict",
                             {"features": x})["predictions"]) == 1
            srv.begin_drain()
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(srv.url + "/readyz")
            assert exc.value.code == 503
            body = json.loads(exc.value.read())
            assert "draining" in body["reasons"]
            # admission stopped: predicts now 503 (typed), not 500/400
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/model/predict", {"features": x})
            assert exc.value.code == 503
            assert exc.value.headers.get("Retry-After") is not None
            assert srv.drain(grace_s=5) is True
            # liveness endpoints keep answering through the drain
            assert _get(srv.url + "/healthz") == {"ok": True}
            snap = srv.serving_stats()
            assert snap["classifier"]["accepting"] is False
        finally:
            srv.stop()

    def test_overload_maps_to_503_with_retry_after(self):
        from deeplearning4j_tpu.ui.server import UiServer

        net = _mlp()
        srv = UiServer(port=0).serve_model(
            net, max_batch=8, ladder=BucketLadder((1, 8)),
            warmup_example=np.zeros((4,), np.float32),
            max_queue_depth=1).start()
        engine = srv.state.engine
        gate = _GatedDispatch()
        engine.batcher._dispatch = gate
        try:
            x = [[0.1, 0.2, 0.3, 0.4]]
            t1 = threading.Thread(target=lambda: _post(
                srv.url + "/model/predict", {"features": x}))
            t1.start()
            assert gate.started.wait(10)     # worker busy
            t2 = threading.Thread(target=lambda: _post(
                srv.url + "/model/predict", {"features": x}))
            t2.start()
            for _ in range(200):
                with engine.batcher._cond:
                    if len(engine.batcher._queue) == 1:
                        break
                time.sleep(0.005)
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/model/predict", {"features": x})
            assert exc.value.code == 503
            assert int(exc.value.headers["Retry-After"]) >= 1
            assert "queue full" in json.loads(exc.value.read())["error"]
            gate.release.set()
            t1.join(timeout=30)
            t2.join(timeout=30)
        finally:
            srv.stop()

    def test_deadline_ms_validation_and_504(self):
        from deeplearning4j_tpu.ui.server import UiServer

        net = _mlp()
        srv = UiServer(port=0).serve_model(
            net, max_batch=8, ladder=BucketLadder((1, 8)),
            warmup_example=np.zeros((4,), np.float32)).start()
        engine = srv.state.engine
        try:
            x = [[0.1, 0.2, 0.3, 0.4]]
            # malformed deadline is a client error
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/model/predict",
                      {"features": x, "deadline_ms": -5})
            assert exc.value.code == 400
            # a deadline that expires while the worker is wedged -> 504
            gate = _GatedDispatch()
            engine.batcher._dispatch = gate
            t1 = threading.Thread(target=lambda: _post(
                srv.url + "/model/predict", {"features": x}))
            t1.start()
            assert gate.started.wait(10)
            got = {}

            def doomed():
                try:
                    _post(srv.url + "/model/predict",
                          {"features": x},
                          headers={"X-Deadline-Ms": "50"})
                except urllib.error.HTTPError as e:
                    got["code"] = e.code
            t2 = threading.Thread(target=doomed)
            t2.start()
            time.sleep(0.15)
            gate.release.set()
            t1.join(timeout=30)
            t2.join(timeout=30)
            assert got["code"] == 504
        finally:
            srv.stop()

    def test_chaos_breaker_scenario_over_http(self):
        """ISSUE-4 acceptance: N injected consecutive dispatch faults
        open the breaker, /readyz flips, admission fast-fails 503, and
        after the cooldown a half-open probe restores service."""
        from deeplearning4j_tpu.ui.server import UiServer

        net = _mlp()
        srv = UiServer(port=0).serve_model(
            net, max_batch=8, ladder=BucketLadder((1, 8)),
            warmup_example=np.zeros((4,), np.float32),
            breaker_threshold=3, breaker_cooldown_s=0.3).start()
        engine = srv.state.engine
        wrapped = chaos_dispatch(
            engine._dispatch,
            ServingChaosConfig(fail_dispatch_steps=(0, 1, 2)))
        engine.batcher._dispatch = wrapped
        try:
            x = [[0.1, 0.2, 0.3, 0.4]]
            assert _get(srv.url + "/readyz") == {"ready": True}
            for _ in range(3):               # N consecutive faults
                with pytest.raises(urllib.error.HTTPError) as exc:
                    _post(srv.url + "/model/predict", {"features": x})
                assert exc.value.code == 400  # device fault surfaces
            with pytest.raises(urllib.error.HTTPError) as exc:
                _get(srv.url + "/readyz")    # breaker open: not ready
            assert exc.value.code == 503
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/model/predict", {"features": x})
            assert exc.value.code == 503     # fast-fail
            assert wrapped.calls == 3        # ...without dispatching
            stats = _get(srv.url + "/serving/stats")["classifier"]
            assert stats["breaker_state"] == "open"
            time.sleep(0.35)                 # cooldown elapses
            out = _post(srv.url + "/model/predict", {"features": x})
            assert len(out["predictions"]) == 1   # probe restored service
            assert _get(srv.url + "/readyz") == {"ready": True}
            stats = _get(srv.url + "/serving/stats")["classifier"]
            assert stats["breaker_state"] == "closed"
            assert stats["breaker_opens"] == 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# CLI: flags + SIGTERM graceful drain


class TestCliServeResilience:
    def test_serve_flags_boot_and_report(self):
        import contextlib
        import io
        import re

        from deeplearning4j_tpu.cli import main as cli_main

        out = io.StringIO()
        rc = {}

        def run():
            with contextlib.redirect_stdout(out):
                rc["rc"] = cli_main(
                    ["serve", "-model", "zoo:iris-mlp", "-port", "0",
                     "-warmup", "-buckets", "1,8", "-max-queue", "8",
                     "-deadline-ms", "500", "-breaker-threshold", "4",
                     "-drain-grace-s", "1", "-serve-seconds", "5"])

        t = threading.Thread(target=run)
        t.start()
        url = None
        for _ in range(120):
            m = re.search(r"Serving on (http://\S+)", out.getvalue())
            if m:
                url = m.group(1)
                break
            time.sleep(0.1)
        assert url, out.getvalue()
        assert "resilience max_queue=8" in out.getvalue()
        assert _get(url + "/healthz") == {"ok": True}
        assert _get(url + "/readyz") == {"ready": True}
        t.join(timeout=60)
        assert rc.get("rc") == 0

    def test_sigterm_drains_and_snapshots_stats(self, tmp_path):
        import contextlib
        import io
        import os
        import re
        import signal

        from deeplearning4j_tpu.cli import main as cli_main

        if threading.current_thread() is not threading.main_thread():
            pytest.skip("SIGTERM handler needs the main thread")
        stats_path = tmp_path / "drain_stats.json"
        out = io.StringIO()
        # deliver SIGTERM to ourselves once the server is up
        killer = {}

        def kill_when_up():
            for _ in range(200):
                if re.search(r"Serving on http://\S+", out.getvalue()):
                    killer["url"] = re.search(
                        r"Serving on (http://\S+)", out.getvalue()).group(1)
                    os.kill(os.getpid(), signal.SIGTERM)
                    return
                time.sleep(0.1)

        t = threading.Thread(target=kill_when_up)
        t.start()
        with contextlib.redirect_stdout(out):
            rc = cli_main(
                ["serve", "-model", "zoo:iris-mlp", "-port", "0",
                 "-warmup", "-buckets", "1,8", "-serve-seconds", "60",
                 "-drain-grace-s", "2",
                 "-drain-stats", str(stats_path)])
        t.join(timeout=30)
        assert rc == 0
        assert "draining" in out.getvalue()
        assert stats_path.exists()
        snap = json.loads(stats_path.read_text())
        assert snap["classifier"]["accepting"] is False
        assert "rejected" in snap["classifier"]
