"""Multi-tenant traffic shaping & SLO control plane (ISSUE-16).

What must hold:

- `TenantRegistry` is THE vocabulary gate: None maps to the built-in
  ``default`` tenant (pre-tenancy clients keep their exact behavior),
  an unknown tenant is a typed refusal naming the registered
  vocabulary — never a silent default.
- The token bucket's 429 carries a Retry-After DERIVED from its own
  refill (deficit / rate), not a constant; while the brownout ladder is
  up the retry is floored at the ladder's real exit timescale
  (down_dwell x observed update cadence).
- WFQ composes UNDER priority: the queue sorts by (rank, vft,
  enqueued), so classes still dominate and weights only interleave
  within a class; with one tenant the key degenerates to the historic
  (rank, enqueued) FIFO — pinned here.
- The HTTP fronts accept the tenant via JSON field or X-Tenant header,
  400 unknown tenants, and 429 + Retry-After over-quota ones; the
  fleet front relays a replica's 429 with its Retry-After intact.
- Per-tenant ledgers re-add to the plane totals; `check_fleet_ledger`
  reports any drift as a named failure and clears `balanced`.
- Composition with PR 15: a compliant tenant's interactive request
  overtakes a flooding tenant's queued best_effort work, and a
  preempted victim still resumes byte-identical with tenancy installed.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.serving import ContinuousLMServer
from deeplearning4j_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    TenantQuotaError,
    TenantRegistry,
    TenantSpec,
)

pytestmark = pytest.mark.tenancy


def _lm(max_len=32, n_layers=1):
    from deeplearning4j_tpu.parallel import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=n_layers, d_ff=32,
                                max_len=max_len)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _want(cfg, params, prompt, new):
    from deeplearning4j_tpu.parallel.generation import generate

    return np.asarray(generate(cfg, params, np.asarray([prompt], np.int32),
                               new))[0].tolist()


def _wait_mid_decode(srv, slot_idx=0, committed=2, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with srv._cond:
            s = srv._slots[slot_idx]
            if (s.active and s.fed >= len(s.req.prompt)
                    and len(s.generated) >= committed):
                return True
        time.sleep(0.002)
    return False


def _post(url, payload, timeout=60, headers=None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers=hdrs)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# ---------------------------------------------------------------------------
# Units: spec validation, registry vocabulary, bucket, WFQ clock, SLO burn


class TestTenantSpec:
    def test_defaults_and_capacity(self):
        s = TenantSpec("a")
        assert s.weight == 1.0 and not s.metered and s.capacity == 0.0
        m = TenantSpec("b", rate=10.0)
        assert m.metered and m.capacity == 40.0   # 4 seconds of rate
        assert TenantSpec("c", rate=10.0, burst=15.0).capacity == 15.0

    def test_validation_is_typed(self):
        with pytest.raises(ValueError, match="weight"):
            TenantSpec("a", weight=0)
        with pytest.raises(ValueError, match="rate"):
            TenantSpec("a", rate=-1)
        with pytest.raises(ValueError, match="slo_budget"):
            TenantSpec("a", slo_budget=0)
        with pytest.raises(ValueError, match="non-empty"):
            TenantSpec("  ")


class TestTenantRegistry:
    def test_default_tenant_always_present(self):
        reg = TenantRegistry()
        assert DEFAULT_TENANT in reg
        assert reg.normalize(None) == DEFAULT_TENANT

    def test_unknown_tenant_names_the_vocabulary(self):
        reg = TenantRegistry([TenantSpec("team-a")])
        with pytest.raises(ValueError, match="team-a"):
            reg.normalize("nobody")

    def test_from_json_and_coerce_contract(self):
        reg = TenantRegistry.from_json(
            '{"a": {"weight": 4, "rate": 100}}')
        assert reg.spec("a").weight == 4.0
        assert TenantRegistry.coerce(None) is None
        assert TenantRegistry.coerce(reg) is reg
        via_dict = TenantRegistry.coerce({"b": {"rate": 5}})
        assert via_dict.spec("b").rate == 5.0
        via_str = TenantRegistry.coerce('{"c": {}}')
        assert "c" in via_str

    def test_from_json_rejects_junk(self):
        with pytest.raises(ValueError, match="parse"):
            TenantRegistry.from_json("{nope")
        with pytest.raises(ValueError, match="object"):
            TenantRegistry.from_json('["a"]')


class TestTokenBucketMeter:
    def _reg(self):
        return TenantRegistry([TenantSpec("b", rate=10.0, burst=20.0)])

    def test_retry_after_is_the_buckets_own_refill(self):
        m = self._reg().meter
        m.charge("b", 20, now=0.0)            # drain the burst
        with pytest.raises(TenantQuotaError) as err:
            m.charge("b", 15, now=0.0)
        # deficit 15 tokens at 10/s -> 1.5s, derived, not a constant
        assert err.value.retry_after_s == pytest.approx(1.5)
        # backing off exactly as told finds the tokens waiting
        m.charge("b", 15, now=1.5)

    def test_unmetered_default_never_throttles(self):
        m = self._reg().meter
        for _ in range(100):
            m.charge(DEFAULT_TENANT, 10**6, now=0.0)
        assert m.ledger(DEFAULT_TENANT)["throttled"] == 0

    def test_ledger_counts_in_out_admitted_throttled(self):
        m = self._reg().meter
        m.charge("b", 8, now=0.0)
        m.record_out("b", 5)
        with pytest.raises(TenantQuotaError):
            m.charge("b", 100, now=0.0)
        led = m.ledger("b")
        assert led == {"tokens_in": 8, "tokens_out": 5,
                       "admitted": 1, "throttled": 1}

    def test_over_quota_window_and_recovery(self):
        m = self._reg().meter
        m.charge("b", 20, now=0.0)
        with pytest.raises(TenantQuotaError):
            m.charge("b", 20, now=0.0)
        assert m.over_quota("b", now=1.0)          # refused 1s ago
        # past the window AND the bucket has refilled: compliant again
        assert not m.over_quota("b", now=30.0)


class TestFairQueueClock:
    def test_single_tenant_vfts_strictly_increase(self):
        reg = TenantRegistry([TenantSpec("a")])
        vfts = [reg.wfq.stamp("a", 4) for _ in range(6)]
        assert vfts == sorted(vfts) and len(set(vfts)) == 6

    def test_weights_share_service_proportionally(self):
        reg = TenantRegistry([TenantSpec("heavy", weight=4.0),
                              TenantSpec("light", weight=1.0)])
        stamps = []
        for i in range(8):     # equal backlogged demand, equal cost
            stamps.append(("heavy", reg.wfq.stamp("heavy", 4), i))
            stamps.append(("light", reg.wfq.stamp("light", 4), i))
        order = sorted(stamps, key=lambda s: (s[1], s[2]))
        # weight 4 vs 1 at equal cost: ~4 heavy dequeues per light one
        first5 = [name for name, _, _ in order[:5]]
        assert first5.count("heavy") == 4 and first5.count("light") == 1

    def test_idle_tenant_reenters_at_vnow_no_banked_credit(self):
        reg = TenantRegistry([TenantSpec("a"), TenantSpec("b")])
        v1 = reg.wfq.stamp("a", 4)
        reg.wfq.advance(100.0)                     # pool serviced a lot
        v2 = reg.wfq.stamp("b", 4)                 # idle until now
        assert v1 < 100.0 < v2                     # no infinite credit


class TestSLOTracker:
    def test_burn_rate_is_over_fraction_over_budget(self):
        reg = TenantRegistry(
            [TenantSpec("a", slo_ms=100.0, slo_budget=0.1)])
        for _ in range(8):
            reg.slo.record("a", 0.05)              # within target
        assert reg.slo.burn_rate("a") == 0.0
        reg.slo.record("a", 0.2)
        reg.slo.record("a", 0.2)                   # 2/10 over, budget .1
        assert reg.slo.burn_rate("a") == pytest.approx(2.0)

    def test_no_slo_means_zero_burn(self):
        reg = TenantRegistry([TenantSpec("a")])
        reg.slo.record("a", 10.0)
        assert reg.slo.burn_rate("a") == 0.0

    def test_badness_orders_quota_over_burn(self):
        reg = TenantRegistry(
            [TenantSpec("hot", slo_ms=10.0, slo_budget=0.05),
             TenantSpec("greedy", rate=10.0, burst=10.0)])
        reg.slo.record("hot", 5.0)                 # burning hard
        reg.meter.charge("greedy", 10, now=0.0)
        with pytest.raises(TenantQuotaError):
            reg.meter.charge("greedy", 10, now=0.0)
        assert reg.badness("greedy", now=0.1) > reg.badness("hot",
                                                            now=0.1)
        assert not reg.compliant("greedy", now=0.1)
        assert reg.any_offender(now=0.1)
        assert reg.compliant(DEFAULT_TENANT, now=0.1)


# ---------------------------------------------------------------------------
# Queue composition: WFQ under priority, the single-tenant FIFO pin


class TestQueueComposition:
    def _server(self, tenants):
        cfg, params = _lm()
        return ContinuousLMServer(cfg, params, slots=1,
                                  page_size=4, tenants=tenants)

    def test_one_tenant_is_the_historic_fifo(self):
        """The PR-15 pin: one class x one tenant must order exactly by
        arrival — tenancy installed but unused changes nothing."""
        srv = self._server({"only": {}})
        try:
            with srv._cond:
                for i in range(5):
                    r = srv._build_request([1 + i], 2, 0.0, 0, None,
                                           None, priority="batch",
                                           tenant="only")
                    r.enqueued = float(i)
                    r.vft = srv.tenants.wfq.stamp("only", r.cost)
                    srv._queue_insert_locked(r)
                order = [int(r.enqueued) for r in srv._queue]
            assert order == [0, 1, 2, 3, 4]
        finally:
            srv.stop()

    def test_priority_rank_dominates_wfq_vft(self):
        srv = self._server({"a": {}, "b": {"weight": 100.0}})
        try:
            with srv._cond:
                # b's tiny vft must NOT let best_effort cut interactive
                r_be = srv._build_request([1], 2, 0.0, 0, None, None,
                                          priority="best_effort",
                                          tenant="b")
                r_be.enqueued, r_be.vft = 0.0, 0.001
                r_ia = srv._build_request([2], 2, 0.0, 0, None, None,
                                          priority="interactive",
                                          tenant="a")
                r_ia.enqueued, r_ia.vft = 1.0, 999.0
                srv._queue_insert_locked(r_be)
                srv._queue_insert_locked(r_ia)
                order = [r.priority for r in srv._queue]
            assert order == ["interactive", "best_effort"]
        finally:
            srv.stop()

    def test_preempted_request_keeps_its_original_vft(self):
        """Re-inserting with the ORIGINAL stamp lands the victim ahead
        of later arrivals of its own class and tenant."""
        srv = self._server({"t": {}})
        try:
            with srv._cond:
                old = srv._build_request([1], 2, 0.0, 0, None, None,
                                         priority="batch", tenant="t")
                old.enqueued = 0.0
                old.vft = srv.tenants.wfq.stamp("t", old.cost)
                late = srv._build_request([2], 2, 0.0, 0, None, None,
                                          priority="batch", tenant="t")
                late.enqueued = 5.0
                late.vft = srv.tenants.wfq.stamp("t", late.cost)
                srv._queue_insert_locked(late)
                srv._queue_insert_locked(old)   # the preempted re-insert
                order = [int(r.enqueued) for r in srv._queue]
            assert order == [0, 5]
        finally:
            srv.stop()

    def test_unknown_tenant_is_a_typed_value_error(self):
        srv = self._server({"a": {}})
        try:
            with pytest.raises(ValueError, match="unknown tenant"):
                srv.generate([1, 2], 2, tenant="nobody")
        finally:
            srv.stop()

    def test_no_registry_rejects_non_default_tenants(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1)
        try:
            with pytest.raises(ValueError, match="tenant"):
                srv.generate([1, 2], 2, tenant="team-a")
            # the built-in name is always honored, registry or not
            srv.warmup()
            out = srv.generate([1, 2], 2, tenant=DEFAULT_TENANT,
                               timeout=600)
            assert out == _want(cfg, params, [1, 2], 2)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Quota enforcement on the pool + the ladder-derived Retry-After floor


class TestQuotaOnThePool:
    def test_over_quota_is_typed_with_derived_retry(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(
            cfg, params, slots=2, page_size=4,
            tenants={"b": {"rate": 10.0, "burst": 10.0}})
        try:
            srv.warmup()
            srv.generate([1, 2], 4, tenant="b", timeout=600)   # cost 6
            with pytest.raises(TenantQuotaError) as err:
                srv.generate([1, 2, 3, 4], 8, tenant="b")      # cost 12
            assert err.value.retry_after_s > 0
            led = srv.tenants.meter.ledger("b")
            assert led["admitted"] == 1 and led["throttled"] == 1
            stats = srv.stats()
            assert stats["tenants"]["b"]["throttled"] == 1
            assert stats["tenants"]["b"]["rejected"] == 1
            assert stats["tenancy"]["b"]["tokens_in"] == 6
        finally:
            srv.stop()

    def test_ladder_retry_after_tracks_observed_cadence(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=4, preempt=True,
                                 brownout=True)
        try:
            with srv._cond:
                dwell = srv._pressure.config.down_dwell
                srv._pressure_tick_s = 0.2
                assert srv._ladder_retry_after_locked() == \
                    pytest.approx(dwell * 0.2)
                srv._pressure_tick_s = 0.001   # floored at 100ms
                assert srv._ladder_retry_after_locked() == 0.1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# MicroBatcher front: quota + per-tenant ledger on the classifier plane


class TestMicroBatcherTenancy:
    def _batcher(self, tenants):
        from deeplearning4j_tpu.serving.batcher import MicroBatcher

        return MicroBatcher(lambda x, mask, n: np.asarray(x) * 2,
                            max_batch=4, max_wait_ms=1.0,
                            tenants=tenants)

    def test_rows_are_the_token_cost_and_ledger_balances(self):
        b = self._batcher({"t": {"rate": 2.0, "burst": 2.0}})
        try:
            out = b.submit(np.ones((2, 3), np.float32), tenant="t")
            assert out.shape == (2, 3)
            with pytest.raises(TenantQuotaError):
                b.submit(np.ones((2, 3), np.float32), tenant="t")
            led = b.tenants.meter.ledger("t")
            assert led["tokens_in"] == 2 and led["throttled"] == 1
            snap = b.metrics.snapshot()
            assert snap["tenants"]["t"]["requests"] == 1
            assert snap["tenants"]["t"]["throttled"] == 1
        finally:
            b.stop()

    def test_unknown_tenant_refused_before_any_charge(self):
        b = self._batcher({"t": {}})
        try:
            with pytest.raises(ValueError, match="unknown tenant"):
                b.submit(np.ones((1, 2), np.float32), tenant="ghost")
            assert b.tenants.meter.ledger("ghost")["tokens_in"] == 0
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# HTTP fronts: JSON field / X-Tenant header, 400 unknown, 429 over-quota


class TestHTTPFronts:
    def _serve(self, tenants):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm()
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=2, tenants=tenants)
        srv.state.lm_server.warmup()
        srv.start()
        return srv, cfg, params

    def test_tenant_field_and_header_both_work(self):
        srv, cfg, params = self._serve({"team-a": {"weight": 2.0}})
        try:
            status, out = _post(srv.url + "/lm/generate",
                                {"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "tenant": "team-a"})
            assert status == 200
            assert out["ids"] == _want(cfg, params, [1, 2, 3], 4)
            status, _ = _post(srv.url + "/lm/generate",
                              {"prompt_ids": [1, 2, 3],
                               "max_new_tokens": 4},
                              headers={"X-Tenant": "team-a"})
            assert status == 200
            stats = json.loads(urllib.request.urlopen(
                srv.url + "/serving/stats", timeout=30).read())
            assert stats["lm"]["tenants"]["team-a"]["requests"] == 2
            text = urllib.request.urlopen(
                srv.url + "/metrics", timeout=30).read().decode()
            assert "serving_lm_tenant_requests_total" in text
            assert 'tenant="team-a"' in text
        finally:
            srv.stop()

    def test_unknown_tenant_is_400_naming_the_vocabulary(self):
        srv, _, _ = self._serve({"team-a": {}})
        try:
            for headers, payload in (
                    (None, {"prompt_ids": [1, 2], "max_new_tokens": 2,
                            "tenant": "ghost"}),
                    ({"X-Tenant": "ghost"},
                     {"prompt_ids": [1, 2], "max_new_tokens": 2})):
                with pytest.raises(urllib.error.HTTPError) as err:
                    _post(srv.url + "/lm/generate", payload,
                          headers=headers)
                assert err.value.code == 400
                assert "team-a" in json.loads(err.value.read())["error"]
        finally:
            srv.stop()

    def test_over_quota_is_429_with_honest_retry_after(self):
        srv, _, _ = self._serve({"b": {"rate": 5.0, "burst": 6.0}})
        try:
            status, _ = _post(srv.url + "/lm/generate",
                              {"prompt_ids": [1, 2], "max_new_tokens": 4,
                               "tenant": "b"})
            assert status == 200
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(srv.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 4,
                       "tenant": "b"})
            assert err.value.code == 429
            assert int(err.value.headers["Retry-After"]) >= 1
            body = json.loads(err.value.read())
            assert body["retry_after_s"] > 0
        finally:
            srv.stop()

    def test_sse_leg_validates_tenant_too(self):
        srv, cfg, params = self._serve({"team-a": {}})
        try:
            req = urllib.request.Request(
                srv.url + "/lm/generate",
                data=json.dumps({"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4, "stream": True,
                                 "tenant": "team-a"}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                body = resp.read().decode()
            done = [json.loads(line[len("data: "):])
                    for line in body.splitlines()
                    if line.startswith("data: ") and "ids" in line]
            assert done[-1]["ids"] == _want(cfg, params, [1, 2, 3], 4)
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(srv.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 2,
                       "stream": True, "tenant": "ghost"})
            assert err.value.code == 400
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Fleet: tenant forwarding, 429 relay, per-tenant aggregation, ledger


class TestFleetTenancy:
    def test_front_forwards_tenant_relays_429_and_aggregates(self):
        from deeplearning4j_tpu.serving.fleet import (
            FleetRouter,
            FleetServer,
            spawn_local_replica,
        )

        cfg, params = _lm()
        router = FleetRouter(
            factory=lambda name: spawn_local_replica(
                name, lm=(cfg, params), lm_slots=2,
                lm_tenants={"team-a": {"weight": 2.0},
                            "b": {"rate": 5.0, "burst": 6.0}}),
            replicas=1)
        front = FleetServer(router, port=0).start()
        try:
            status, out = _post(front.url + "/lm/generate",
                                {"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "tenant": "team-a"})
            assert status == 200
            assert out["ids"] == _want(cfg, params, [1, 2, 3], 4)
            # over-quota at the replica relays as 429 + Retry-After
            status, _ = _post(front.url + "/lm/generate",
                              {"prompt_ids": [1, 2], "max_new_tokens": 4,
                               "tenant": "b"})
            assert status == 200
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(front.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 4,
                       "tenant": "b"})
            assert err.value.code == 429
            assert int(err.value.headers["Retry-After"]) >= 1
            assert json.loads(err.value.read())["retry_after_s"] > 0
            # unknown tenant 400s at the replica and propagates
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(front.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 2,
                       "tenant": "ghost"})
            assert err.value.code == 400
            # /fleet/stats: per-tenant aggregation + reconciled ledger
            stats = json.loads(urllib.request.urlopen(
                front.url + "/fleet/stats", timeout=30).read())
            agg = stats["fleet"]["tenants"]
            assert agg["team-a"]["requests"] == 1
            assert agg["b"]["throttled"] == 1
            assert stats["ledger"]["failures"] == []
            assert stats["ledger"]["balanced"] is True
        finally:
            front.stop()

    def test_ledger_reconciliation_catches_injected_drift(self):
        from deeplearning4j_tpu.serving.fleet import check_fleet_ledger

        def payload(requests, tenant_requests):
            return {"classifier": None,
                    "lm": {"requests": requests, "rejected": 0,
                           "shed": 0, "deadline_missed": 0,
                           "poison_isolated": 0,
                           "tenants": {"a": {"requests":
                                             tenant_requests}}}}

        clean = {"fleet": {"requests": 3, "rejected": 0},
                 "retired": {"aggregate": {}, "lost": 0},
                 "replicas": [{"name": "r0", "state": "active",
                               "stats": payload(3, 3)}]}
        led = check_fleet_ledger(clean)
        assert led["balanced"] and led["failures"] == []
        # drift: the tenant breakdown stops re-adding to the plane total
        drifted = {"fleet": {"requests": 3, "rejected": 0},
                   "retired": {"aggregate": {}, "lost": 0},
                   "replicas": [{"name": "r0", "state": "active",
                                 "stats": payload(3, 2)}]}
        led = check_fleet_ledger(drifted)
        assert not led["balanced"]
        assert len(led["failures"]) == 1
        assert "r0/lm" in led["failures"][0]
        assert "tenants.requests" in led["failures"][0]

    def test_absent_breakdown_sections_are_vacuously_balanced(self):
        from deeplearning4j_tpu.serving.fleet import check_fleet_ledger

        stats = {"fleet": {"requests": 2, "rejected": 0},
                 "retired": {"aggregate": {}, "lost": 0},
                 "replicas": [{"name": "r0", "state": "active",
                               "stats": {"classifier": None,
                                         "lm": {"requests": 2,
                                                "rejected": 0,
                                                "shed": 0,
                                                "deadline_missed": 0,
                                                "poison_isolated": 0}}}]}
        led = check_fleet_ledger(stats)
        assert led["balanced"] and led["failures"] == []


# ---------------------------------------------------------------------------
# Chaos harness + the composition regressions (satellite 3)


class TestTenantChaos:
    def test_flood_is_throttled_to_quota_and_counted(self):
        from deeplearning4j_tpu.resilience.chaos import (
            TenantChaosConfig,
            chaos_tenant,
        )

        cfg, params = _lm()
        srv = ContinuousLMServer(
            cfg, params, slots=2, page_size=4,
            tenants={"flood": {"rate": 20.0, "burst": 8.0}})
        try:
            srv.warmup()
            flood = chaos_tenant(srv, TenantChaosConfig(
                tenant="flood", rate_multiple=5.0, prompt_tokens=4,
                max_new_tokens=4, threads=2, timeout_s=5.0))
            flood.run(1.0)
            st = flood.stats()
            assert st["submitted"] == (st["completed"] + st["throttled"]
                                       + st["rejected"])
            assert st["throttled"] > 0          # the bucket pushed back
            assert st["completed"] > 0          # but quota still flows
        finally:
            srv.stop()

    def test_needs_a_registry(self):
        from deeplearning4j_tpu.resilience.chaos import (
            TenantChaosConfig,
            chaos_tenant,
        )

        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1)
        try:
            with pytest.raises(ValueError, match="registry"):
                chaos_tenant(srv, TenantChaosConfig())
        finally:
            srv.stop()


class TestCompositionRegression:
    def test_compliant_interactive_overtakes_flooding_best_effort(self):
        """Tenant A's interactive request must win the slot over tenant
        B's ALREADY-QUEUED best_effort work — priority composes over
        WFQ exactly as it did pre-tenancy.  The request that holds the
        slot decodes 480 tokens, so both others are queued long before
        it ends (at 6, and under six test workers at 100, it could end
        first on a loaded host, and the best_effort request then had the
        slot before the interactive one existed).  The best_effort
        request decodes 200: `done` is the order in which the CLIENTS'
        threads woke, and at 6 tokens each the loser of the slot ended
        a few milliseconds after the winner, less than a thread's
        wake-up on a loaded host, so the order could read reversed
        (the driver's run of PR 38 did)."""
        cfg, params = _lm(max_len=512)
        srv = ContinuousLMServer(
            cfg, params, slots=1, page_size=4,
            tenants={"team-a": {"weight": 4.0, "slo_ms": 500.0},
                     "team-b": {"weight": 1.0}})
        srv.warmup()
        done = []
        lock = threading.Lock()

        def run(name, prompt, prio, tenant, new=6):
            srv.generate(prompt, new, priority=prio, tenant=tenant,
                         timeout=600)
            with lock:
                done.append(name)

        try:
            t0 = threading.Thread(target=run, args=("first", [1, 2],
                                                    "batch", "team-b",
                                                    480))
            t0.start()
            _wait_mid_decode(srv, committed=1)
            t1 = threading.Thread(target=run, args=("be", [3, 4],
                                                    "best_effort",
                                                    "team-b", 200))
            t1.start()
            deadline = time.perf_counter() + 5
            while time.perf_counter() < deadline:
                with srv._cond:
                    if srv._queue:
                        break
                time.sleep(0.002)
            t2 = threading.Thread(target=run, args=("ia", [5, 6],
                                                    "interactive",
                                                    "team-a"))
            t2.start()
            for t in (t0, t1, t2):
                t.join(timeout=600)
            assert done.index("ia") < done.index("be")
        finally:
            srv.stop()

    def test_preempted_victim_resumes_byte_identical_with_tenancy(self):
        """Pool-dry preemption round trip with a registry installed:
        the best_effort victim's KV lane swaps out to host, restores,
        and its final output matches the uncontended reference — and
        the per-tenant ledgers still re-add to the plane totals."""
        import jax.monitoring

        cfg, params = _lm()
        srv = ContinuousLMServer(
            cfg, params, slots=2, page_size=4, pages=8,
            prefill_chunk=4, preempt=True,
            tenants={"team-a": {"weight": 4.0},
                     "team-b": {"weight": 1.0}})
        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        res = {}
        try:
            srv.warmup()
            jax.monitoring.register_event_duration_secs_listener(
                listener)
            try:
                def victim():
                    res["victim"] = srv.generate(
                        [1, 2, 3], 28, priority="best_effort",
                        tenant="team-b", timeout=600)

                t1 = threading.Thread(target=victim)
                t1.start()
                assert _wait_mid_decode(srv)
                res["ia"] = srv.generate([4, 5, 6, 7], 8,
                                         priority="interactive",
                                         tenant="team-a", timeout=600)
                t1.join(timeout=600)
            finally:
                jax.monitoring.clear_event_listeners()
            assert res["victim"] == _want(cfg, params, [1, 2, 3], 28)
            assert res["ia"] == _want(cfg, params, [4, 5, 6, 7], 8)
            stats = srv.stats()
            assert stats.get("preemptions", 0) >= 1
            assert stats["tenants"]["team-b"]["preempted"] >= 1
            # off-ladder compiles stay zero: tenancy adds policy, not
            # shapes
            assert compiles == []
            # the per-tenant ledger re-adds to the plane totals even
            # across a preempt/restore round trip
            for ev in ("requests", "rejected", "shed",
                       "deadline_missed"):
                part = sum(int(c.get(ev) or 0)
                           for c in stats["tenants"].values())
                assert part == int(stats.get(ev) or 0), ev
            with srv._cond:
                assert srv._pool.check_ledger()["balanced"]
        finally:
            srv.stop()

    def test_l4_shed_spares_compliant_tenants(self):
        """Brownout L4 with an offender present: the compliant tenant's
        best_effort request still admits; the offender's is shed with
        the ladder-derived Retry-After."""
        from deeplearning4j_tpu.serving.resilience import (
            ServingOverloadError,
        )

        cfg, params = _lm()
        srv = ContinuousLMServer(
            cfg, params, slots=2, page_size=4,
            preempt=True, brownout=True,
            tenants={"good": {"weight": 1.0},
                     "bad": {"slo_ms": 1.0, "slo_budget": 0.01}})
        try:
            srv.warmup()
            # make "bad" an offender via SLO burn (unmetered, so its
            # requests still reach the L4 gate rather than 429ing)
            for _ in range(4):
                srv.tenants.slo.record("bad", 1.0)   # 1s >> 1ms target
            assert not srv.tenants.compliant("bad")
            assert srv.tenants.any_offender()
            with srv._cond:
                srv._pressure.level = 4   # force the top rung
            with pytest.raises(ServingOverloadError) as err:
                srv.generate([1, 2], 2, priority="best_effort",
                             tenant="bad")
            assert err.value.retry_after_s >= 0.1
            # the compliant tenant's best_effort still admits — the
            # L4 shed would have raised inside _enqueue — and is served
            r = srv._build_request([3, 4], 2, 0.0, 0, None, None,
                                   priority="best_effort",
                                   tenant="good")
            srv._enqueue(r)
            assert srv._wait(r, timeout=600) == _want(cfg, params,
                                                      [3, 4], 2)
        finally:
            srv.stop()

    def test_429_retry_is_floored_at_the_ladder_exit_while_up(self):
        """Satellite 1: tokens refilling sooner than the pool recovers
        would invite the flood straight back — while the ladder is up
        the 429's Retry-After is max(bucket refill, ladder dwell)."""
        cfg, params = _lm()
        srv = ContinuousLMServer(
            cfg, params, slots=2, page_size=4,
            preempt=True, brownout=True,
            tenants={"b": {"rate": 1000.0, "burst": 6.0}})
        try:
            srv.tenants.meter.charge("b", 6)      # drain the burst
            with srv._cond:
                srv._pressure.level = 1
                srv._pressure_tick_s = 2.0        # dwell = 3 x 2s = 6s
            with pytest.raises(TenantQuotaError) as err:
                srv.generate([1, 2], 2, tenant="b")
            # the bare bucket refill would be ~4 tokens / 1000 per s;
            # the ladder floor dominates
            assert err.value.retry_after_s == pytest.approx(6.0)
        finally:
            srv.stop()
