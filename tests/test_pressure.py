"""Overload-survival tests (ISSUE-15 acceptance surface).

Covers: the priority vocabulary and the priority-ordered admission
queue (one class == the historic FIFO); the host `SwapStore`'s LRU
byte-cap economy and typed eviction; the `BrownoutLadder` automaton's
enter/exit hysteresis in both directions; KV lane preemption with host
swap-out — a preempted lane (greedy AND seeded sampling, streaming,
speculating) resumes BYTE-IDENTICALLY to an unpreempted run with the
page ledger balanced and zero off-ladder compiles after warmup; the
recompute-from-prompt fallback when swap state is evicted or corrupted
(the wire frame's SHA-256 check detects a flipped byte, the victim
request alone carries the typed error in its trace, output stays
byte-identical); the pool-exhaustion FIFO regression that pins
pre-preemption behavior (never deadlocks, ledger balanced); priority
on the HTTP fronts (single serve and fleet, incl. a typed 400 for an
unknown class); brownout level-4 shedding of best_effort admissions
with interactive untouched; and the role-aware queue-depth autoscale
split (`fleet_queue_depth{role}`).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.resilience.chaos import (
    PoolChaosConfig,
    SwapChaosConfig,
    chaos_pool,
    chaos_swap,
)
from deeplearning4j_tpu.serving import ContinuousLMServer
from deeplearning4j_tpu.serving.pressure import (
    BROWNOUT_LEVELS,
    BrownoutLadder,
    PRIORITY_CLASSES,
    PressureConfig,
    SwapEvictedError,
    SwapStore,
    normalize_priority,
)
from deeplearning4j_tpu.serving.resilience import ServingOverloadError

pytestmark = pytest.mark.pressure


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
    """Block until the lane in `slot_idx` has fed its prompt and
    committed at least `committed` tokens (it is preemptible
    mid-decode)."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with srv._cond:
            s = srv._slots[slot_idx]
            if (s.active and s.fed >= len(s.req.prompt)
                    and len(s.generated) >= committed):
                return True
        time.sleep(0.002)
    return False


# ---------------------------------------------------------------------------
# Units: priority vocabulary, swap store, ladder automaton (no device)


class TestPriorityVocabulary:
    def test_normalize_defaults_and_validates(self):
        assert normalize_priority(None) == "interactive"
        for c in PRIORITY_CLASSES:
            assert normalize_priority(c) == c
        with pytest.raises(ValueError, match="priority must be one of"):
            normalize_priority("urgent")

    def test_export_priority_rides_the_wire(self):
        from deeplearning4j_tpu.serving.transfer import (
            PageExport,
            deserialize_export,
            serialize_export,
        )

        pages = np.zeros((1, 1, 4, 2, 8), np.float32)
        ex = PageExport(prompt=[1, 2, 3, 4], max_new=4, temperature=0.0,
                        seed=0, committed=[5], pos=4, page_size=4,
                        pages_k=pages, pages_v=pages,
                        model={"n_layers": 1}, priority="best_effort")
        back = deserialize_export(serialize_export(ex))
        assert back.priority == "best_effort"
        # a pre-ISSUE-15 frame (no priority header) stays interactive
        ex2 = PageExport(prompt=[1, 2, 3, 4], max_new=4, temperature=0.0,
                         seed=0, committed=[5], pos=4, page_size=4,
                         pages_k=pages, pages_v=pages,
                         model={"n_layers": 1})
        assert deserialize_export(serialize_export(ex2)).priority == \
            "interactive"


class TestSwapStore:
    def test_round_trip_and_counters(self):
        s = SwapStore(capacity_bytes=1000)
        assert s.put("a", b"x" * 100) == []
        assert s.take("a") == b"x" * 100
        assert s.bytes_stored == 0
        assert s.puts == 1 and s.takes == 1 and s.evicted == 0

    def test_byte_cap_evicts_lru_first(self):
        s = SwapStore(capacity_bytes=250)
        s.put("a", b"a" * 100)
        s.put("b", b"b" * 100)
        evicted = s.put("c", b"c" * 100)     # must evict the oldest
        assert evicted == ["a"]
        assert s.take("b") and s.take("c")
        with pytest.raises(SwapEvictedError):
            s.take("a")
        assert s.evicted == 1

    def test_oversized_blob_is_refused_not_destructive(self):
        s = SwapStore(capacity_bytes=100)
        s.put("a", b"a" * 80)
        assert s.put("big", b"x" * 101) is None   # refused
        assert s.rejected == 1
        assert s.take("a") == b"a" * 80           # others untouched

    def test_discard_and_peak(self):
        s = SwapStore(capacity_bytes=300)
        s.put("a", b"a" * 100)
        s.put("b", b"b" * 150)
        assert s.peak_bytes == 250
        s.discard("a")
        s.discard("missing")                      # no-op, no raise
        assert s.bytes_stored == 150
        assert s.stats()["entries"] == 1

    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            SwapStore(0)


class TestBrownoutLadder:
    def _ladder(self, dwell=2):
        return BrownoutLadder(PressureConfig(
            enter_free_frac=(0.5, 0.25, 0.125, 0.05),
            enter_queue_ratio=(2.0, 4.0, 8.0, 16.0),
            exit_free_margin=0.1, exit_queue_factor=0.5,
            down_dwell=dwell))

    def test_enters_levels_from_either_signal(self):
        lad = self._ladder()
        assert lad.update(10, 10, 0, 4) == []          # healthy
        assert lad.update(4, 10, 0, 4) == [(0, 1)]     # free 0.4 -> L1
        assert lad.update(2, 10, 0, 4) == [(1, 2)]     # free 0.2 -> L2
        lad2 = self._ladder()
        assert lad2.update(10, 10, 20, 4) == [(0, 2)]  # queue 5/slot

    def test_sudden_exhaustion_jumps_up_immediately(self):
        lad = self._ladder()
        assert lad.update(0, 10, 40, 4) == [(0, 4)]
        assert lad.level == 4
        assert BROWNOUT_LEVELS[lad.level] == "shed"

    def test_down_needs_margin_and_dwell_one_step_at_a_time(self):
        lad = self._ladder(dwell=2)
        lad.update(1, 10, 0, 4)                        # -> L3 (0.1 free)
        assert lad.level == 3
        # hovering just above the enter threshold is NOT calm (the
        # margin is the hysteresis): no step down, ever
        for _ in range(5):
            assert lad.update(2, 10, 0, 4) == []       # 0.2 <= 0.125+0.1
        # calm for one update only: dwell not met
        assert lad.update(10, 10, 0, 4) == []
        # a pressure blip resets the dwell counter
        assert lad.update(2, 10, 0, 4) == []
        assert lad.update(10, 10, 0, 4) == []
        assert lad.update(10, 10, 0, 4) == [(3, 2)]    # dwell met
        assert lad.level == 2
        assert lad.transitions_down == 1

    def test_transitions_counted_and_history_bounded(self):
        lad = self._ladder(dwell=1)
        lad.update(0, 10, 0, 4)
        for _ in range(4):
            lad.update(10, 10, 0, 4)
        st = lad.stats()
        assert st["level"] == 0
        assert st["transitions_up"] == 1
        assert st["transitions_down"] == 4
        assert lad.transitions == 5
        assert len(st["recent"]) == 5

    def test_config_validation(self):
        with pytest.raises(ValueError, match="non-increasing"):
            PressureConfig(enter_free_frac=(0.1, 0.5),
                           enter_queue_ratio=(2.0, 4.0))
        with pytest.raises(ValueError, match="same number"):
            PressureConfig(enter_free_frac=(0.5,),
                           enter_queue_ratio=(2.0, 4.0))


# ---------------------------------------------------------------------------
# Priority-ordered admission (queue order only — no pages needed)


class TestPriorityAdmission:
    def test_queue_is_priority_then_fifo_ordered(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=4)
        try:
            reqs = []
            for i, p in enumerate(["batch", "best_effort", "batch",
                                   "interactive", "best_effort"]):
                r = srv._build_request([1 + i], 2, 0.0, 0, None, None,
                                       priority=p)
                r.enqueued = float(i)   # deterministic arrival order
                reqs.append(r)
            with srv._cond:
                for r in reqs:
                    srv._queue_insert_locked(r)
                order = [(r.priority, int(r.enqueued))
                         for r in srv._queue]
            assert order == [("interactive", 3), ("batch", 0),
                             ("batch", 2), ("best_effort", 1),
                             ("best_effort", 4)]
        finally:
            srv.stop()

    def test_interactive_overtakes_queued_best_effort(self):
        cfg, params = _lm(max_len=128)
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=4)
        srv.warmup()
        done = []
        lock = threading.Lock()

        def run(name, prompt, prio, new=6):
            srv.generate(prompt, new, priority=prio, timeout=600)
            with lock:
                done.append(name)

        try:
            # the slot's holder decodes 100 tokens: two thread starts on
            # a loaded host have outlasted 6 (a whole run of PR 40)
            t0 = threading.Thread(target=run,
                                  args=("first", [1, 2], "batch", 100))
            t0.start()
            _wait_mid_decode(srv, committed=1)
            # while the slot is busy: best_effort queues first,
            # interactive second — interactive must still win the slot
            t1 = threading.Thread(target=run,
                                  args=("be", [3, 4], "best_effort"))
            t1.start()
            deadline = time.perf_counter() + 5
            while time.perf_counter() < deadline:
                with srv._cond:
                    if srv._queue:
                        break
                time.sleep(0.002)
            t2 = threading.Thread(target=run,
                                  args=("ia", [5, 6], "interactive"))
            t2.start()
            for t in (t0, t1, t2):
                t.join(timeout=600)
            assert done.index("ia") < done.index("be")
        finally:
            srv.stop()

    def test_prefill_export_carries_the_class(self):
        """A disaggregated split must not launder best_effort into
        interactive: the prefill worker's export stamps the class and
        the decode pool admits under it."""
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, ship=True)
        try:
            ex = srv.prefill_export([1, 2, 3, 4, 5], 4,
                                    priority="best_effort",
                                    timeout=600)
            assert ex.priority == "best_effort"
        finally:
            srv.stop()

    def test_unknown_priority_is_a_typed_value_error(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1)
        try:
            with pytest.raises(ValueError, match="priority"):
                srv.generate([1, 2], 2, priority="urgent")
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Preemption with host swap-out: the byte-parity acceptance


class TestPreemptionParity:
    def _preempt_run(self, *, victim_kw, swap_chaos=None,
                     speculate="off", swap_bytes=64 << 20):
        """One contended run: a best_effort victim fills the pool
        mid-decode, an interactive arrival preempts it.  Returns
        (victim_out, interactive_out, stats, compiles)."""
        import jax.monitoring

        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, pages=8, prefill_chunk=4,
                                 preempt=True, swap_bytes=swap_bytes,
                                 speculate=speculate)
        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        res = {}
        try:
            srv.warmup()
            if swap_chaos is not None:
                with srv._cond:
                    chaos_swap(srv._swap, swap_chaos)
            jax.monitoring.register_event_duration_secs_listener(
                listener)
            try:
                def victim():
                    res["victim"] = srv.generate(
                        [1, 2, 3], 28, priority="best_effort",
                        timeout=600, **victim_kw)

                t1 = threading.Thread(target=victim)
                t1.start()
                assert _wait_mid_decode(srv)
                res["ia"] = srv.generate([4, 5, 6, 7], 8,
                                         priority="interactive",
                                         timeout=600)
                t1.join(timeout=600)
            finally:
                jax.monitoring.clear_event_listeners()
            stats = srv.stats()
            with srv._cond:
                ledger = srv._pool.check_ledger()
            assert ledger["balanced"], ledger
        finally:
            srv.stop()
        return res["victim"], res["ia"], stats, compiles

    def test_greedy_victim_resumes_byte_identical(self):
        cfg, params = _lm()
        victim, ia, stats, compiles = self._preempt_run(victim_kw={})
        assert stats.get("preemptions", 0) >= 1
        assert stats["swap"]["out"] >= 1 and stats["swap"]["in"] >= 1
        assert victim == _want(cfg, params, [1, 2, 3], 28)
        assert ia == _want(cfg, params, [4, 5, 6, 7], 8)
        assert not compiles, "preemption must not mint programs"
        # per-class ledger carries both classes
        assert stats["priority"]["interactive"]["requests"] == 1
        assert stats["priority"]["best_effort"]["requests"] == 1

    def test_seeded_sampling_victim_resumes_byte_identical(self):
        cfg, params = _lm()
        victim, _, stats, _ = self._preempt_run(
            victim_kw={"seed": 7, "temperature": 0.7})
        assert stats.get("preemptions", 0) >= 1
        ref_srv = ContinuousLMServer(cfg, params, slots=1,
                                     page_size=4)
        try:
            ref = ref_srv.generate([1, 2, 3], 28, seed=7,
                                   temperature=0.7, timeout=600)
        finally:
            ref_srv.stop()
        assert victim == ref

    def test_speculating_victim_resumes_byte_identical(self):
        cfg, params = _lm()
        victim, ia, stats, compiles = self._preempt_run(
            victim_kw={}, speculate="ngram")
        assert stats.get("preemptions", 0) >= 1
        assert victim == _want(cfg, params, [1, 2, 3], 28)
        assert ia == _want(cfg, params, [4, 5, 6, 7], 8)
        assert not compiles

    def test_evicted_swap_recomputes_byte_identical(self):
        cfg, params = _lm()
        victim, _, stats, _ = self._preempt_run(
            victim_kw={}, swap_chaos=SwapChaosConfig(drop_puts=(0,)))
        assert stats.get("preemptions", 0) >= 1
        assert stats["swap"]["evicted"] >= 1
        assert stats["swap"]["in"] == 0          # nothing restored
        assert victim == _want(cfg, params, [1, 2, 3], 28)

    def test_corrupted_swap_detected_and_recomputed(self):
        """Chaos acceptance: a flipped byte in the stored export fails
        the wire frame's SHA-256 check at restore; the typed error
        lands on exactly the victim request (its trace/ledger), the
        lane recomputes from its prompt, and the output is still
        byte-identical — never a wrong token."""
        cfg, params = _lm()
        victim, ia, stats, _ = self._preempt_run(
            victim_kw={}, swap_chaos=SwapChaosConfig(corrupt_puts=(0,)))
        assert stats.get("preemptions", 0) >= 1
        assert stats["swap"]["corrupt"] >= 1
        assert stats["swap"]["in"] == 0
        assert victim == _want(cfg, params, [1, 2, 3], 28)
        assert ia == _want(cfg, params, [4, 5, 6, 7], 8)

    def test_streamed_victim_never_duplicates_tokens(self):
        """A preempted streaming lane must stream each committed token
        exactly once — including across a lost-swap recompute, where
        the early tokens are regenerated (byte-identically) and must
        not be re-pushed."""
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, pages=8, prefill_chunk=4,
                                 preempt=True)
        try:
            srv.warmup()
            with srv._cond:
                chaos_swap(srv._swap, SwapChaosConfig(drop_puts=(0,)))
            toks = []

            def victim():
                for t in srv.generate_stream([1, 2, 3], 28,
                                             priority="best_effort",
                                             timeout=600):
                    toks.append(t)

            t1 = threading.Thread(target=victim)
            t1.start()
            assert _wait_mid_decode(srv)
            srv.generate([4, 5, 6, 7], 8, priority="interactive",
                         timeout=600)
            t1.join(timeout=600)
            assert srv.stats().get("preemptions", 0) >= 1
            assert [1, 2, 3] + toks == _want(cfg, params, [1, 2, 3], 28)
        finally:
            srv.stop()

    def test_compiled_programs_counts_the_swap_pair(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, preempt=True)
        try:
            # decode + chunk + copy + gather + install
            assert srv.warmup() == srv.compiled_programs() == 5
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Satellite: pool-exhaustion FIFO regression (pins pre-preemption path)


class TestExhaustionRegression:
    def test_exhaustion_storm_never_deadlocks_fifo(self):
        """A storm that fully exhausts the pool with mixed request
        sizes, preemption OFF: every request completes (head-of-line
        FIFO waits, never a deadlock) and the page ledger balances.
        This pins the behavior preemption composes on top of."""
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=4,
                                 page_size=4, pages=10, prefill_chunk=4)
        try:
            srv.warmup()
            rng = np.random.default_rng(0)
            prompts = [rng.integers(0, cfg.vocab_size,
                                    (int(n),)).tolist()
                       for n in rng.integers(2, 9, (24,))]
            news = [int(n) for n in rng.integers(4, 20, (24,))]
            results = [None] * 24
            errors = []

            def client(i):
                try:
                    results[i] = srv.generate(prompts[i], news[i],
                                              timeout=600)
                except BaseException as e:  # noqa: BLE001 — surfaced below
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            assert not errors, errors[:3]
            assert all(r is not None for r in results)
            for i in (0, 7, 23):
                assert results[i] == _want(cfg, params, prompts[i],
                                           news[i])
            with srv._cond:
                assert srv._pool.check_ledger()["balanced"]
        finally:
            srv.stop()

    def test_denied_allocs_only_delay_admission(self):
        """chaos_pool: alloc denials (deterministic exhaustion) stall
        the head request for a round, never wedge it or unbalance the
        ledger."""
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, prefill_chunk=4)
        try:
            srv.warmup()
            with srv._cond:
                chaos = chaos_pool(srv._pool,
                                   PoolChaosConfig(deny_allocs=(0, 1)))
            out = srv.generate([1, 2, 3], 6, timeout=600)
            assert out == _want(cfg, params, [1, 2, 3], 6)
            assert chaos.allocs >= 3     # denied twice, then granted
            with srv._cond:
                assert srv._pool.check_ledger()["balanced"]
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Brownout ladder wired into the pool


class TestBrownoutWiring:
    def test_level4_sheds_best_effort_only(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, brownout=True)
        try:
            srv.warmup()
            with srv._cond:
                srv._pressure.level = 4
            with pytest.raises(ServingOverloadError,
                               match="brownout level 4"):
                srv.generate([1, 2], 2, priority="best_effort",
                             timeout=600)
            # interactive (and batch) admit right through level 4
            out = srv.generate([1, 2], 2, priority="interactive",
                               timeout=600)
            assert out == _want(cfg, params, [1, 2], 2)
            st = srv.stats()
            assert st["brownout"]["shed"] == 1
            assert st["priority"]["best_effort"]["rejected"] == 1
        finally:
            srv.stop()

    def test_pressure_storm_counts_transitions_and_recovers(self):
        """Drive the ladder with real pool pressure: a tight pool under
        a multi-request storm climbs the ladder (transitions counted in
        stats + metrics), then steps back down once idle (hysteresis
        dwell) — every move counted, level visible in stats()."""
        cfg, params = _lm()
        srv = ContinuousLMServer(
            cfg, params, slots=4, page_size=4, pages=10,
            prefill_chunk=4, preempt=True,
            brownout=PressureConfig(
                enter_free_frac=(0.8, 0.5, 0.3, 0.1),
                enter_queue_ratio=(1.0, 2.0, 4.0, 100.0),
                exit_free_margin=0.1, exit_queue_factor=0.5,
                down_dwell=2))
        try:
            srv.warmup()
            rng = np.random.default_rng(1)
            prompts = [rng.integers(0, cfg.vocab_size, (6,)).tolist()
                       for _ in range(16)]
            threads = [threading.Thread(
                target=lambda p=p: srv.generate(
                    p, 12, priority="batch", timeout=600))
                for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            st = srv.stats()
            br = st["pressure"]["brownout"]
            assert br["transitions_up"] >= 1
            assert st["brownout"]["transitions"] >= 1   # metrics side
            # idle rounds decay the ladder back to healthy
            deadline = time.perf_counter() + 10
            while time.perf_counter() < deadline:
                with srv._cond:
                    if srv._pressure.level == 0:
                        break
                time.sleep(0.05)
            with srv._cond:
                assert srv._pressure.level == 0
            assert srv.stats()["pressure"]["brownout"][
                "transitions_down"] >= 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# HTTP fronts: priority accepted everywhere, typed 400 on junk


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


class TestHTTPFronts:
    def test_priority_on_lm_generate_and_stats(self):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm()
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=2, preempt=True, brownout=True)
        srv.state.lm_server.warmup()
        srv.start()
        try:
            status, out = _post(srv.url + "/lm/generate",
                                {"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "priority": "batch"})
            assert status == 200
            assert out["ids"] == _want(cfg, params, [1, 2, 3], 4)
            stats = json.loads(urllib.request.urlopen(
                srv.url + "/serving/stats", timeout=30).read())
            assert stats["lm"]["priority"]["batch"]["requests"] == 1
            assert stats["lm"]["pressure"]["preempt"] is True
            # the exposition carries the new families
            text = urllib.request.urlopen(
                srv.url + "/metrics", timeout=30).read().decode()
            assert "serving_brownout_level" in text
            assert 'serving_lm_class_requests_total' in text
        finally:
            srv.stop()

    def test_unknown_priority_is_400_on_the_front(self):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm()
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=2)
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(srv.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 2,
                       "priority": "urgent"})
            assert err.value.code == 400
            assert "priority" in json.loads(err.value.read())["error"]
        finally:
            srv.stop()

    def test_priority_streams_through_sse(self):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm()
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=2)
        srv.state.lm_server.warmup()
        srv.start()
        try:
            req = urllib.request.Request(
                srv.url + "/lm/generate",
                data=json.dumps({"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4, "stream": True,
                                 "priority": "best_effort"}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/event-stream")
                body = resp.read().decode()
            done = [json.loads(line[len("data: "):])
                    for line in body.splitlines()
                    if line.startswith("data: ") and "ids" in line]
            assert done[-1]["ids"] == _want(cfg, params, [1, 2, 3], 4)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Fleet: priority forwarding + role-aware autoscale signals


class _FakeReplica:
    """Router-shaped stand-in for autoscale unit tests (no HTTP)."""

    def __init__(self, name, role, in_flight=0):
        from deeplearning4j_tpu.serving.fleet import REPLICA_ACTIVE

        self.name = name
        self.url = f"http://127.0.0.1:1/{name}"
        self.role = role
        self.in_flight = in_flight
        self.state = REPLICA_ACTIVE
        self.breaker = None
        self.version = 0
        self.server = None
        self.process = None
        self.lock = threading.Lock()
        self.dispatches = self.failures = 0
        self.ejections = self.readmissions = 0

    def routable(self):
        return True

    def _on_breaker(self, state):
        pass

    def begin_drain(self):
        pass

    def drain(self, grace_s=5.0):
        return True

    def stop(self):
        pass

    def summary(self):
        return {"name": self.name, "state": self.state,
                "role": self.role}


class TestRoleAwareAutoscale:
    def _router(self, replicas, factory=None, **kw):
        from deeplearning4j_tpu.serving.fleet import FleetRouter

        router = FleetRouter(factory=factory, scale_up_depth=4.0,
                             scale_down_depth=0.5, max_replicas=8, **kw)
        for r in replicas:
            with router._lock:
                router._replicas.append(r)
        return router

    def test_queue_depth_splits_per_role(self):
        router = self._router([
            _FakeReplica("p0", "prefill", in_flight=7),
            _FakeReplica("d0", "decode", in_flight=1),
            _FakeReplica("d1", "decode", in_flight=2)])
        depths = router.queue_depth_by_role()
        assert depths == {"prefill": 7, "decode": 3}
        stats = router.fleet_stats(include_replica_stats=False)
        assert stats["fleet"]["queue_depth_by_role"] == depths

    def test_scale_up_grows_the_loaded_role_only(self):
        spawned = []

        def factory(name):
            r = _FakeReplica(name, "both")
            spawned.append(r)
            return r

        # prefill pool saturated (mean 7), decode idle: the new
        # replica must join the PREFILL pool
        router = self._router([
            _FakeReplica("p0", "prefill", in_flight=7),
            _FakeReplica("d0", "decode", in_flight=0)], factory=factory)
        assert router.autoscale_tick() == 1
        assert spawned and spawned[0].role == "prefill"

    def test_role_aware_factory_receives_the_role(self):
        """A factory that declares a `role` kwarg builds the worker FOR
        its role (e.g. a ship-capable pool for a prefill worker)
        instead of being re-stamped after the fact."""
        seen = []

        def factory(name, role=None):
            seen.append(role)
            return _FakeReplica(name, role or "both")

        router = self._router([
            _FakeReplica("p0", "prefill", in_flight=7),
            _FakeReplica("d0", "decode", in_flight=0)], factory=factory)
        assert router.autoscale_tick() == 1
        assert seen == ["prefill"]
        assert router.replicas()[-1].role == "prefill"

    def test_scale_down_never_drains_a_roles_last_replica(self):
        router = self._router([
            _FakeReplica("p0", "prefill", in_flight=0),
            _FakeReplica("d0", "decode", in_flight=0),
            _FakeReplica("d1", "decode", in_flight=0)],
            min_replicas=1)
        # both roles are idle; only the decode pool has a spare
        assert router.autoscale_tick() == -1
        names = [r.name for r in router.replicas()]
        assert "p0" in names and len(names) == 2

    def test_single_role_fleet_keeps_historic_semantics(self):
        spawned = []

        def factory(name):
            r = _FakeReplica(name, "both")
            spawned.append(r)
            return r

        router = self._router(
            [_FakeReplica("r0", "both", in_flight=9)], factory=factory)
        assert router.autoscale_tick() == 1
        assert spawned[0].role == "both"   # not re-stamped

    def test_metrics_gauge_carries_role_labels(self):
        from deeplearning4j_tpu.serving.fleet import FleetServer

        router = self._router([
            _FakeReplica("p0", "prefill", in_flight=3),
            _FakeReplica("d0", "decode", in_flight=1)])
        front = FleetServer(router, port=0).start()
        try:
            text = urllib.request.urlopen(
                front.url + "/metrics", timeout=30).read().decode()
            assert 'fleet_queue_depth{role="prefill"} 3' in text
            assert 'fleet_queue_depth{role="decode"} 1' in text
        finally:
            front._server.shutdown()
            front._server.server_close()

    def test_fleet_front_forwards_priority(self):
        from deeplearning4j_tpu.serving.fleet import (
            FleetRouter,
            FleetServer,
            spawn_local_replica,
        )

        cfg, params = _lm()
        router = FleetRouter(
            factory=lambda name: spawn_local_replica(
                name, lm=(cfg, params), lm_slots=2, lm_preempt=True),
            replicas=1)
        front = FleetServer(router, port=0).start()
        try:
            status, out = _post(front.url + "/lm/generate",
                                {"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "priority": "batch"})
            assert status == 200
            assert out["ids"] == _want(cfg, params, [1, 2, 3], 4)
            stats = router.fleet_stats()
            entry = stats["replicas"][0]["stats"]["lm"]
            assert entry["priority"]["batch"]["requests"] == 1
            # an unknown class 400s at the replica and propagates
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(front.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 2,
                       "priority": "urgent"})
            assert err.value.code == 400
        finally:
            front.stop()
