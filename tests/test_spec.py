"""Speculative multi-token decode tests (ISSUE-13 acceptance surface).

Covers: the n-gram/prompt-lookup drafter's proposal properties on
random token streams (every proposal continues a historical suffix
occurrence, never exceeds the budget, degenerate inputs propose
nothing); the small-model drafter's lane state self-healing (rewind on
rejection, slot reuse); greedy byte-parity of the speculating pool
against whole-sequence `generate()` across page sizes, chunk widths,
drafter modes, mid-flight joins and ADVERSARIAL drafters (all-wrong,
oversized, out-of-vocab proposals) — the accept/rollback rule, not
draft quality, is what guarantees output; mixed speculative/sampling
lanes (sampling falls back to 1-token decode and stays seeded-parity
with a non-speculating pool); unsupported-combo admission (typed errors
at construction and over HTTP); the page-refcount ledger after a
rollback-heavy chaos storm;
zero XLA compiles after warmup; and the accept-rate / tokens-per-round
accounting in stats(), /metrics and trace spans.
"""

import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.serving import ContinuousLMServer
from deeplearning4j_tpu.serving.draft import (
    ModelDrafter,
    NgramDrafter,
    make_drafter,
)

pytestmark = pytest.mark.spec


def _lm(max_len=48, n_layers=2, vocab=50):
    from deeplearning4j_tpu.parallel import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=vocab, d_model=16, n_heads=2,
                                n_layers=n_layers, d_ff=32,
                                max_len=max_len)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _want(cfg, params, prompt, new):
    from deeplearning4j_tpu.parallel.generation import generate

    return np.asarray(generate(cfg, params, np.asarray([prompt], np.int32),
                               new))[0].tolist()


def _wait_idle(srv, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with srv._cond:
            if not any(s.active for s in srv._slots) and not srv._queue:
                return True
        time.sleep(0.01)
    return False


# ---------------------------------------------------------------------------
# N-gram drafter properties (satellite: property-style coverage)


class TestNgramDrafter:
    def _check_is_continuation(self, hist, prop, max_ngram):
        """A proposal must be the continuation of some PRIOR occurrence
        of a history suffix: exists n in [1, max_ngram] and i with
        hist[i:i+n] == hist[-n:] and prop == hist[i+n:i+n+len(prop)]."""
        for n in range(1, max_ngram + 1):
            if n > len(hist) - 1:
                break
            suffix = hist[-n:]
            for i in range(len(hist) - n):
                if (hist[i:i + n] == suffix
                        and prop == hist[i + n:i + n + len(prop)]):
                    return True
        return False

    def test_random_streams_propose_historical_continuations(self):
        rng = np.random.default_rng(42)
        drafter = NgramDrafter(max_ngram=4)
        checked = 0
        for trial in range(200):
            n = int(rng.integers(2, 40))
            vocab = int(rng.integers(2, 8))   # small vocab: matches happen
            hist = [int(t) for t in rng.integers(0, vocab, n)]
            budget = int(rng.integers(1, 6))
            (prop,) = drafter.propose([hist], [budget])
            assert len(prop) <= budget
            if prop:
                assert self._check_is_continuation(hist, prop, 4), (
                    hist, prop)
                checked += 1
        assert checked > 50        # the property was actually exercised

    def test_degenerate_inputs_propose_nothing(self):
        drafter = NgramDrafter()
        assert drafter.propose([[]], [4]) == [[]]          # empty history
        assert drafter.propose([[7]], [4]) == [[]]         # no prior
        assert drafter.propose([None], [4]) == [[]]        # masked lane
        assert drafter.propose([[1, 2, 3]], [0]) == [[]]   # no budget
        # all-distinct history: no suffix re-occurs
        assert drafter.propose([list(range(20))], [4]) == [[]]

    def test_repetition_is_predicted(self):
        drafter = NgramDrafter()
        hist = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
        (prop,) = drafter.propose([hist], [4])
        assert prop == [3, 4, 1, 2]

    def test_most_recent_occurrence_wins(self):
        # suffix [5] occurred twice with different continuations: the
        # LATER occurrence's continuation is proposed
        hist = [5, 1, 1, 5, 2, 9, 5]
        (prop,) = drafter_prop = NgramDrafter().propose([hist], [2])
        assert prop == [2, 9], drafter_prop

    def test_longer_ngram_preferred(self):
        # [2, 3] matches at index 1 (continuation 7); the shorter [3]
        # also matches at index 4 (continuation 8) — longest wins
        hist = [1, 2, 3, 7, 3, 8, 2, 3]
        (prop,) = NgramDrafter().propose([hist], [1])
        assert prop == [7]

    def test_batch_lanes_are_independent(self):
        drafter = NgramDrafter()
        out = drafter.propose([[1, 2, 1], None, [4, 4, 4, 4]], [3, 3, 2])
        assert out[0] == [2, 1]
        assert out[1] == []
        # longest n-gram wins: [4,4,4] matches at index 0, whose
        # continuation has just one token left before the history ends
        assert out[2] == [4]


# ---------------------------------------------------------------------------
# Model drafter (self-speculation: the target drafts for itself)


class TestModelDrafter:
    def test_self_draft_proposes_the_models_own_greedy_continuation(self):
        cfg, params = _lm(max_len=32, n_layers=1)
        want = _want(cfg, params, [1, 2, 3], 4)
        drafter = ModelDrafter(cfg, params, slots=2)
        (prop, empty) = drafter.propose([[1, 2, 3], None], [4, 4])
        assert prop == want[3:]
        assert empty == []

    def test_rejected_drafts_rewind_and_history_extends(self):
        cfg, params = _lm(max_len=32, n_layers=1)
        drafter = ModelDrafter(cfg, params, slots=1)
        (p1,) = drafter.propose([[1, 2, 3]], [3])
        # pretend verify rejected everything and committed [9] instead:
        # the next call's history diverges from what the drafter fed
        (p2,) = drafter.propose([[1, 2, 3, 9]], [3])
        assert p2 == _want(cfg, params, [1, 2, 3, 9], 3)[4:]
        assert p1 == _want(cfg, params, [1, 2, 3], 3)[3:]

    def test_lanes_at_different_depths_draft_their_own_continuation(self):
        """The drafter runs the paged step over a pool of its own with
        an identity block table: lanes one token and two pages deep, an
        idle lane between them, each proposing the draft model's greedy
        `generate()` continuation for its own budget."""
        cfg, _ = _lm(max_len=48, n_layers=1)
        from deeplearning4j_tpu.parallel import transformer as tfm

        params = tfm.init_params(cfg, jax.random.PRNGKey(7))
        deep = [(3 * i) % 47 + 1 for i in range(21)]   # into page 2 of 3
        drafter = ModelDrafter(cfg, params, slots=4)
        props = drafter.propose([[5], None, deep, [2, 4, 6]], [4, 3, 2, 3])
        assert props[0] == _want(cfg, params, [5], 4)[1:]
        assert props[1] == []
        assert props[2] == _want(cfg, params, deep, 2)[21:]
        assert props[3] == _want(cfg, params, [2, 4, 6], 3)[3:]
        mp = drafter._table.shape[1]
        assert mp == 3 and drafter._table.tolist() == [
            list(range(1 + i * mp, 1 + (i + 1) * mp)) for i in range(4)]
        assert drafter.compiled_programs() == 1

    @pytest.mark.parametrize("plen", [3, 15])
    def test_rejected_draft_rewinds_to_what_a_fresh_drafter_proposes(
            self, plen):
        """Rejected drafts wrote the lane's future positions (`plen` 15:
        over a page boundary).  After the rewind (a host counter move;
        the causal mask hides what lies past `pos`) the next proposal is
        byte-identical to that of a drafter that never saw them."""
        cfg, params = _lm(max_len=48, n_layers=1)
        hist = [(5 * i) % 47 + 1 for i in range(plen)]
        used = ModelDrafter(cfg, params, slots=2)
        (drafted, _) = used.propose([hist, None], [4, 0])
        other = (drafted[0] + 1) % 49 + 1               # the target's pick
        fresh = ModelDrafter(cfg, params, slots=2)
        (p_used, _) = used.propose([hist + [other], None], [4, 0])
        (p_fresh, _) = fresh.propose([hist + [other], None], [4, 0])
        assert p_used == p_fresh == _want(
            cfg, params, hist + [other], 4)[plen + 1:]

    def test_slot_reuse_resets_cleanly(self):
        cfg, params = _lm(max_len=32, n_layers=1)
        drafter = ModelDrafter(cfg, params, slots=1)
        drafter.propose([[5, 6, 7, 8]], [2])
        # a new request landed on the slot with an unrelated prompt
        (prop,) = drafter.propose([[2, 4]], [3])
        assert prop == _want(cfg, params, [2, 4], 3)[2:]

    def test_vocab_mismatch_is_typed(self):
        cfg, params = _lm(vocab=50)
        with pytest.raises(ValueError, match="vocab"):
            ModelDrafter(cfg, params, slots=1, target_vocab=100)

    def test_short_draft_cache_is_typed_and_never_corrupts(self):
        cfg, params = _lm(max_len=8, n_layers=1)
        # the factory seam rejects a draft model the target's histories
        # would outgrow...
        with pytest.raises(ValueError, match="max_len"):
            ModelDrafter(cfg, params, slots=1, target_max_len=32)
        # ...and a hand-built drafter fed an oversized history sits the
        # round out instead of scattering at clamped positions
        drafter = ModelDrafter(cfg, params, slots=1)
        assert drafter.propose([list(range(1, 13))], [3]) == [[]]
        (prop,) = drafter.propose([[2, 4]], [3])   # in-range still works
        assert prop == _want(cfg, params, [2, 4], 3)[2:]

    def test_make_drafter_modes(self):
        cfg, params = _lm()
        assert make_drafter("off", cfg, params, 2) is None
        assert make_drafter("ngram", cfg, params, 2).name == "ngram"
        assert make_drafter("model", cfg, params, 2).name == "model"
        with pytest.raises(ValueError, match="speculate"):
            make_drafter("wat", cfg, params, 2)


# ---------------------------------------------------------------------------
# Greedy byte-parity vs generate() (the tentpole acceptance)


class TestSpeculativeParity:
    @pytest.mark.parametrize("mode", ["ngram", "model"])
    @pytest.mark.parametrize("page_size,chunk,draft_len", [
        (4, 4, 3), (8, 1, 4), (6, 4, 2),   # non-dividing page size too
    ])
    def test_greedy_matches_generate(self, mode, page_size, chunk,
                                     draft_len):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=4,
                                 page_size=page_size, prefill_chunk=chunk,
                                 speculate=mode, draft_len=draft_len)
        try:
            srv.warmup()
            prompts = [[1, 2, 3, 4, 5, 1, 2, 3],
                       [7, 8, 9, 10, 11, 12, 7, 8, 9],
                       [3, 3, 3, 3],
                       [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]]
            results = {}

            def run(i, p):
                results[i] = srv.generate(p, 12, timeout=120)

            threads = [threading.Thread(target=run, args=(i, p))
                       for i, p in enumerate(prompts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, p in enumerate(prompts):
                assert results[i] == _want(cfg, params, p, 12), (mode, i)
        finally:
            srv.stop()

    def test_self_draft_accepts_everything(self):
        """Self-speculation is the wiring's oracle: the drafter IS the
        target, so every greedy draft must be accepted and decode must
        finish in ~max_new/(draft_len+1) rounds instead of max_new."""
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=4, prefill_chunk=4,
                                 speculate="model", draft_len=3)
        try:
            srv.warmup()
            p = [1, 2, 3, 4, 5]
            assert srv.generate(p, 12, timeout=120) == _want(
                cfg, params, p, 12)
            st = srv.stats()
            assert st["spec_accept_rate"] == 1.0
            assert st["speculate"]["accept_rate"] == 1.0
            # 12 tokens in at most ceil(11/4)+1 decode rounds + slack
            assert st["decode_rounds"] <= 5
            assert st["tokens_per_decode_round"] > 2.0
        finally:
            srv.stop()

    def test_midflight_join_keeps_parity(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, prefill_chunk=4,
                                 speculate="ngram", draft_len=3)
        try:
            srv.warmup()
            long_p = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
            results = {}

            def first():
                results["a"] = srv.generate(long_p, 16, timeout=120)

            t = threading.Thread(target=first)
            t.start()
            time.sleep(0.05)           # join mid-decode of the first
            results["b"] = srv.generate([9, 8, 9, 8, 9], 10, timeout=120)
            t.join()
            assert results["a"] == _want(cfg, params, long_p, 16)
            assert results["b"] == _want(cfg, params,
                                         [9, 8, 9, 8, 9], 10)
        finally:
            srv.stop()

    def test_adversarial_drafters_cannot_corrupt_output(self):
        """Draft QUALITY is a throughput knob, never a correctness one:
        an all-wrong drafter (every round fully rolled back), an
        oversized proposal, and an out-of-vocab proposal all yield
        byte-identical greedy output."""
        cfg, params = _lm()

        class WrongDrafter:
            name = "wrong"

            def propose(self, histories, budgets):
                # propose the WORST token: vocab-1 never matches this
                # tiny model's argmax on these prompts... and even if it
                # did, acceptance only speeds things up
                return [[cfg.vocab_size - 1] * int(b) if h is not None
                        else [] for h, b in zip(histories, budgets)]

            def reset(self):
                pass

            def compiled_programs(self):
                return 0

        class RudeDrafter(WrongDrafter):
            name = "rude"

            def propose(self, histories, budgets):
                # over-budget AND out-of-vocab mid-proposal
                return [[1, 2, cfg.vocab_size + 7, 3] * 4
                        if h is not None else []
                        for h, b in zip(histories, budgets)]

        for drafter in (WrongDrafter(), RudeDrafter()):
            srv = ContinuousLMServer(cfg, params, slots=2,
                                     page_size=4, prefill_chunk=4,
                                     draft_len=3, drafter=drafter)
            try:
                srv.warmup()
                for p in ([1, 2, 3, 4, 5], [6, 5, 4, 3, 2, 1]):
                    assert srv.generate(p, 10, timeout=120) == _want(
                        cfg, params, p, 10), drafter.name
                st = srv.stats()
                assert st["speculate"]["mode"] == "custom"
            finally:
                srv.stop()

    def test_rollbacks_keep_the_page_ledger_balanced(self):
        """Rollback-heavy decode (all-wrong drafter: EVERY round writes
        then abandons draft_len columns) must not move a single page:
        allocation happens at admission, release at completion, and the
        ledger balances after the storm."""
        cfg, params = _lm()

        class WrongDrafter:
            name = "wrong"

            def propose(self, histories, budgets):
                return [[cfg.vocab_size - 1] * int(b) if h is not None
                        else [] for h, b in zip(histories, budgets)]

            def reset(self):
                pass

        srv = ContinuousLMServer(cfg, params, slots=3,
                                 page_size=4, pages=24, prefill_chunk=4,
                                 draft_len=3, drafter=WrongDrafter())
        try:
            srv.warmup()
            rng = np.random.default_rng(0)
            threads = []

            def one(i, p, n):
                try:
                    if i % 5 == 3:      # born-dead: shed at the admitter
                        srv.generate(p, n, deadline_s=0.0, timeout=60)
                    elif i % 7 == 2:    # client abandons mid-decode
                        srv.generate(p, n, timeout=0.001)
                    else:
                        srv.generate(p, n, timeout=120)
                except TimeoutError:
                    pass

            for i in range(16):
                p = [int(t) for t in rng.integers(1, 49,
                                                  rng.integers(2, 10))]
                t = threading.Thread(target=one,
                                     args=(i, p, int(rng.integers(2, 10))))
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
            assert _wait_idle(srv)
            ledger = srv._pool.check_ledger()
            assert ledger["balanced"], ledger
            assert ledger["in_use"] == srv._tree.nodes
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Mixed speculative / sampling lanes (satellite: fallback, not mis-sampling)


class TestSamplingFallback:
    def test_sampled_lane_falls_back_and_matches_nonspec_pool(self):
        """A temperature>0 request on a speculating pool is never
        drafted for: it decodes 1 token per round and its seeded output
        is byte-identical to the same request on a non-speculating
        pool — the documented fallback, not silent mis-sampling."""
        cfg, params = _lm()
        spec = ContinuousLMServer(cfg, params, slots=2,
                                  page_size=4, prefill_chunk=4,
                                  speculate="ngram", draft_len=3)
        base = ContinuousLMServer(cfg, params, slots=2,
                                  page_size=4, prefill_chunk=4)
        try:
            spec.warmup()
            base.warmup()
            p = [1, 2, 1, 2, 1]
            results = {}

            # a concurrent greedy lane keeps the wide verify program hot
            # while the sampled lane rides the same dispatches
            def greedy():
                results["g"] = spec.generate([4, 5, 4, 5, 4, 5], 12,
                                             timeout=120)

            t = threading.Thread(target=greedy)
            t.start()
            got = spec.generate(p, 10, temperature=0.8, seed=11,
                                timeout=120)
            t.join()
            assert got == base.generate(p, 10, temperature=0.8, seed=11,
                                        timeout=120)
            assert results["g"] == _want(cfg, params,
                                         [4, 5, 4, 5, 4, 5], 12)
        finally:
            spec.stop()
            base.stop()


# ---------------------------------------------------------------------------
# Unsupported-combo admission (satellite: typed errors, not crashes)


class TestAdmissionValidation:
    def test_bad_speculate_mode_is_typed(self):
        cfg, params = _lm()
        with pytest.raises(ValueError, match="speculate"):
            ContinuousLMServer(cfg, params, speculate="warp")

    def test_bad_draft_len_is_typed(self):
        cfg, params = _lm()
        with pytest.raises(ValueError, match="draft_len"):
            ContinuousLMServer(cfg, params, speculate="ngram",
                               draft_len=0)

    def test_http_speculate_on_speculating_pool_serves(self):
        import json
        import urllib.request

        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm(max_len=32, n_layers=1)
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=1, speculate="ngram",
                     draft_len=3).start()
        try:
            srv.state.lm_server.warmup()
            p = [1, 2, 1, 2, 1]
            body = json.dumps({"prompt_ids": p, "max_new_tokens": 8,
                               "speculate": True}).encode()
            req = urllib.request.Request(
                srv.url + "/lm/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                out = json.loads(resp.read().decode())
            assert out["ids"] == _want(cfg, params, p, 8)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Compile discipline + accounting


class TestSpecCompileGuard:
    def test_zero_compiles_after_warmup(self):
        import jax.monitoring

        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=3,
                                 page_size=4, prefill_chunk=4,
                                 speculate="ngram", draft_len=3)
        try:
            warmed = srv.warmup()
            assert warmed == srv.compiled_programs() == 3
            compiles = []

            def listener(event, duration, **kw):
                if event == ("/jax/core/compile/"
                             "backend_compile_duration"):
                    compiles.append(event)

            jax.monitoring.register_event_duration_secs_listener(
                listener)
            try:
                rng = np.random.default_rng(1)
                threads = []
                for _ in range(9):
                    p = [int(t) for t in rng.integers(
                        1, 49, rng.integers(2, 12))]
                    t = threading.Thread(
                        target=lambda p=p: srv.generate(p, 8,
                                                        timeout=120))
                    t.start()
                    threads.append(t)
                for t in threads:
                    t.join()
            finally:
                jax.monitoring.clear_event_listeners()
            assert not compiles
        finally:
            srv.stop()

    def test_model_drafter_program_is_counted_and_warmed(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, prefill_chunk=4,
                                 speculate="model", draft_len=2)
        try:
            assert srv.warmup() == srv.compiled_programs() == 4
        finally:
            srv.stop()

    def test_model_speculation_compiles_nothing_after_warmup(self):
        """`warmup()` covers the draft model's paged step too (its own
        pool geometry, so its own program, key `lm:draft`): a storm of
        ragged prompts after it compiles nothing, and stays
        byte-identical to `generate()`."""
        import jax.monitoring

        from deeplearning4j_tpu.parallel import transformer as tfm

        cfg, params = _lm()
        d_cfg, _ = _lm(n_layers=1)
        draft = (d_cfg, tfm.init_params(d_cfg, jax.random.PRNGKey(3)))
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, prefill_chunk=4,
                                 speculate="model", draft_len=3,
                                 draft_model=draft)
        prompts = [[(7 * i + j) % 49 + 1 for j in range(n)]
                   for i, n in enumerate((2, 9, 5, 13))]
        want = [_want(cfg, params, p, 8) for p in prompts]
        try:
            assert srv.warmup() == 4
            warm = srv.stats()
            assert "lm:drafter" in warm["warmup"]["programs"]
            compiles = []

            def listener(event, duration, **kw):
                if event == ("/jax/core/compile/"
                             "backend_compile_duration"):
                    compiles.append(event)

            got = [None] * len(prompts)

            def client(i):
                got[i] = srv.generate(prompts[i], 8, timeout=120)

            jax.monitoring.register_event_duration_secs_listener(
                listener)
            try:
                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(len(prompts))]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=150)
            finally:
                jax.monitoring.clear_event_listeners()
            assert got == want
            assert not compiles
            assert srv.stats()["compiles_total"] == warm["compiles_total"]
        finally:
            srv.stop()


class TestSpecAccounting:
    def test_stats_metrics_and_trace_carry_the_spec_ledger(self):
        from deeplearning4j_tpu.obs.registry import MetricsRegistry
        from deeplearning4j_tpu.obs.trace import TraceRecorder

        cfg, params = _lm()
        registry = MetricsRegistry()
        tracer = TraceRecorder()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=4, prefill_chunk=4,
                                 speculate="model", draft_len=3,
                                 tracer=tracer, registry=registry)
        try:
            srv.warmup()
            p = [1, 2, 3, 4, 5]
            srv.generate(p, 10, timeout=120)
            st = srv.stats()
            assert st["spec_drafted"] >= st["spec_accepted"] > 0
            assert st["speculate"]["mode"] == "model"
            assert st["speculate"]["draft_len"] == 3
            assert 0 < st["speculate"]["accept_rate"] <= 1.0
            text = registry.exposition()
            assert "serving_spec_drafted_total" in text
            assert "serving_spec_accepted_total" in text
            assert "serving_lm_decode_tokens_total" in text
            traces = tracer.recent()
            decode = [s for t in traces for s in t["spans"]
                      if s["name"] == "decode"]
            assert decode and decode[-1]["attrs"]["drafted"] > 0
            assert decode[-1]["attrs"]["accepted"] > 0
        finally:
            srv.stop()

    def test_fallback_server_without_speculation_reports_no_section(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=4)
        try:
            srv.generate([1, 2, 3], 4, timeout=120)
            st = srv.stats()
            assert "speculate" not in st
            assert "spec_drafted" not in st
            # the per-lane decode cadence is still accounted
            assert st["tokens_per_decode_round"] == 1.0
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Fleet pass-through: speculating replicas + /fleet/stats aggregation


class TestFleetSpeculate:
    def test_speculating_replicas_aggregate_accept_rate(self):
        """`spawn_local_replica(lm_speculate=...)` boots speculating
        replicas; routed greedy traffic stays byte-identical to
        `generate()` and /fleet/stats grows an `lm_speculate` aggregate
        with the fleet-wide accept rate.  The replicas draft with their
        own weights, so every draft is accepted whatever these random
        weights continue a prompt with (an n-gram drafter proposes only
        once the continuation repeats the history: these never do)."""
        from deeplearning4j_tpu.serving import FleetRouter
        from deeplearning4j_tpu.serving.fleet import spawn_local_replica

        cfg, params = _lm(max_len=32, n_layers=1)

        def factory(name):
            return spawn_local_replica(
                name, lm=(cfg, params), lm_slots=2, lm_page_size=8,
                lm_prefill_chunk=4, lm_speculate="model",
                lm_draft_len=3)

        router = FleetRouter(factory, replicas=2, request_timeout_s=60.0)
        try:
            prompts = [[1, 2, 1, 2, 1, 2, 1], [5, 5, 5, 5, 5],
                       [7, 8, 7, 8, 7, 8]]
            for p in prompts:
                assert router.generate(p, 8, timeout=60) == _want(
                    cfg, params, p, 8)
            stats = router.fleet_stats()
        finally:
            router.stop()
        spec = stats["fleet"].get("lm_speculate")
        assert spec is not None
        assert spec["drafted"] == spec["accepted"] > 0
        assert spec["accept_rate"] == 1.0


# ---------------------------------------------------------------------------
# Static-analysis coverage (satellite: the drafter plane rides the
# lock-discipline sweep and the serving strict-except ceiling)


class TestLintCoverage:
    def test_draft_module_is_inside_the_strict_sweeps(self):
        from tools.dl4jlint.pass_excepts import STRICT_PREFIXES
        from tools.dl4jlint.pass_locks import INCLUDE_PREFIXES

        rel = "deeplearning4j_tpu/serving/draft.py"
        assert rel.startswith(INCLUDE_PREFIXES)
        assert any(rel.startswith(prefix)
                   for prefix, _, _ in STRICT_PREFIXES)

    def test_draft_module_lints_clean(self):
        import pathlib

        from tools.dl4jlint.engine import _make_context, default_passes

        root = pathlib.Path(__file__).resolve().parents[1]
        path = root / "deeplearning4j_tpu" / "serving" / "draft.py"
        ctx, syntax_error = _make_context(root, path)
        assert syntax_error is None
        findings = [f for p in default_passes() for f in p.run(ctx)
                    if not (f.respect_pragma
                            and ctx.has_pragma(f.line, f.code))]
        assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# Fault recovery: the drafter must not outlive a rebuilt pool


class TestSpecFaultRecovery:
    def test_failed_dispatch_resets_drafter_with_the_pool(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=4, prefill_chunk=4,
                                 speculate="model", draft_len=3)
        try:
            srv.warmup()
            p = [1, 2, 3, 4, 5, 6]
            want = _want(cfg, params, p, 8)
            assert srv.generate(p, 8, timeout=120) == want
            real_step = srv._step
            srv._step = lambda *a, **kw: (_ for _ in ()).throw(
                RuntimeError("boom"))
            with pytest.raises(RuntimeError, match="boom"):
                srv.generate(p, 8, timeout=120)
            srv._step = real_step
            assert srv._drafter._fed == [[]]   # lane state died with pool
            assert srv.generate(p, 8, timeout=120) == want
        finally:
            srv.stop()
