"""Paged-KV serving tests (ISSUE-7 acceptance surface).

Covers: the host-side page allocator's refcount economy and the radix
prefix tree's match/insert/evict mechanics (pure Python, no device);
greedy byte-parity of the paged pool against whole-sequence
`generate()` across page sizes and prefill-chunk widths, including
mid-flight joins; radix prefix reuse (a shared system prompt is
prefilled once) and copy-on-write at the divergence page, both
byte-identical to a cold pool; freed-slot/page hygiene (a reused slot
with a shorter prompt matches a fresh pool bit-for-bit — stale KV from
the previous occupant is unreachable); the page-refcount ledger across
a 200-request chaos storm of deadline-shed, client-abandoned and
dispatch-failed requests (allocated == in_use + free, no leaks); the
compile-count guard (zero XLA compiles across a mixed-length
prefix-reuse storm after warmup, via jax.monitoring); pool-exhaustion
queueing; the actual-vs-provisioned KV bytes accounting for both dense
and paged modes; and the fleet-level prefix_hit_rate aggregation the
prefix-affinity router feeds.
"""

import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.serving import ContinuousLMServer
from deeplearning4j_tpu.serving.paged import (
    PageLeakError,
    PagePool,
    RadixPrefixCache,
)

pytestmark = pytest.mark.paged


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


def _wait_idle(srv, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        with srv._cond:
            if not any(s.active for s in srv._slots) and not srv._queue:
                return True
        time.sleep(0.01)
    return False


# ---------------------------------------------------------------------------
# Host-side allocator + radix tree (no device)


class TestPagePool:
    def test_alloc_release_refcounts(self):
        pool = PagePool(pages=5, page_size=8)     # 4 usable + null
        assert pool.usable == 4 and pool.free == 4
        a = pool.alloc(2)
        assert len(a) == 2 and pool.in_use == 2
        assert 0 not in a                          # null page never granted
        pool.retain(a)
        pool.release(a)
        assert pool.in_use == 2                    # still held once
        pool.release(a)
        assert pool.in_use == 0 and pool.free == 4
        assert pool.check_ledger()["balanced"]

    def test_alloc_is_all_or_nothing(self):
        pool = PagePool(pages=4, page_size=8)
        assert pool.alloc(4) is None               # only 3 usable
        assert pool.free == 3                      # nothing leaked
        assert pool.alloc(3) is not None
        assert pool.alloc(1) is None

    def test_double_release_is_a_typed_leak(self):
        pool = PagePool(pages=4, page_size=8)
        (p,) = pool.alloc(1)
        pool.release([p])
        with pytest.raises(PageLeakError):
            pool.release([p])
        with pytest.raises(PageLeakError):
            pool.retain([p])                       # retain of a freed page
        with pytest.raises(PageLeakError):
            pool.release([0])                      # the null page

    def test_ledger_detects_imbalance(self):
        pool = PagePool(pages=4, page_size=8)
        pool.alloc(2)
        out = pool.check_ledger()
        assert out["balanced"] and out["in_use"] == 2 and out["free"] == 1


class TestRadixPrefixCache:
    def _pool_tree(self, pages=16, ps=4):
        pool = PagePool(pages=pages, page_size=ps)
        return pool, RadixPrefixCache(pool)

    def test_match_miss_then_insert_then_hit(self):
        pool, tree = self._pool_tree()
        toks = list(range(1, 13))                  # 3 full pages of 4
        full, partial = tree.match(toks)
        assert full == [] and partial is None
        pages = pool.alloc(3)
        tree.insert(toks, pages)                   # tree holds +1 each
        full, partial = tree.match(toks)
        assert full == pages and partial is None
        # match retained them: owner + tree + this match
        assert all(pool.refcount(p) == 3 for p in pages)
        pool.release(full)

    def test_partial_match_is_the_cow_divergence_page(self):
        pool, tree = self._pool_tree()
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        pages = pool.alloc(2)
        tree.insert(toks, pages)
        # shares page 1 fully, diverges 2 tokens into page 2
        full, partial = tree.match([1, 2, 3, 4, 5, 6, 9, 9])
        assert full == [pages[0]]
        assert partial == (pages[1], 2)
        pool.release(full)
        pool.release([partial[0]])

    def test_insert_existing_node_keeps_it(self):
        pool, tree = self._pool_tree()
        toks = [1, 2, 3, 4]
        (a,) = pool.alloc(1)
        tree.insert(toks, [a])
        (b,) = pool.alloc(1)                       # duplicate content
        assert tree.insert(toks, [b]) == 0         # kept the original
        assert pool.refcount(a) == 2 and pool.refcount(b) == 1
        assert tree.nodes == 1

    def test_evictable_counts_only_unpinned_subtrees(self):
        """A shared descendant pins its ancestors (eviction is
        leaf-first): evictable() must not promise pages it cannot
        deliver — admission uses it to decide whether evicting is worth
        destroying cached prefixes at all."""
        pool, tree = self._pool_tree(pages=8, ps=4)
        pages = pool.alloc(3)
        tree.insert(list(range(1, 13)), pages)
        pool.release(pages)                        # tree-only chain of 3
        assert tree.evictable() == 3
        # pin the MIDDLE page (an active lane shares it): it and its
        # ancestor are now un-evictable, only the leaf below remains
        pool.retain([pages[1]])
        assert tree.evictable() == 1
        pool.release([pages[1]])
        assert tree.evictable() == 3
        assert tree.evict(need_free=pool.usable) == 3
        assert pool.in_use == 0

    def test_evict_frees_lru_tree_only_pages(self):
        pool, tree = self._pool_tree(pages=5, ps=4)   # 4 usable
        p1 = pool.alloc(2)
        tree.insert([1, 2, 3, 4, 5, 6, 7, 8], p1)
        pool.release(p1)                           # tree is sole holder
        p2 = pool.alloc(1)
        tree.insert([9, 9, 9, 9], p2)
        # p2's owner still holds it: eviction must take p1's LRU leaf
        assert pool.free == 1
        evicted = tree.evict(need_free=3)
        assert evicted >= 2 and pool.free >= 3
        assert pool.refcount(p2[0]) == 2           # shared page untouched
        tree.clear()
        pool.release(p2)
        assert pool.check_ledger()["balanced"] and pool.in_use == 0


# ---------------------------------------------------------------------------
# Paged pool parity with generate()


class TestPagedParity:
    @pytest.mark.parametrize("page_size,chunk", [(8, 1), (8, 4), (4, 8)])
    def test_concurrent_greedy_matches_generate(self, page_size, chunk):
        """Paged slot decode == whole-sequence generate(), token for
        token, for concurrent prompts of different lengths — across
        page sizes that do and do not divide max_len and both prefill
        widths (ISSUE-7 acceptance: byte-identical)."""
        cfg, params = _lm(max_len=30)
        srv = ContinuousLMServer(cfg, params, slots=3,
                                 page_size=page_size, prefill_chunk=chunk)
        prompts = [[1, 2, 3], [5, 6], [7, 8, 9, 10, 11, 12, 13],
                   [4], [11, 12, 13, 14, 15, 16, 17, 18, 19]]
        want = [_want(cfg, params, p, 6) for p in prompts]
        got = [None] * len(prompts)

        def client(i):
            got[i] = srv.generate(prompts[i], 6, timeout=120)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.stats()
        srv.stop()
        assert got == want
        assert stats["kv"]["mode"] == "paged"
        assert stats["tokens"] == 6 * len(prompts)

    def test_midflight_join_does_not_disturb_running_request(self):
        """A prompt admitted while another request decodes must not
        change the running request's output — now with page allocation
        happening at the join."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8, prefill_chunk=4)
        long_p, short_p = [1, 2, 3, 4], [9, 8]
        want_long = _want(cfg, params, long_p, 20)
        want_short = _want(cfg, params, short_p, 4)
        out = {}

        def late():
            out["short"] = srv.generate(short_p, 4, timeout=120)

        def early():
            out["long"] = srv.generate(long_p, 20, timeout=120)

        t0 = threading.Thread(target=early)
        t1 = threading.Thread(target=late)
        t0.start()
        t1.start()
        t0.join()
        t1.join()
        srv.stop()
        assert out["long"] == want_long
        assert out["short"] == want_short

    def test_sampling_is_seeded_per_request(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8)
        a = srv.generate([1, 2], 5, temperature=0.9, seed=7, timeout=120)
        b = srv.generate([1, 2], 5, temperature=0.9, seed=7, timeout=120)
        srv.stop()
        other = ContinuousLMServer(cfg, params, slots=3, page_size=4,
                                   prefill_chunk=1)
        c = other.generate([1, 2], 5, temperature=0.9, seed=7, timeout=120)
        other.stop()
        assert a == b
        # the draw is the request's (seed, tokens generated), whatever
        # the pool's lanes, pages and programs: same seed, same draw
        assert a == c


class TestPrefixReuse:
    def test_shared_prefix_skips_prefill_and_matches_generate(self):
        """The radix-cache core claim: request B sharing request A's
        prompt prefix reuses A's pages (hit counted, prefill steps
        saved) and still matches generate() byte-for-byte — cached KV
        IS the KV B would have written."""
        cfg, params = _lm(max_len=32)
        ps = 8
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=ps, prefill_chunk=4)
        system = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]  # 2 pages
        a_p, b_p = system + [10, 11], system + [12, 13, 14]
        want_a, want_b = _want(cfg, params, a_p, 6), _want(cfg, params,
                                                          b_p, 6)
        steps_a = srv.generate(a_p, 6, timeout=120)
        before = srv.stats()["decode_steps"]
        got_b = srv.generate(b_p, 6, timeout=120)
        stats = srv.stats()
        srv.stop()
        assert steps_a == want_a and got_b == want_b
        assert stats["prefix_queries"] == 2
        assert stats["prefix_hits"] == 1
        assert stats["prefix_tokens_saved"] == len(system)
        assert stats["prefix_hit_rate"] == 0.5
        # B's 16 reused tokens cost ZERO dispatches: remaining prompt
        # (3-token sub-chunk tail, fed singly) + 6 decode steps only
        assert stats["decode_steps"] - before <= 3 + 6

    def test_cow_divergence_mid_page_matches_generate(self):
        """Prompts diverging inside a page share it copy-on-write: the
        divergence page is copied device-side and overwritten from the
        split point — byte-identical to a cold decode, and the copy's
        source page survives for the next hit."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8, prefill_chunk=4)
        a_p = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]     # caches page 1-8
        b_p = [1, 2, 3, 4, 5, 6, 40, 41, 42]   # diverges INSIDE the page
        want_b = _want(cfg, params, b_p, 6)
        srv.generate(a_p, 6, timeout=120)
        got_b = srv.generate(b_p, 6, timeout=120)
        stats = srv.stats()
        # third request re-walking A's exact prompt still hits the
        # ORIGINAL page (the CoW copy never replaced it)
        want_a = _want(cfg, params, a_p, 6)
        got_a = srv.generate(a_p, 6, timeout=120)
        srv.stop()
        assert got_b == want_b and got_a == want_a
        assert stats["prefix_hits"] == 1
        # 6 tokens into the divergence page, served copy-on-write
        assert stats["prefix_tokens_saved"] == 6

    def test_identical_prompt_refeeds_last_token_only(self):
        """Reuse is capped at plen-1: the last prompt token is re-fed so
        its logits seed the first sample — an identical prompt still
        matches generate()."""
        cfg, params = _lm(max_len=32)
        p = [1, 2, 3, 4, 5, 6, 7, 8, 9]                   # 9 tokens, ps 8
        want = _want(cfg, params, p, 5)
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=8, prefill_chunk=4)
        assert srv.generate(p, 5, timeout=120) == want
        assert srv.generate(p, 5, timeout=120) == want
        stats = srv.stats()
        srv.stop()
        assert stats["prefix_hits"] == 1
        assert stats["prefix_tokens_saved"] == 8          # the full page


# ---------------------------------------------------------------------------
# Freed-slot / freed-page hygiene (satellite: stale-KV leakage)


class TestFreedSlotHygiene:
    @pytest.mark.parametrize("chunk", [1, 4])
    def test_slot_reuse_with_shorter_prompt_matches_fresh_pool(self, chunk):
        """A slot freed by a LONG request and reoccupied by a SHORTER
        one must produce output byte-identical to a fresh pool: the
        previous occupant's KV beyond the new request's positions is
        unreachable (unreferenced pages), with the chunk program
        (`chunk` 4) and on the server that has none (`chunk` 1)."""
        cfg, params = _lm(max_len=32)
        kw = dict(page_size=8, prefill_chunk=chunk)
        long_p = [7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18]
        short_p = [5, 6]
        srv = ContinuousLMServer(cfg, params, slots=1, **kw)
        srv.generate(long_p, 12, timeout=120)             # fill the slot
        reused = srv.generate(short_p, 4, timeout=120)    # same slot
        srv.stop()
        fresh_srv = ContinuousLMServer(cfg, params, slots=1, **kw)
        fresh = fresh_srv.generate(short_p, 4, timeout=120)
        fresh_srv.stop()
        assert reused == fresh == _want(cfg, params, short_p, 4)

    def test_recycled_page_never_leaks_previous_kv(self):
        """Tight pool: request B's pages are literally request A's
        recycled pages — B must still match generate() (every attended
        position was written by B or by B's matched prefix)."""
        cfg, params = _lm(max_len=32)
        # exactly one lane's worth of pages: B always recycles A's
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=8, pages=4, prefill_chunk=4)
        a_p = [9, 8, 7, 6, 5, 4, 3, 2, 1]
        b_p = [1, 2, 3]
        want_b = _want(cfg, params, b_p, 8)
        srv.generate(a_p, 20, timeout=120)
        got_b = srv.generate(b_p, 8, timeout=120)
        srv.stop()
        assert got_b == want_b


# ---------------------------------------------------------------------------
# Capacity: exhaustion queues, oversize rejects, eviction recovers


class TestPoolCapacity:
    def test_request_larger_than_pool_is_a_client_error(self):
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8, pages=2)
        with pytest.raises(ValueError, match="KV pages"):
            srv.generate([1, 2, 3], 20)                   # needs 3 pages
        srv.stop()

    def test_exhausted_pool_queues_until_pages_free(self):
        """Two concurrent max-size requests over a one-lane pool: the
        second waits for the first's pages, then completes correctly —
        admission control by capacity, not failure."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8, pages=3, prefill_chunk=4)
        p1, p2 = [1, 2, 3, 4, 5], [6, 7, 8, 9]
        want = [_want(cfg, params, p1, 18), _want(cfg, params, p2, 18)]
        got = [None, None]

        def client(i, p):
            got[i] = srv.generate(p, 18, timeout=120)

        ts = [threading.Thread(target=client, args=(i, p))
              for i, p in enumerate([p1, p2])]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stats = srv.stats()
        srv.stop()
        assert got == want
        # the pool is too small for both lanes at once: occupancy of
        # the second lane had to wait (max 1 active at any dispatch)
        assert stats["max_batch_occupancy"] == 1

    def test_eviction_recycles_cached_prefixes_under_pressure(self):
        """Radix-held pages are capacity on loan: when a new prompt
        needs them, LRU cached prefixes are evicted and the request
        still serves (correctly) instead of waiting forever."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=8, pages=4, prefill_chunk=4)
        outs, wants = [], []
        for base in (0, 10, 20, 30):                      # distinct pages
            p = [base + j for j in range(9)]
            wants.append(_want(cfg, params, p, 4))
            outs.append(srv.generate(p, 4, timeout=120))
        stats = srv.stats()
        ledger = srv._pool.check_ledger()
        srv.stop()
        assert outs == wants
        assert ledger["balanced"]
        # the 4-page pool cannot hold 4 cached prefixes + a live lane:
        # eviction had to run, and nothing leaked
        assert stats["kv"]["radix_nodes"] <= 3


# ---------------------------------------------------------------------------
# Chaos: the page-refcount ledger survives shed/abandon/fault traffic


class TestPageLedgerChaos:
    def test_no_page_leaks_across_200_chaos_requests(self):
        """ISSUE-7 satellite: after a storm mixing completed requests,
        deadline-shed queue items, client-abandoned in-flight requests
        and injected dispatch faults, the allocator's ledger balances —
        allocated == in_use + free, with in_use exactly the radix-held
        prefix pages.  A leaked page would show up as in_use nobody
        owns; a double-free raises PageLeakError inside the worker."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=3,
                                 page_size=8, pages=12, prefill_chunk=4)
        srv.warmup()
        real_step = srv._step
        fault = {"n": 0}

        def flaky(*a, **kw):
            fault["n"] += 1
            if fault["n"] % 17 == 0:                      # periodic fault
                raise RuntimeError("injected device fault")
            return real_step(*a, **kw)

        srv._step = flaky
        rng = np.random.default_rng(0)
        system = [3, 1, 4, 1, 5, 9, 2, 6]
        errors = {"deadline": 0, "fault": 0, "ok": 0, "other": 0}

        def one(i):
            p = (system + [int(t) for t in
                           rng.integers(1, 49, rng.integers(1, 8))])
            try:
                if i % 11 == 3:
                    # born-dead deadline: shed at the admitter
                    srv.generate(p, 6, deadline_s=0.0, timeout=30)
                elif i % 13 == 5:
                    # client abandons almost immediately
                    srv.generate(p, 12, timeout=0.001)
                else:
                    srv.generate(p, 6, timeout=60)
                    errors["ok"] += 1
                    return
            except TimeoutError:
                errors["deadline"] += 1
            except RuntimeError:
                errors["fault"] += 1
            except Exception:  # noqa: BLE001 — the tally below asserts
                errors["other"] += 1

        threads = []
        for i in range(200):
            t = threading.Thread(target=one, args=(i,))
            t.start()
            threads.append(t)
            if len(threads) >= 8:
                threads.pop(0).join()
        for t in threads:
            t.join()
        assert _wait_idle(srv)
        ledger = srv._pool.check_ledger()
        tree_pages = srv._tree.nodes
        stats = srv.stats()
        srv._step = real_step
        srv.stop()
        assert errors["other"] == 0
        assert errors["ok"] > 100                  # the storm mostly served
        assert ledger["balanced"], ledger
        # idle pool: every in-use page is a radix-cached prefix page
        assert ledger["in_use"] == tree_pages
        assert stats["pages_in_use"] + stats["pages_free"] == 12

    def test_failed_dispatch_resets_pool_and_tree_together(self):
        """A dispatch fault kills the donated buffers AND the page
        contents: the tree must not survive the pool, or the next
        prefix hit would serve zeros."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=1,
                                 page_size=8, prefill_chunk=4)
        p = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
        want = _want(cfg, params, p, 6)
        assert srv.generate(p, 6, timeout=120) == want
        assert srv._tree.nodes > 0                 # prefix cached
        real_step = srv._step
        srv._step = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("boom"))
        with pytest.raises(RuntimeError, match="boom"):
            srv.generate(p, 6, timeout=120)
        srv._step = real_step
        # the tree was reset with the pool: this is a MISS, then a
        # correct cold decode
        assert srv.generate(p, 6, timeout=120) == want
        stats = srv.stats()
        srv.stop()
        assert stats["prefix_hits"] == 1           # only the pre-fault hit


# ---------------------------------------------------------------------------
# Compile-count guard (satellite: zero recompiles across a paged storm)


class TestPagedCompileGuard:
    def test_zero_compiles_across_mixed_length_prefix_storm(self):
        """After warmup() (decode step, prefill-chunk step, CoW copy),
        a storm of mixed-length prompts — cold, prefix-hit and CoW
        admissions interleaved — triggers ZERO XLA compiles
        (jax.monitoring, the test_serving pattern)."""
        import jax.monitoring

        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=3,
                                 page_size=8, prefill_chunk=4)
        assert srv.warmup() == 3                   # decode + chunk + copy
        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        rng = np.random.default_rng(2)
        system = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            threads = []
            for i in range(24):
                if i % 3 == 0:
                    p = system + [int(t) for t in rng.integers(1, 49, 3)]
                else:
                    p = [int(t) for t in
                         rng.integers(1, 49, rng.integers(1, 14))]
                t = threading.Thread(
                    target=lambda p=p: srv.generate(p, 5, timeout=120))
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
            stats = srv.stats()
        finally:
            jax.monitoring.clear_event_listeners()
            srv.stop()
        assert compiles == []
        assert stats["compiled_programs"] == 3
        assert stats["requests"] == 24


# ---------------------------------------------------------------------------
# Stats honesty (satellite: actual vs provisioned KV bytes)


class TestKVBytesAccounting:
    @pytest.mark.parametrize("slots", [2, 4])
    def test_provisioned_is_the_pool_whatever_the_lane_count(self, slots):
        """`kv_bytes.provisioned` is pages * page_size * per_token: the
        pool's bytes, not a lane count times max_len."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=slots,
                                 page_size=8, pages=6)
        kvb = srv.stats()["kv_bytes"]
        srv.stop()
        assert kvb["per_token"] == (2 * cfg.n_layers * cfg.n_heads
                                    * cfg.head_dim
                                    * np.dtype(cfg.dtype).itemsize)
        assert kvb["provisioned"] == 6 * 8 * kvb["per_token"]
        assert kvb["active"] == 0                  # nothing resident

    def test_paged_active_bytes_follow_the_refcounted_pages(self):
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=4,
                                 page_size=8, pages=8)
        per_tok = (2 * cfg.n_layers * cfg.n_heads * cfg.head_dim
                   * np.dtype(cfg.dtype).itemsize)
        srv.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], 4, timeout=120)
        assert _wait_idle(srv)
        kvb = srv.stats()["kv_bytes"]
        srv.stop()
        assert kvb["provisioned"] == 8 * 8 * per_tok   # pages, not slots
        # idle: only the radix-cached prompt page is resident
        assert kvb["active"] == 1 * 8 * per_tok


# ---------------------------------------------------------------------------
# Fleet aggregation (satellite: prefix_hit_rate through /fleet/stats)


class TestFleetPrefixStats:
    def test_affinity_routed_storm_reports_fleet_hit_rate(self):
        """Two LM replicas behind the prefix-affinity router: a
        shared-prefix storm lands on ONE replica (rendezvous hashing),
        so the fleet-level prefix_hit_rate — aggregated from the
        replicas' /serving/stats through /fleet/stats — shows the reuse
        the router was built to feed (ROADMAP items 2+5)."""
        from deeplearning4j_tpu.serving import FleetRouter
        from deeplearning4j_tpu.serving.fleet import spawn_local_replica

        cfg, params = _lm(max_len=32)
        system = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]

        def factory(name):
            return spawn_local_replica(
                name, lm=(cfg, params), lm_slots=2, lm_page_size=8,
                lm_prefill_chunk=4)

        router = FleetRouter(factory, replicas=2, request_timeout_s=60.0)
        try:
            want = {}
            for i in range(6):
                p = system + [10 + i]
                want[i] = _want(cfg, params, p, 4)
            got = {i: router.generate(system + [10 + i], 4, timeout=60)
                   for i in range(6)}
            stats = router.fleet_stats()
        finally:
            router.stop()
        assert got == want
        prefix = stats["fleet"]["lm_prefix"]
        assert prefix["queries"] == 6
        # one cold miss per replica that saw the prefix; affinity keeps
        # the storm on one replica, so at least 4 of 6 hit
        assert prefix["hit_rate"] > 0.5
        assert prefix["tokens_saved"] >= 4 * len(system)


# ---------------------------------------------------------------------------
# Paged-attention kernel serving integration (ISSUE-18): the fused
# block-table kernel rides the SAME compile ladder as the gather oracle
# — same program count, zero off-ladder compiles — and stays
# byte-identical to whole-sequence generate().


@pytest.mark.paged_kernel
class TestPagedKernelServing:
    @pytest.fixture(autouse=True)
    def _kernel_on(self, monkeypatch):
        """The platform rule gives the gather oracle on a CPU: stand in
        for it, so the whole server runs the kernel (interpret mode)."""
        from deeplearning4j_tpu.parallel import paged_kernel

        monkeypatch.setattr(paged_kernel, "paged_kernel_enabled",
                            lambda: True)

    def test_kernel_pool_greedy_parity_with_generate(self):
        """Greedy byte-parity of the kernel-backed pool against
        `generate()` across ragged prompt lengths — including prompts
        that straddle page boundaries mid-prefill."""
        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=3,
                                 page_size=8, prefill_chunk=4)
        try:
            for plen in (1, 3, 7, 9, 13):
                prompt = [(5 * i + 1) % 49 + 1 for i in range(plen)]
                assert srv.generate(prompt, 6, timeout=300) == \
                    _want(cfg, params, prompt, 6)
        finally:
            srv.stop()

    def test_kernel_ladder_zero_new_compiles(self):
        """The kernel changes WHAT each ladder program
        computes, never how many there are: warmup still compiles the
        same 3 programs (decode + chunk + CoW) and a mixed-length
        storm after warmup triggers ZERO XLA compiles — the
        test_zero.py-style recompile guard for the kernel plane."""
        import jax.monitoring

        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8, prefill_chunk=4)
        assert srv.warmup() == 3                   # the existing ladder
        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for i, plen in enumerate((2, 5, 9, 1, 12)):
                prompt = [(3 * (i + j)) % 49 + 1 for j in range(plen)]
                srv.generate(prompt, 4, timeout=300)
            stats = srv.stats()
        finally:
            jax.monitoring.clear_event_listeners()
            srv.stop()
        assert compiles == []
        assert stats["compiled_programs"] == 3
        assert stats["kv"]["paged_kernel"] is True
        # both programs write their fed rows by the row writer
        # (`generation.kv_rows_by_kernel`)
        assert stats["kv"]["write_path"] == {"w1": "kernel", "w4": "kernel"}

    def test_kernel_speculative_parity(self):
        """The verify dispatch on the kernel path: speculative greedy
        output stays byte-identical to 1-token decode."""
        cfg, params = _lm(max_len=48)
        prompt = [1, 2, 3, 1, 2, 3, 1]
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=8, prefill_chunk=4,
                                 speculate="ngram", draft_len=3)
        try:
            assert srv.generate(prompt, 10, timeout=300) == \
                _want(cfg, params, prompt, 10)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# One KV path, one rule for the kernel: nothing above `generation.py`
# offers a choice


def _serving_entry(name):
    if name == "ContinuousLMServer":
        return ContinuousLMServer.__init__
    if name == "UiServer.serve_lm":
        from deeplearning4j_tpu.ui.server import UiServer

        return UiServer.serve_lm
    from deeplearning4j_tpu.serving.fleet import spawn_local_replica

    return spawn_local_replica


class TestOneKVPath:
    @pytest.mark.parametrize("entry", ["ContinuousLMServer",
                                       "UiServer.serve_lm",
                                       "spawn_local_replica"])
    def test_serving_entry_points_offer_no_kv_mode_or_kernel_switch(
            self, entry):
        import inspect

        params = set(inspect.signature(_serving_entry(entry)).parameters)
        assert not params & {"kv", "lm_kv", "paged_kernel",
                             "lm_paged_kernel"}

    def test_the_oracle_seam_stays_in_generation(self):
        import inspect

        from deeplearning4j_tpu.parallel import generation

        for fn in (generation.paged_forward, generation.make_paged_step,
                   generation.make_spec_step):
            assert "paged_kernel" in inspect.signature(fn).parameters

    def test_no_module_reads_the_retired_env_names(self):
        import pathlib

        import deeplearning4j_tpu

        root = pathlib.Path(deeplearning4j_tpu.__file__).parent
        for path in root.rglob("*.py"):
            text = path.read_text()
            for name in ("DL4J_TPU_PAGED_KERNEL", "DL4J_TPU_FUSED_LSTM"):
                assert name not in text, (path, name)
