"""Tiered KV state hierarchy tests (ISSUE-19 acceptance surface).

Covers: the content-addressed `prefix_key`; the `DiskTier`'s
checksummed blob + atomic manifest economy (roundtrip, LRU byte-cap
eviction, manifest reopen, orphan/stale GC, typed corruption); the
`TieredStateStore`'s host → disk spill with the `SwapStore` surface
preserved; the int8 quantized wire frame (v2) next to byte-exact v1
frames, incl. the typed rejection of a quantized frame on an
exact-bytes pool; idle sticky-session hibernation → resume
BYTE-IDENTICAL to a never-hibernated run (greedy AND seeded, quantize
on AND off, composed with speculation + chunked prefill, zero
off-ladder compiles); a FULL process-restart resume over the same disk
directory with crashed-predecessor debris garbage-collected and
counted; the disk chaos ladder (truncated/bit-flipped/unlinked blobs
caught by the manifest's SHA-256 at take, ENOSPC and kill -9 in the
commit window dropping the entry with `write_failed` counted) — every
victim recomputes from its prompt, streams never duplicate a token,
and the page ledger stays balanced; and preemption swap riding the
same tiers.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.resilience.chaos import (
    DiskChaosConfig,
    chaos_disk,
)
from deeplearning4j_tpu.serving import ContinuousLMServer
from deeplearning4j_tpu.serving.hibernate import (
    DiskTier,
    MANIFEST_NAME,
    TieredStateStore,
    prefix_key,
)
from deeplearning4j_tpu.serving.pressure import SwapEvictedError
from deeplearning4j_tpu.serving.transfer import (
    PageExport,
    PageShipError,
    deserialize_export,
    quantize_export,
    serialize_export,
)

pytestmark = pytest.mark.hibernate

PS = 4


def _lm(max_len=64, n_layers=1):
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


def _srv(cfg, params, tmp=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", PS)
    kw.setdefault("pages", 32)
    if tmp is not None:
        kw.setdefault("state_dir", str(tmp))
    return ContinuousLMServer(cfg, params, **kw)


def _wait_hibernated(srv, n=1, timeout=15.0):
    """Block until the idle sweep has hibernated >= n sessions."""
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if srv.stats().get("hibernate", {}).get("out", 0) >= n:
            return True
        time.sleep(0.02)
    return False


def _frame(prompt, pos, n_layers=1, heads=2, dim=8):
    n_pages = -(-pos // PS)
    rng = np.random.default_rng(0)
    shape = (n_layers, n_pages, PS, heads, dim)
    return PageExport(prompt=list(prompt), max_new=4, temperature=0.0,
                      seed=0, committed=[7], pos=pos, page_size=PS,
                      pages_k=rng.standard_normal(shape).astype(np.float32),
                      pages_v=rng.standard_normal(shape).astype(np.float32),
                      model={"n_layers": n_layers})


# ---------------------------------------------------------------------------
# Units: keys, disk tier, tiered store (no device)


class TestPrefixKey:
    def test_content_addressed_and_stable(self):
        a = prefix_key([1, 2, 3, 4])
        assert a == prefix_key([1, 2, 3, 4])     # pure function of tokens
        assert a != prefix_key([1, 2, 3, 5])
        assert a.startswith("hib-")
        # numpy ints hash identically to python ints (gather paths)
        assert a == prefix_key(np.asarray([1, 2, 3, 4], np.int32))


class TestDiskTier:
    def test_roundtrip_reopen_and_shared_manifest(self, tmp_path):
        d = DiskTier(str(tmp_path), 1 << 20)
        d.put("hib-aa", b"x" * 100)
        d.put("hib-bb", b"y" * 50)
        # a FRESH tier over the same dir (the restart path) sees both
        d2 = DiskTier(str(tmp_path), 1 << 20)
        assert "hib-aa" in d2 and "hib-bb" in d2
        assert d2.take("hib-aa") == b"x" * 100
        assert d2.bytes_stored == 50
        with pytest.raises(SwapEvictedError):
            d2.take("hib-aa")                     # take consumes

    def test_lru_eviction_by_bytes(self, tmp_path):
        d = DiskTier(str(tmp_path), 120)
        assert d.put("hib-a", b"a" * 50) == []
        assert d.put("hib-b", b"b" * 50) == []
        assert d.put("hib-c", b"c" * 50) == ["hib-a"]   # oldest out
        assert d.evicted == 1 and len(d) == 2
        files = [f for f in os.listdir(str(tmp_path))
                 if f.endswith(".kvblob")]
        assert len(files) == 2                    # victim blob unlinked
        assert d.put("hib-huge", b"z" * 200) is None    # refused, not stored
        assert "hib-huge" not in d

    def test_orphan_and_stale_gc_counted(self, tmp_path):
        d = DiskTier(str(tmp_path), 1 << 20)
        d.put("hib-keep", b"k" * 10)
        d.put("swap-0", b"s" * 10)
        # crashed-predecessor debris: a stage file and a stray blob
        (tmp_path / ".tmp-hib-dead.kvblob").write_bytes(b"torn")
        (tmp_path / "hib-stray.kvblob").write_bytes(b"stray")
        d2 = DiskTier(str(tmp_path), 1 << 20)
        assert d2.gc_orphans == 2
        assert not (tmp_path / ".tmp-hib-dead.kvblob").exists()
        assert not (tmp_path / "hib-stray.kvblob").exists()
        assert d2.gc("swap-") == 1               # stale process-local keys
        assert d2.gc_stale == 1
        assert "swap-0" not in d2 and "hib-keep" in d2

    def test_corrupt_blob_typed_and_counted(self, tmp_path):
        d = DiskTier(str(tmp_path), 1 << 20)
        d.put("hib-x", b"q" * 64)
        fname = d._index["hib-x"]["file"]
        p = tmp_path / fname
        raw = bytearray(p.read_bytes())
        raw[10] ^= 0xFF
        p.write_bytes(bytes(raw))
        with pytest.raises(PageShipError, match="integrity"):
            d.take("hib-x")
        assert d.corrupt == 1
        assert "hib-x" not in d                  # poisoned entry dropped
        d.put("hib-y", b"r" * 64)
        os.unlink(str(tmp_path / d._index["hib-y"]["file"]))
        with pytest.raises(PageShipError, match="unreadable"):
            d.take("hib-y")
        assert d.corrupt == 2
        with pytest.raises(SwapEvictedError):
            d.take("hib-missing")


class TestTieredStore:
    def test_host_spills_to_disk_not_void(self, tmp_path):
        s = TieredStateStore(120, disk_dir=str(tmp_path))
        assert s.put("hib-a", b"a" * 80) == []
        # the second put pushes the first DOWN, not out
        assert s.put("hib-b", b"b" * 80) == []
        assert s.spills == 1
        assert "hib-a" in s and "hib-b" in s
        assert s.disk is not None and "hib-a" in s.disk
        assert s.take("hib-b") == b"b" * 80      # host tier
        assert s.take("hib-a") == b"a" * 80      # verified disk read
        st = s.stats()
        assert st["spills"] == 1 and st["disk"]["takes"] == 1

    def test_without_disk_degrades_to_swapstore(self):
        s = TieredStateStore(120)
        s.put("swap-0", b"a" * 80)
        assert s.put("swap-1", b"b" * 80) == ["swap-0"]  # evicted for real
        assert s.evicted == 1
        assert s.put("swap-big", b"z" * 200) is None
        assert s.rejected == 1

    def test_clear_prefix_spares_the_durable_tier(self, tmp_path):
        s = TieredStateStore(1 << 20, disk_dir=str(tmp_path))
        s.put("swap-0", b"s" * 10)
        s.put("hib-a", b"h" * 10)
        s.flush_to_disk()
        s.put("swap-1", b"t" * 10)
        s.clear("swap-")                          # both tiers, swap- only
        assert "swap-0" not in s and "swap-1" not in s
        assert "hib-a" in s.disk
        s.clear()                                 # bare clear: host only
        assert "hib-a" in s.disk


# ---------------------------------------------------------------------------
# The quantized wire frame: v2 next to v1, typed version gate


class TestQuantizedWire:
    def test_quantize_ratio_and_roundtrip(self):
        ex = _frame(list(range(1, 9)), pos=8)
        q = quantize_export(ex)
        assert q.quantized and not ex.quantized
        assert q.nbytes() <= 0.3 * q.exact_nbytes()
        back = deserialize_export(serialize_export(q))
        assert back.quantized
        np.testing.assert_array_equal(back.pages_k, q.pages_k)
        np.testing.assert_array_equal(back.scales_k, q.scales_k)
        deq = back.dequantized()
        assert not deq.quantized
        # int8 per-page scaling holds ~1/127 relative error
        err = np.abs(deq.pages_k - ex.pages_k).max()
        assert err <= np.abs(ex.pages_k).max() / 100
        assert quantize_export(q) is q            # idempotent

    def test_v1_exact_frames_still_parse(self):
        ex = _frame(list(range(1, 9)), pos=8)
        blob = serialize_export(ex)
        back = deserialize_export(blob)
        assert not back.quantized
        np.testing.assert_array_equal(back.pages_k, ex.pages_k)
        assert back.prompt == ex.prompt and back.pos == ex.pos

    def test_quantized_ship_rejected_on_exact_pool(self):
        cfg, params = _lm()
        pre = _srv(cfg, params, ship=True)
        dec = _srv(cfg, params, ship=True, swap_quantize=False)
        try:
            ex = pre.prefill_export([1, 2, 3, 4, 5], 4, timeout=600)
            with pytest.raises(PageShipError, match="quantized"):
                dec.admit_with_pages(quantize_export(ex), timeout=600)
        finally:
            pre.stop()
            dec.stop()

    def test_quantized_ship_accepted_on_quantizing_pool(self):
        cfg, params = _lm()
        pre = _srv(cfg, params, ship=True)
        dec = _srv(cfg, params, ship=True)
        try:
            prompt = [1, 2, 3, 4, 5]
            ex = pre.prefill_export(prompt, 4, timeout=600)
            got = dec.admit_with_pages(quantize_export(ex), timeout=600)
            assert got == _want(cfg, params, prompt, 4)
        finally:
            pre.stop()
            dec.stop()


# ---------------------------------------------------------------------------
# Hibernate → resume byte-parity (the tentpole acceptance)


class TestHibernateResume:
    def _two_turns(self, tmp_path, *, turn2_extra=(3, 4), gen_kw=None,
                   srv_kw=None, between=None):
        """Turn 1 on a sticky session, idle past the deadline (the
        sweep hibernates it), then turn 2 whose prompt extends turn 1's
        full sequence.  Returns (turn2_out, turn2_prompt, stats)."""
        cfg, params = _lm()
        gen_kw = dict(gen_kw or {})
        srv = _srv(cfg, params, tmp_path, hibernate_idle_s=0.15,
                   **(srv_kw or {}))
        try:
            srv.warmup()
            out1 = srv.generate(list(range(1, 9)), 8, timeout=600,
                                session_id="s1", **gen_kw)
            assert _wait_hibernated(srv), "idle sweep never fired"
            if between is not None:
                between(srv)
            p2 = out1 + list(turn2_extra)
            out2 = srv.generate(p2, 6, timeout=600, session_id="s1",
                                **gen_kw)
            stats = srv.stats()
            with srv._cond:
                assert srv._pool.check_ledger()["balanced"]
        finally:
            srv.stop()
        return out2, p2, stats

    def _reference(self, p2, gen_kw=None):
        cfg, params = _lm()
        ref_srv = _srv(cfg, params)
        try:
            return ref_srv.generate(p2, 6, timeout=600,
                                    **(gen_kw or {}))
        finally:
            ref_srv.stop()

    def test_greedy_resume_byte_identical(self, tmp_path):
        out2, p2, stats = self._two_turns(tmp_path)
        assert stats["hibernate"]["out"] == 1
        assert stats["hibernate"]["in"] == 1
        assert stats["hibernate"]["bytes_ratio"] <= 0.3
        assert out2 == self._reference(p2)
        assert out2 == _want(*_lm(), p2, 6)

    def test_seeded_resume_byte_identical(self, tmp_path):
        kw = {"temperature": 0.8, "seed": 11}
        out2, p2, stats = self._two_turns(tmp_path, gen_kw=kw)
        assert stats["hibernate"]["in"] == 1
        assert out2 == self._reference(p2, gen_kw=kw)

    def test_resume_composes_with_speculation_and_chunks(self, tmp_path):
        out2, p2, stats = self._two_turns(
            tmp_path, srv_kw={"speculate": "ngram", "prefill_chunk": 4})
        assert stats["hibernate"]["in"] == 1
        assert out2 == _want(*_lm(), p2, 6)

    def test_exact_mode_resume(self, tmp_path):
        out2, p2, stats = self._two_turns(
            tmp_path, srv_kw={"swap_quantize": False})
        assert stats["hibernate"]["in"] == 1
        # opt-out really stores exact bytes: ratio 1.0, not ~0.26
        assert stats["hibernate"]["bytes"] == \
            stats["hibernate"]["exact_bytes"]
        assert out2 == self._reference(p2)

    def test_zero_offladder_compiles(self, tmp_path):
        import jax.monitoring

        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        def arm(srv):
            jax.monitoring.register_event_duration_secs_listener(listener)

        try:
            out2, p2, stats = self._two_turns(tmp_path, between=arm)
        finally:
            jax.monitoring.clear_event_listeners()
        assert stats["hibernate"]["in"] == 1
        assert not compiles, "resume must not mint programs"
        assert out2 == _want(*_lm(), p2, 6)

    def test_resume_from_the_disk_tier(self, tmp_path):
        # force the blob all the way down before the resume probes it
        def flush(srv):
            with srv._cond:
                assert srv._swap.flush_to_disk() >= 1
        out2, p2, stats = self._two_turns(tmp_path, between=flush)
        assert stats["hibernate"]["in"] == 1
        assert stats["hibernation"]["store"]["disk"]["takes"] == 1
        assert out2 == self._reference(p2)


class TestRestartResume:
    def test_fresh_process_resumes_from_the_manifest(self, tmp_path):
        """The durable half of hibernation: a NEW server over the same
        disk directory re-opens the manifest, GCs a crashed
        predecessor's debris (counted), and resumes the session
        byte-identically — device, host tier and process all gone."""
        cfg, params = _lm()
        srv1 = _srv(cfg, params, tmp_path, hibernate_idle_s=0.15)
        try:
            out1 = srv1.generate(list(range(1, 9)), 8, timeout=600,
                                 session_id="s1")
            assert _wait_hibernated(srv1)
            with srv1._cond:
                assert srv1._swap.flush_to_disk() >= 1
        finally:
            srv1.stop()
        # simulate the predecessor dying mid-write: stage debris + a
        # stray unmanifested blob
        (tmp_path / ".tmp-hib-dead.kvblob").write_bytes(b"torn")
        (tmp_path / "hib-stray.kvblob").write_bytes(b"stray")
        assert (tmp_path / MANIFEST_NAME).exists()

        srv2 = _srv(cfg, params, tmp_path, hibernate_idle_s=30.0)
        try:
            p2 = out1 + [3, 4]
            out2 = srv2.generate(p2, 6, timeout=600, session_id="s1")
            stats = srv2.stats()
            assert stats["hibernate"]["in"] == 1
            disk = stats["hibernation"]["store"]["disk"]
            assert disk["gc_orphans"] == 2       # debris counted, gone
            assert not (tmp_path / "hib-stray.kvblob").exists()
        finally:
            srv2.stop()
        assert out2 == _want(cfg, params, p2, 6)

    def test_clean_stop_flushes_host_tier_to_disk(self, tmp_path):
        """No explicit flush: stop() itself must demote host-resident
        hibernations so a successor over the same state_dir RESUMES
        (hibernate.in == 1) rather than silently recomputing — the gap
        the HTTP verify drive caught."""
        cfg, params = _lm()
        srv1 = _srv(cfg, params, tmp_path, hibernate_idle_s=0.15)
        try:
            out1 = srv1.generate(list(range(1, 9)), 8, timeout=600,
                                 session_id="s1")
            assert _wait_hibernated(srv1)
        finally:
            srv1.stop()
        srv2 = _srv(cfg, params, tmp_path, hibernate_idle_s=30.0)
        try:
            p2 = out1 + [3, 4]
            out2 = srv2.generate(p2, 6, timeout=600, session_id="s1")
            assert srv2.stats()["hibernate"]["in"] == 1
        finally:
            srv2.stop()
        assert out2 == _want(cfg, params, p2, 6)


# ---------------------------------------------------------------------------
# The disk chaos ladder: every rung recomputes, typed, balanced


class TestDiskChaos:
    def _chaos_resume(self, tmp_path, disk_cfg, *, stream=False):
        """Hibernate, flush to a FAULTY disk, resume: the victim must
        recompute from its prompt with the loss typed and counted."""
        cfg, params = _lm()
        srv = _srv(cfg, params, tmp_path, hibernate_idle_s=0.15)
        try:
            srv.warmup()
            out1 = srv.generate(list(range(1, 9)), 8, timeout=600,
                                session_id="s1")
            assert _wait_hibernated(srv)
            with srv._cond:
                chaos_disk(srv._swap, disk_cfg)
                srv._swap.flush_to_disk()
            p2 = out1 + [3, 4]
            if stream:
                toks = []
                for t in srv.generate_stream(p2, 6, timeout=600,
                                             session_id="s1"):
                    toks.append(t)
                out2 = p2 + toks
            else:
                out2 = srv.generate(p2, 6, timeout=600, session_id="s1")
            stats = srv.stats()
            with srv._cond:
                assert srv._pool.check_ledger()["balanced"]
        finally:
            srv.stop()
        assert out2 == _want(cfg, params, p2, 6), \
            "chaos must never change tokens"
        return stats

    def test_truncated_blob_recomputes(self, tmp_path):
        stats = self._chaos_resume(
            tmp_path, DiskChaosConfig(truncate_writes=(0,)))
        assert stats["hibernate"]["corrupt"] == 1
        assert stats["hibernate"]["in"] == 0

    def test_bitflipped_blob_recomputes(self, tmp_path):
        stats = self._chaos_resume(
            tmp_path, DiskChaosConfig(flip_writes=(0,)))
        assert stats["hibernate"]["corrupt"] == 1
        assert stats["hibernate"]["in"] == 0

    def test_unlinked_blob_recomputes(self, tmp_path):
        stats = self._chaos_resume(
            tmp_path, DiskChaosConfig(unlink_writes=(0,)))
        assert stats["hibernate"]["corrupt"] == 1
        assert stats["hibernate"]["in"] == 0

    def test_enospc_drops_the_entry_typed(self, tmp_path):
        stats = self._chaos_resume(
            tmp_path, DiskChaosConfig(enospc_writes=(0,)))
        disk = stats["hibernation"]["store"]["disk"]
        assert disk["write_failed"] == 1
        assert stats["hibernate"]["in"] == 0     # nothing durable to find

    def test_kill_in_commit_window_leaves_only_debris(self, tmp_path):
        stats = self._chaos_resume(
            tmp_path, DiskChaosConfig(kill_writes=(0,)))
        disk = stats["hibernation"]["store"]["disk"]
        assert disk["write_failed"] == 1
        assert stats["hibernate"]["in"] == 0
        # the successor GCs the orphaned stage file
        d2 = DiskTier(str(tmp_path), 1 << 20)
        assert d2.gc_orphans >= 1
        assert not [f for f in os.listdir(str(tmp_path))
                    if f.startswith(".tmp-")]

    def test_streamed_resume_never_duplicates(self, tmp_path):
        stats = self._chaos_resume(
            tmp_path, DiskChaosConfig(flip_writes=(0,)), stream=True)
        assert stats["hibernate"]["corrupt"] == 1


# ---------------------------------------------------------------------------
# Preemption swap rides the same hierarchy


class TestPreemptionOnTiers:
    def test_preempted_victim_resumes_through_the_store(self, tmp_path):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2,
                                 page_size=PS, pages=8, prefill_chunk=4,
                                 preempt=True, state_dir=str(tmp_path))
        res = {}
        try:
            srv.warmup()

            def victim():
                res["v"] = srv.generate([1, 2, 3], 28,
                                        priority="best_effort",
                                        timeout=600)

            t = threading.Thread(target=victim)
            t.start()
            deadline = time.perf_counter() + 10
            while time.perf_counter() < deadline:
                with srv._cond:
                    s = srv._slots[0]
                    if (s.active and s.req is not None
                            and s.fed >= len(s.req.prompt)
                            and len(s.generated) >= 2):
                        break
                time.sleep(0.002)
            res["ia"] = srv.generate([4, 5, 6, 7], 8,
                                     priority="interactive", timeout=600)
            t.join(timeout=600)
            stats = srv.stats()
            with srv._cond:
                assert srv._pool.check_ledger()["balanced"]
        finally:
            srv.stop()
        assert stats.get("preemptions", 0) >= 1
        # the swap frame was quantized in transit (default on)
        assert stats["swap"]["out"] >= 1
        assert res["v"] == _want(cfg, params, [1, 2, 3], 28)
        assert res["ia"] == _want(cfg, params, [4, 5, 6, 7], 8)

    def test_stale_swap_keys_gcd_on_restart(self, tmp_path):
        d = DiskTier(str(tmp_path), 1 << 20)
        d.put("swap-0", b"dead lane" * 4)
        d.put("hib-live", b"hibernated" * 4)
        del d
        cfg, params = _lm()
        srv = _srv(cfg, params, tmp_path, preempt=True)
        try:
            with srv._cond:
                assert "swap-0" not in srv._swap      # never resumable
                assert "hib-live" in srv._swap        # durable, kept
                assert srv._swap.disk.gc_stale == 1
        finally:
            srv.stop()
