"""Disaggregated prefill/decode serving tests (ISSUE-14 acceptance).

Covers: the KV page-shipping wire format (round-trip, SHA-256
integrity, geometry compatibility — every malformed input a typed
`PageShipError`); shipped-lane byte parity against whole-sequence
`generate()` (greedy AND seeded sampling, across page sizes including
non-dividing ones, with speculation on the decode side, with the
page ledger balanced on BOTH workers after every storm); the
role-based fleet — long prompts split prefill->ship->decode, short
prompts straight to decode workers, prefill-only workers never taking
direct LM traffic; the recompute failure ladder (corrupted shipment ->
typed 422 -> local recompute; a prefill worker killed mid-storm ->
resubmit to a peer / recompute, ZERO failed requests); sticky
`session_id` rendezvous affinity (fleet prefix hit rate + affinity-hit
counters, and the same `session_id` payload accepted on a bare
single-replica serve); SSE token streaming (event concatenation ==
the non-streamed body, mid-stream client disconnect freeing the slot
and its pages); TTFT accounting; and the zero-compile guard over the
whole disagg path after warmup.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request
from concurrent import futures

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.serving import (
    ContinuousLMServer,
    FleetRouter,
    spawn_local_replica,
)
from deeplearning4j_tpu.serving.transfer import (
    PageExport,
    PageShipError,
    check_compatible,
    deserialize_export,
    model_signature,
    serialize_export,
)

pytestmark = pytest.mark.disagg

PS, CHUNK, SLOTS, MAXLEN = 8, 4, 2, 64


def _lm(max_len=MAXLEN, n_layers=1):
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


def _srv(cfg, params, *, page_size=PS, ship=True, **kw):
    return ContinuousLMServer(cfg, params, slots=SLOTS,
                              page_size=page_size, prefill_chunk=CHUNK,
                              ship=ship, **kw)


@pytest.fixture(scope="module")
def lm():
    return _lm()


# ---------------------------------------------------------------------------
# Wire format (no device)


def _fake_export(n_pages=2, ps=4, layers=1, heads=2, kd=8, plen=7):
    rng = np.random.default_rng(0)
    pk = rng.random((layers, n_pages, ps, heads, kd)).astype(np.float32)
    pv = rng.random((layers, n_pages, ps, heads, kd)).astype(np.float32)
    return PageExport(
        prompt=list(range(plen)), max_new=5, temperature=0.5, seed=7,
        committed=[3], pos=plen, page_size=ps, pages_k=pk, pages_v=pv,
        model={"n_layers": layers, "n_heads": heads, "head_dim": kd,
               "dtype": "float32", "max_len": 32, "vocab_size": 50,
               "page_size": ps},
        session_id="sess-1")


class TestWireFormat:
    def test_round_trip(self):
        ex = _fake_export()
        out = deserialize_export(serialize_export(ex))
        assert out.prompt == ex.prompt and out.committed == [3]
        assert out.max_new == 5 and out.seed == 7 and out.pos == ex.pos
        assert out.temperature == 0.5 and out.session_id == "sess-1"
        assert np.array_equal(out.pages_k, ex.pages_k)
        assert np.array_equal(out.pages_v, ex.pages_v)
        assert out.model == ex.model

    def test_corrupted_payload_rejected(self):
        blob = bytearray(serialize_export(_fake_export()))
        blob[-5] ^= 0x20                       # flip one payload bit
        with pytest.raises(PageShipError, match="integrity"):
            deserialize_export(bytes(blob))

    def test_truncated_and_misframed_rejected(self):
        blob = serialize_export(_fake_export())
        with pytest.raises(PageShipError):
            deserialize_export(blob[:10])          # truncated header
        with pytest.raises(PageShipError):
            deserialize_export(blob[:-9])          # truncated payload
        with pytest.raises(PageShipError, match="magic"):
            deserialize_export(b"NOPE" + blob[4:])
        with pytest.raises(PageShipError):
            deserialize_export(b"")

    def test_header_tampering_rejected(self):
        import struct

        from deeplearning4j_tpu.serving.transfer import MAGIC

        ex = _fake_export()
        blob = serialize_export(ex)
        pre = len(MAGIC) + 4
        (hlen,) = struct.unpack(">I", blob[len(MAGIC):pre])
        header = json.loads(blob[pre:pre + hlen])
        del header["sha256"]
        hj = json.dumps(header).encode()
        forged = MAGIC + struct.pack(">I", len(hj)) + hj + blob[pre + hlen:]
        with pytest.raises(PageShipError, match="missing"):
            deserialize_export(forged)

    def test_compatibility_gate(self, lm):
        cfg, _ = lm
        ex = _fake_export()
        with pytest.raises(PageShipError, match="incompatible"):
            check_compatible(ex, cfg, PS)      # d16/2-head vs fake geometry
        sig = model_signature(cfg, PS)
        assert sig["page_size"] == PS and sig["n_layers"] == cfg.n_layers


# ---------------------------------------------------------------------------
# Shipped-lane byte parity (the acceptance core)


class TestShipParity:
    @pytest.mark.parametrize("ps", [8, 5])   # 5 does not divide prompts
    def test_greedy_parity_across_page_sizes(self, lm, ps):
        cfg, params = lm
        rng = np.random.default_rng(ps)
        pre = _srv(cfg, params, page_size=ps)
        dec = _srv(cfg, params, page_size=ps)
        try:
            for plen, new in ((13, 8), (16, 6), (7, 1), (22, 10)):
                prompt = rng.integers(0, 50, (plen,)).tolist()
                ex = deserialize_export(serialize_export(
                    pre.prefill_export(prompt, new, timeout=60)))
                got = dec.admit_with_pages(ex, timeout=60)
                assert got == _want(cfg, params, prompt, new)
            assert pre._pool.check_ledger()["balanced"]
            assert dec._pool.check_ledger()["balanced"]
        finally:
            pre.stop()
            dec.stop()

    def test_seeded_sampling_parity(self, lm):
        """A shipped sampled lane must match a locally-decoded one
        bit-for-bit: the fold_in(seed, count) automaton sees identical
        (seed, count) sequences on both sides of the wire."""
        cfg, params = lm
        rng = np.random.default_rng(1)
        pre = _srv(cfg, params)
        dec = _srv(cfg, params)
        loc = _srv(cfg, params, ship=False)
        try:
            for seed in (0, 3, 99):
                prompt = rng.integers(0, 50, (11,)).tolist()
                ex = pre.prefill_export(prompt, 8, temperature=0.8,
                                        seed=seed, timeout=60)
                got = dec.admit_with_pages(ex, timeout=60)
                want = loc.generate(prompt, 8, temperature=0.8,
                                    seed=seed, timeout=60)
                assert got == want
        finally:
            pre.stop()
            dec.stop()
            loc.stop()

    def test_ship_into_speculating_pool(self, lm):
        """A shipped lane joining a decode worker that speculates stays
        byte-identical: the lane arrives in decode phase with history,
        exactly what the drafter feeds on."""
        cfg, params = lm
        rng = np.random.default_rng(2)
        pre = _srv(cfg, params)
        dec = _srv(cfg, params, speculate="ngram", draft_len=3)
        try:
            prompt = rng.integers(0, 50, (12,)).tolist()
            # a repetitive tail so the n-gram drafter actually proposes
            prompt = prompt[:4] * 3
            ex = pre.prefill_export(prompt, 12, timeout=60)
            got = dec.admit_with_pages(ex, timeout=60)
            assert got == _want(cfg, params, prompt, 12)
            assert dec._pool.check_ledger()["balanced"]
        finally:
            pre.stop()
            dec.stop()

    def test_concurrent_ship_and_local_traffic(self, lm):
        """Shipped lanes join mid-flight like chunked-prefill
        completions: local requests decoding on the importer keep their
        own outputs byte-identical while imports install around them."""
        cfg, params = lm
        rng = np.random.default_rng(3)
        pre = _srv(cfg, params)
        dec = _srv(cfg, params)
        prompts = [rng.integers(0, 50, (10 + i,)).tolist()
                   for i in range(4)]
        want = {tuple(p): _want(cfg, params, p, 8) for p in prompts}
        try:
            with futures.ThreadPoolExecutor(4) as pool:
                def shipped(p):
                    ex = pre.prefill_export(list(p), 8, timeout=120)
                    return dec.admit_with_pages(ex, timeout=120)

                jobs = [pool.submit(shipped, p) if i % 2
                        else pool.submit(lambda p=p: dec.generate(
                            list(p), 8, timeout=120), p)
                        for i, p in enumerate(prompts)]
                for p, job in zip(prompts, jobs):
                    assert job.result(timeout=120) == want[tuple(p)]
            assert pre._pool.check_ledger()["balanced"]
            assert dec._pool.check_ledger()["balanced"]
        finally:
            pre.stop()
            dec.stop()

    def test_second_ship_reuses_decode_radix(self, lm):
        """A sticky session's next turn re-ships its grown prompt; the
        decode pool must REUSE the prefix pages it already caches
        instead of installing duplicate shipped copies — page pressure
        grows with new tokens, not with O(turns x prompt)."""
        cfg, params = lm
        rng = np.random.default_rng(7)
        pre = _srv(cfg, params)
        dec = _srv(cfg, params)
        try:
            system = rng.integers(0, 50, (16,)).tolist()  # 2 full pages
            for i, tail in enumerate(([1, 2], [3, 4])):
                prompt = system + tail
                ex = pre.prefill_export(prompt, 6, timeout=60)
                got = dec.admit_with_pages(ex, timeout=60)
                assert got == _want(cfg, params, prompt, 6)
            st = dec.stats()
            # the second import radix-matched the shared system pages
            assert st["prefix_hits"] >= 1
            assert st["prefix_tokens_saved"] >= 16
            assert dec._pool.check_ledger()["balanced"]
        finally:
            pre.stop()
            dec.stop()

    def test_prefill_worker_keeps_radix_prefix(self, lm):
        """Export does not strip the prefill worker's radix cache: the
        second export of a shared-prefix prompt reuses cached pages."""
        cfg, params = lm
        rng = np.random.default_rng(4)
        pre = _srv(cfg, params)
        try:
            system = rng.integers(0, 50, (16,)).tolist()
            pre.prefill_export(system + [1, 2], 4, timeout=60)
            pre.prefill_export(system + [3, 4], 4, timeout=60)
            st = pre.stats()
            assert st["prefix_hits"] >= 1
            assert st["ship"]["out"] == 2
        finally:
            pre.stop()

    def test_ship_requires_paged_and_flag(self, lm):
        cfg, params = lm
        srv = _srv(cfg, params, ship=False)
        try:
            with pytest.raises(ValueError, match="ship"):
                srv.prefill_export([1, 2, 3], 4)
            with pytest.raises(ValueError, match="ship"):
                srv.admit_with_pages(_fake_export())
        finally:
            srv.stop()

    def test_incompatible_geometry_rejected_typed(self, lm):
        cfg, params = lm
        dec = _srv(cfg, params)
        try:
            with pytest.raises(PageShipError, match="incompatible"):
                dec.admit_with_pages(_fake_export())
        finally:
            dec.stop()

    def test_zero_compiles_after_warmup(self, lm):
        """The whole disagg path — prefill, gather, wire, install,
        decode — runs ZERO XLA compiles after warmup, and the program
        count accounts for the shipping pair."""
        import jax.monitoring

        cfg, params = lm
        rng = np.random.default_rng(5)
        pre = _srv(cfg, params)
        dec = _srv(cfg, params)
        try:
            assert pre.warmup() == 5       # decode+chunk+copy+gather+install
            assert dec.warmup() == 5
            prompts = [rng.integers(0, 50, (13,)).tolist()
                       for _ in range(3)]
            # ground truth BEFORE the listener: generate() compiles per
            # (batch, prompt_len, max_new) and must not taint the count
            want = {tuple(p): _want(cfg, params, p, 6) for p in prompts}
            compiles = []

            def listener(event, duration, **kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    compiles.append(event)

            jax.monitoring.register_event_duration_secs_listener(listener)
            try:
                for prompt in prompts:
                    ex = pre.prefill_export(prompt, 6, timeout=60)
                    got = dec.admit_with_pages(
                        deserialize_export(serialize_export(ex)),
                        timeout=60)
                    assert got == want[tuple(prompt)]
            finally:
                jax.monitoring.clear_event_listeners()
            assert not compiles
        finally:
            pre.stop()
            dec.stop()

    def test_ttft_and_ship_accounting(self, lm):
        cfg, params = lm
        rng = np.random.default_rng(6)
        pre = _srv(cfg, params)
        dec = _srv(cfg, params)
        try:
            prompt = rng.integers(0, 50, (13,)).tolist()
            ex = pre.prefill_export(prompt, 6, timeout=60)
            dec.admit_with_pages(ex, timeout=60)
            n_pages = -(-len(prompt) // PS)
            pst, dst = pre.stats(), dec.stats()
            assert pst["ship"]["out"] == 1 and dst["ship"]["in"] == 1
            assert pst["ship"]["pages_shipped"] == n_pages
            assert pst["ship"]["ship_bytes"] == ex.nbytes()
            assert pst["ttft"]["count"] == 1   # prefill committed token 1
            assert dst["ttft"]["count"] == 1   # import stamps at install
            assert ex.n_pages == n_pages
        finally:
            pre.stop()
            dec.stop()


# ---------------------------------------------------------------------------
# Role-based fleet: split routing + the recompute failure ladder


def _mk_replica(lm_pair, name, role):
    return spawn_local_replica(
        name, lm=lm_pair, lm_slots=SLOTS, lm_page_size=PS,
        lm_prefill_chunk=CHUNK, role=role)


class TestFleetDisagg:
    @pytest.fixture(scope="class")
    def fleet(self, lm):
        router = FleetRouter(disagg_min_prompt=16, request_timeout_s=120)
        names = [("prefill-0", "prefill"), ("decode-0", "decode"),
                 ("decode-1", "decode")]
        for name, role in names:
            router.attach(_mk_replica(lm, name, role))
        yield router
        router.stop()

    def test_long_prompt_ships_short_decodes_direct(self, lm, fleet):
        cfg, params = lm
        rng = np.random.default_rng(10)
        ships0 = fleet.ships
        long_p = rng.integers(0, 50, (24,)).tolist()
        short_p = rng.integers(0, 50, (4,)).tolist()
        assert fleet.generate(long_p, 8, timeout=120) == _want(
            cfg, params, long_p, 8)
        assert fleet.ships == ships0 + 1
        roles0 = dict(fleet._role_requests)
        assert fleet.generate(short_p, 8, timeout=120) == _want(
            cfg, params, short_p, 8)
        # the short prompt never touched the prefill worker
        assert fleet._role_requests["prefill"] == roles0["prefill"]
        assert fleet._role_requests["decode"] == roles0["decode"] + 1

    def test_one_trace_names_prefill_ship_decode(self, lm, fleet):
        rng = np.random.default_rng(11)
        long_p = rng.integers(0, 50, (20,)).tolist()
        rid = "disagg-trace-1"
        fleet.generate_payload(long_p, 6, timeout=120, request_id=rid)
        tr = next(t for t in fleet.tracer.recent()
                  if t.get("request_id") == rid)
        stages = [s.get("attrs", {}).get("stage") for s in tr["spans"]]
        assert "prefill" in stages and "decode" in stages
        assert any(s["name"] == "ship" for s in tr["spans"])
        assert tr.get("attrs", {}).get("disagg") is True

    def test_corrupted_ship_recomputes_locally(self, lm, fleet,
                                               monkeypatch):
        """A shipment corrupted on the wire is rejected typed (422) by
        the decode worker and the router recomputes locally — the
        client still gets byte-identical output, never an error."""
        cfg, params = lm
        rng = np.random.default_rng(12)
        long_p = rng.integers(0, 50, (21,)).tolist()
        real_http = fleet._http

        def corrupting(method, url, body=None, timeout=None, **kw):
            status, payload = real_http(method, url, body=body,
                                        timeout=timeout, **kw)
            if url.endswith("/lm/prefill") and isinstance(payload, bytes):
                blob = bytearray(payload)
                blob[-7] ^= 0x10
                payload = bytes(blob)
            return status, payload

        monkeypatch.setattr(fleet, "_http", corrupting)
        fb0 = fleet.ship_fallbacks
        assert fleet.generate(long_p, 8, timeout=120) == _want(
            cfg, params, long_p, 8)
        assert fleet.ship_fallbacks == fb0 + 1

    def test_no_decode_worker_is_typed(self, lm):
        from deeplearning4j_tpu.serving import ServingUnavailableError

        router = FleetRouter(disagg_min_prompt=16, request_timeout_s=60)
        router.attach(_mk_replica(lm, "prefill-only", "prefill"))
        try:
            with pytest.raises(ServingUnavailableError):
                router.generate(list(range(20)), 4, timeout=30)
        finally:
            router.stop()

    def test_mid_storm_prefill_kill_zero_failed(self, lm):
        """ACCEPTANCE: a prefill worker SIGKILL'd mid-storm costs
        resubmissions/recomputes, never a failed request — and every
        output stays byte-identical."""
        cfg, params = lm
        rng = np.random.default_rng(13)
        router = FleetRouter(disagg_min_prompt=16, request_timeout_s=120)
        pre0 = router.attach(_mk_replica(lm, "prefill-0", "prefill"))
        router.attach(_mk_replica(lm, "prefill-1", "prefill"))
        d0 = router.attach(_mk_replica(lm, "decode-0", "decode"))
        d1 = router.attach(_mk_replica(lm, "decode-1", "decode"))
        prompts = [rng.integers(0, 50, (18 + (i % 5),)).tolist()
                   for i in range(12)]
        want = {tuple(p): _want(cfg, params, p, 6) for p in prompts}
        failed, done = [], []
        lock = threading.Lock()

        def one(p):
            try:
                out = router.generate(list(p), 6, timeout=120)
            except Exception as e:  # noqa: BLE001 — the storm COUNTS failures
                with lock:
                    failed.append((p, repr(e)))
                return
            assert out == want[tuple(p)]
            with lock:
                done.append(p)
                kill = len(done) == 3
            if kill:
                pre0.kill()            # mid-storm prefill-worker death
        try:
            with futures.ThreadPoolExecutor(4) as pool:
                list(pool.map(one, prompts))
            assert failed == []
            assert len(done) == len(prompts)
            for r in (d0, d1):
                ledger = r.server.state.lm_server._pool.check_ledger()
                assert ledger["balanced"], ledger
        finally:
            router.stop()

    def test_sticky_session_storm(self, lm):
        """Sticky sessions: each conversation's turns land on the
        replica holding its pages — replica-side affinity hits count
        every repeat visit, and the fleet-aggregated prefix hit rate
        shows the radix reuse the stickiness buys."""
        cfg, params = lm
        rng = np.random.default_rng(14)
        router = FleetRouter(request_timeout_s=120)
        for i in range(2):
            router.attach(_mk_replica(lm, f"both-{i}", "both"))
        sessions = {f"chat-{k}": rng.integers(0, 50, (12,)).tolist()
                    for k in range(4)}
        turns = 3
        try:
            convo = {sid: list(start)
                     for sid, start in sessions.items()}
            for t in range(turns):
                for sid in sessions:
                    prompt = convo[sid]
                    out = router.generate(prompt, 4, timeout=120,
                                          session_id=sid)
                    assert out == _want(cfg, params, prompt, 4)
                    convo[sid] = out       # next turn extends the chat
            # every turn after the first re-landed on its replica
            assert router.session_affinity_hits == len(sessions) * (
                turns - 1)
            stats = router.fleet_stats()
            prefix = stats["fleet"].get("lm_prefix", {})
            assert prefix.get("hit_rate", 0.0) > 0.3
            disagg = stats["fleet"]["disagg"]
            assert disagg["replica_session_affinity_hits"] == (
                len(sessions) * (turns - 1))
        finally:
            router.stop()


# ---------------------------------------------------------------------------
# SSE token streaming


class TestStreaming:
    def test_stream_parity_and_multi_commit(self, lm):
        cfg, params = lm
        rng = np.random.default_rng(20)
        srv = _srv(cfg, params, ship=False, speculate="ngram",
                   draft_len=3)
        try:
            prompt = rng.integers(0, 50, (4,)).tolist() * 3
            toks = list(srv.generate_stream(prompt, 10, timeout=60))
            assert prompt + toks == _want(cfg, params, prompt, 10)
            # speculation commits multiple tokens per round; every one
            # still streams as its own event
            assert len(toks) == 10
        finally:
            srv.stop()

    def test_stream_close_abandons_request(self, lm):
        """Deterministic disconnect: closing the token iterator after
        the first token abandons the request — its slot and pages free
        at the next admit round, counted shed."""
        cfg, params = lm
        rng = np.random.default_rng(21)
        srv = _srv(cfg, params, ship=False)
        try:
            prompt = rng.integers(0, 50, (9,)).tolist()
            gen = srv.generate_stream(prompt, 40, timeout=60)
            first = next(gen)
            assert isinstance(first, int)
            gen.close()                      # client goes away
            deadline = time.perf_counter() + 10
            while time.perf_counter() < deadline:
                with srv._cond:
                    idle = (not any(s.active for s in srv._slots)
                            and not srv._queue)
                if idle:
                    break
                time.sleep(0.01)
            assert idle
            assert srv._pool.check_ledger()["balanced"]
            assert srv.stats()["shed"] >= 1
        finally:
            srv.stop()

    def test_http_sse_parity(self, lm):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = lm
        rng = np.random.default_rng(22)
        prompt = rng.integers(0, 50, (9,)).tolist()
        ui = UiServer(port=0)
        ui.serve_lm(cfg, params, slots=SLOTS, page_size=PS,
                    prefill_chunk=CHUNK)
        ui.start()
        try:
            body = json.dumps({"prompt_ids": prompt,
                               "max_new_tokens": 6, "stream": True,
                               "session_id": "s1"}).encode()
            req = urllib.request.Request(
                ui.url + "/lm/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.headers["Content-Type"] == "text/event-stream"
                raw = r.read().decode()
            events = [e for e in raw.split("\n\n") if e.strip()]
            toks = [json.loads(e.split("data: ", 1)[1])["token"]
                    for e in events if e.startswith("data: ")]
            done = next(e for e in events if e.startswith("event: done"))
            ids = json.loads(done.split("data: ", 1)[1])["ids"]
            want = _want(cfg, params, prompt, 6)
            # concatenated token events == the non-streamed body
            assert ids == want and prompt + toks == want
        finally:
            ui.stop()

    def test_http_disconnect_frees_slot_and_pages(self, lm):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = lm
        rng = np.random.default_rng(23)
        prompt = rng.integers(0, 50, (9,)).tolist()
        ui = UiServer(port=0)
        ui.serve_lm(cfg, params, slots=SLOTS, page_size=PS,
                    prefill_chunk=CHUNK)
        ui.start()
        try:
            host, port = ui.url.replace("http://", "").split(":")
            body = json.dumps({"prompt_ids": prompt,
                               "max_new_tokens": 50,
                               "stream": True}).encode()
            s = socket.create_connection((host, int(port)))
            s.sendall(b"POST /lm/generate HTTP/1.1\r\nHost: x\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: %d\r\n\r\n" % len(body) + body)
            s.recv(256)                      # first event bytes arrived
            s.close()                        # mid-stream disconnect
            srv = ui.state.lm_server
            deadline = time.perf_counter() + 15
            while time.perf_counter() < deadline:
                with srv._cond:
                    idle = (not any(sl.active for sl in srv._slots)
                            and not srv._queue)
                if idle:
                    break
                time.sleep(0.02)
            assert idle
            assert srv._pool.check_ledger()["balanced"]
        finally:
            ui.stop()

    def test_stream_refused_on_whole_sequence_legs(self, lm):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = lm
        ui = UiServer(port=0)
        ui.serve_lm(cfg, params, slots=SLOTS, page_size=PS,
                    prefill_chunk=CHUNK)
        ui.start()
        try:
            body = json.dumps({"prompt_ids": [1, 2, 3],
                               "max_new_tokens": 4, "stream": True,
                               "beam_size": 2}).encode()
            req = urllib.request.Request(
                ui.url + "/lm/generate", data=body,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 400
            assert "stream" in json.loads(ei.value.read())["error"]
        finally:
            ui.stop()

    def test_fleet_front_stream_passthrough(self, lm):
        from deeplearning4j_tpu.serving import FleetServer

        cfg, params = lm
        rng = np.random.default_rng(24)
        prompt = rng.integers(0, 50, (8,)).tolist()
        router = FleetRouter(request_timeout_s=120)
        router.attach(_mk_replica(lm, "both-0", "both"))
        front = FleetServer(router, port=0).start()
        try:
            body = json.dumps({"prompt_ids": prompt,
                               "max_new_tokens": 5,
                               "stream": True}).encode()
            req = urllib.request.Request(
                front.url + "/lm/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.headers["Content-Type"] == "text/event-stream"
                raw = r.read().decode()
            done = next(e for e in raw.split("\n\n")
                        if e.startswith("event: done"))
            ids = json.loads(done.split("data: ", 1)[1])["ids"]
            assert ids == _want(cfg, params, prompt, 5)
            # sampling knobs forward: the fleet front must relay the
            # replica's typed 400, never silently downgrade a sampled
            # stream to greedy
            bad = json.dumps({"prompt_ids": prompt,
                              "max_new_tokens": 5, "stream": True,
                              "beam_size": 2}).encode()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    front.url + "/lm/generate", data=bad,
                    headers={"Content-Type": "application/json"}),
                    timeout=30)
            assert ei.value.code == 400
        finally:
            front.stop()


# ---------------------------------------------------------------------------
# HTTP ship surface + single-serve session satellite


class TestHTTPSurface:
    @pytest.fixture(scope="class")
    def ui(self, lm):
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = lm
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=SLOTS, page_size=PS,
                     prefill_chunk=CHUNK, ship=True)
        srv.start()
        yield srv
        srv.stop()

    def _post(self, url, payload, raw=False, timeout=60):
        data = (payload if raw
                else json.dumps(payload).encode())
        ctype = ("application/octet-stream" if raw
                 else "application/json")
        req = urllib.request.Request(url, data=data,
                                     headers={"Content-Type": ctype})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            body = r.read()
            return r.status, r.headers.get("Content-Type"), body

    def test_prefill_admit_over_http(self, lm, ui):
        cfg, params = lm
        rng = np.random.default_rng(30)
        prompt = rng.integers(0, 50, (14,)).tolist()
        status, ctype, blob = self._post(
            ui.url + "/lm/prefill",
            {"prompt_ids": prompt, "max_new_tokens": 6})
        assert status == 200 and ctype == "application/octet-stream"
        status, _, body = self._post(ui.url + "/lm/admit_pages", blob,
                                     raw=True)
        assert status == 200
        assert json.loads(body)["ids"] == _want(cfg, params, prompt, 6)

    def test_corrupt_admit_is_422(self, ui):
        rng = np.random.default_rng(31)
        prompt = rng.integers(0, 50, (14,)).tolist()
        _, _, blob = self._post(
            ui.url + "/lm/prefill",
            {"prompt_ids": prompt, "max_new_tokens": 4})
        bad = bytearray(blob)
        bad[-3] ^= 0x40
        with pytest.raises(urllib.error.HTTPError) as ei:
            self._post(ui.url + "/lm/admit_pages", bytes(bad), raw=True)
        assert ei.value.code == 422
        payload = json.loads(ei.value.read())
        assert payload["kind"] == "page_ship"

    def test_session_id_on_single_serve(self, lm, ui):
        """Satellite: the same `session_id` payload shape works on a
        bare single-replica serve — counted into affinity hits."""
        rng = np.random.default_rng(32)
        prompt = rng.integers(0, 50, (6,)).tolist()
        for _ in range(3):
            self._post(ui.url + "/lm/generate",
                       {"prompt_ids": prompt, "max_new_tokens": 3,
                        "session_id": "single-serve-chat"})
        with urllib.request.urlopen(ui.url + "/serving/stats",
                                    timeout=30) as r:
            stats = json.loads(r.read())["lm"]
        assert stats["session_queries"] >= 3
        assert stats["session_affinity_hits"] >= 2
        assert stats["ttft"]["count"] >= 3

    def test_bad_session_id_is_400(self, ui):
        with pytest.raises(urllib.error.HTTPError) as ei:
            self._post(ui.url + "/lm/generate",
                       {"prompt_ids": [1, 2], "max_new_tokens": 2,
                        "session_id": {"not": "scalar"}})
        assert ei.value.code == 400

    def test_prefill_on_unshipped_pool_is_typed_422(self, lm):
        """A worker that cannot ship answers the TYPED 422 (kind
        page_ship) — machine-distinguishable from 'this request is bad
        everywhere', so the router recomputes instead of propagating."""
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = lm
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, slots=SLOTS, page_size=PS,
                     prefill_chunk=CHUNK)     # ship=False
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._post(srv.url + "/lm/prefill",
                           {"prompt_ids": [1, 2, 3],
                            "max_new_tokens": 2})
            assert ei.value.code == 422
            assert json.loads(ei.value.read())["kind"] == "page_ship"
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Router units + CLI surface (no device traffic)


class TestRoleUnits:
    def test_pick_filters_by_role(self):
        from deeplearning4j_tpu.serving.fleet import Replica

        router = FleetRouter()
        p = router.attach(Replica("p0", "http://127.0.0.1:1",
                                  role="prefill"))
        d = router.attach(Replica("d0", "http://127.0.0.1:2",
                                  role="decode"))
        b = router.attach(Replica("b0", "http://127.0.0.1:3"))
        try:
            assert router._pick(roles=("prefill",)) is p
            assert router._pick(roles=("decode",)) is d
            got = router._pick(roles=("decode", "both"))
            assert got in (d, b)
            assert router._pick(roles=("prefill",),
                                excluded=frozenset({"p0"})) is None
            assert b.role == "both"
        finally:
            router.stop()

    def test_bad_role_is_typed(self):
        from deeplearning4j_tpu.serving.fleet import Replica

        with pytest.raises(ValueError, match="role"):
            Replica("x", "http://127.0.0.1:1", role="chewer")

    def test_session_key_beats_prefix_key(self):
        router = FleetRouter()
        try:
            assert router._lm_affinity_key([1, 2, 3], "abc") == (
                "session:abc")
            assert router._lm_affinity_key(list(range(20)), None) == (
                ",".join(map(str, range(router.affinity_prefix_tokens))))
        finally:
            router.stop()

    def test_launcher_roles_and_lm_command(self, tmp_path):
        from deeplearning4j_tpu.runtime.launcher import (
            FleetProcessLauncher,
            replica_serve_command,
        )

        launcher = FleetProcessLauncher(
            None, n_replicas=3, lm_dir="lm-out", lm_slots=4,
            lm_page_size=16, prefill_chunk=8, lm_ship=True,
            roles=["prefill", "decode", "decode"])
        cmd = launcher.command(0)
        for flag, val in [("-lm", "lm-out"), ("-lm-slots", "4"),
                          ("-page-size", "16"), ("-prefill-chunk", "8")]:
            assert cmd[cmd.index(flag) + 1] == val
        assert "-lm-ship" in cmd and "-model" not in cmd
        assert [launcher.role(i) for i in range(3)] == [
            "prefill", "decode", "decode"]
        with pytest.raises(ValueError, match="neither"):
            replica_serve_command(None)
        with pytest.raises(ValueError, match="roles"):
            FleetProcessLauncher(None, n_replicas=2, lm_dir="x",
                                 roles=["prefill"]).role(0)

    def test_workerspec_role_reaches_replica(self):
        from deeplearning4j_tpu.serving.procfleet import WorkerSpec

        spec = WorkerSpec(name="w0", url="http://127.0.0.1:1",
                          role="prefill")
        assert spec.role == "prefill"
        assert WorkerSpec(name="w1", url="u").role == "both"


class TestCLISurface:
    def test_parser_accepts_disagg_flags(self):
        from deeplearning4j_tpu.cli import build_parser

        args = build_parser().parse_args(
            ["serve-fleet", "-lm", "lm-out", "-prefill-workers", "1",
             "-decode-workers", "2", "-disagg-min-prompt", "24",
             "-page-size", "8", "-prefill-chunk", "4"])
        assert args.prefill_workers == 1 and args.decode_workers == 2
        assert args.disagg_min_prompt == 24
        args = build_parser().parse_args(
            ["serve", "-lm", "lm-out", "-lm-ship"])
        assert args.lm_ship

    def test_role_split_validation(self):
        from deeplearning4j_tpu.cli import cmd_serve_fleet, build_parser

        args = build_parser().parse_args(
            ["serve-fleet", "-model", "m", "-prefill-workers", "1"])
        with pytest.raises(SystemExit, match="-lm"):
            cmd_serve_fleet(args)
        args = build_parser().parse_args(
            ["serve-fleet", "-lm", "x", "-prefill-workers", "1"])
        with pytest.raises(SystemExit, match="decode-workers"):
            cmd_serve_fleet(args)
        args = build_parser().parse_args(["serve-fleet"])
        with pytest.raises(SystemExit, match="-model and/or -lm"):
            cmd_serve_fleet(args)

    def test_serve_has_one_kv_mode_and_no_flag_for_it(self, capsys):
        from deeplearning4j_tpu.cli import build_parser

        with pytest.raises(SystemExit):
            build_parser().parse_args(
                ["serve", "-lm", "x", "-lm-kv", "dense", "-lm-ship"])
        assert "-lm-kv" in capsys.readouterr().err
        args = build_parser().parse_args(["serve", "-lm", "x", "-lm-ship"])
        assert args.lm_ship and not hasattr(args, "lm_kv")
