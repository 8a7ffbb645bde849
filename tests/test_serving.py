"""Serving subsystem tests (ISSUE-3 acceptance surface).

Covers: dynamic micro-batching correctness under concurrency (byte-
identical to sequential single-request calls, with real coalescing),
shape-bucketed compilation with the warmup API and the compile-count
guard under a mixed batch-size/length request storm (via jax.monitoring,
same pattern as tests/test_fused_driver.py), continuous slot-based LM
decode (greedy parity with `generate()`, mid-flight joins, slot reuse,
per-request seeded sampling), the `/lm/generate` limit validation, the
evaluate() tail-batch single-program fix, and the serving HTTP surface.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models import MultiLayerNetwork, iris_mlp
from deeplearning4j_tpu.serving import (
    BucketLadder,
    ContinuousLMServer,
    MicroBatcher,
    ServingEngine,
    pow2_length_buckets,
)

pytestmark = pytest.mark.serving


def _mlp():
    return MultiLayerNetwork(iris_mlp()).init()


def _requests(n, rows=1, feats=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(rows, feats)).astype(np.float32)
            for _ in range(n)]


class TestBucketLadder:
    def test_batch_bucket_rounds_up(self):
        lad = BucketLadder((1, 8, 32))
        assert [lad.batch_bucket(n) for n in (1, 2, 8, 9, 32)] == \
            [1, 8, 8, 32, 32]

    def test_oversize_and_invalid_raise(self):
        lad = BucketLadder((1, 8))
        with pytest.raises(ValueError, match="largest bucket"):
            lad.batch_bucket(9)
        with pytest.raises(ValueError):
            lad.batch_bucket(0)
        with pytest.raises(ValueError):
            BucketLadder(())
        with pytest.raises(ValueError):
            BucketLadder((0, 4))

    def test_pad_rows_zero_pads_to_bucket(self):
        lad = BucketLadder((1, 8))
        x = np.ones((3, 4), np.float32)
        padded, n = lad.pad_rows(x)
        assert padded.shape == (8, 4) and n == 3
        np.testing.assert_array_equal(padded[3:], 0.0)
        same, n = lad.pad_rows(np.ones((8, 4), np.float32))
        assert same.shape == (8, 4) and n == 8

    def test_length_buckets_and_masked_padding(self):
        lad = BucketLadder((1, 8), pow2_length_buckets(32, min_len=4))
        assert lad.length_buckets == (4, 8, 16, 32)
        assert lad.length_bucket(5) == 8
        x = np.ones((2, 5, 3), np.float32)
        px, mask = lad.pad_length(x)
        assert px.shape == (2, 8, 3) and mask.shape == (2, 8)
        np.testing.assert_array_equal(mask[:, :5], 1.0)
        np.testing.assert_array_equal(mask[:, 5:], 0.0)
        np.testing.assert_array_equal(px[:, 5:], 0.0)

    def test_program_bound(self):
        assert BucketLadder((1, 8, 32)).program_bound == 3
        assert BucketLadder((1, 8), (16, 32)).program_bound == 4


class TestLatencyStats:
    def test_percentile_is_ceil_nearest_rank(self):
        from deeplearning4j_tpu.runtime.profiler import percentile

        assert percentile([1, 2, 3, 4, 5], 50) == 3   # true median,
        assert percentile(list(range(1, 14)), 50) == 7  # not round-half-even
        assert percentile([1, 2, 3, 4], 99) == 4
        assert percentile([7.0], 50) == 7.0
        with pytest.raises(ValueError):
            percentile([], 50)

    def test_recorder_summary_is_window_consistent(self):
        from deeplearning4j_tpu.runtime.profiler import LatencyRecorder

        rec = LatencyRecorder(window=4)
        for v in (1.0, 1.0, 1.0, 1.0, 9.0, 9.0, 9.0, 9.0):
            rec.record(v)
        s = rec.summary()
        assert s["count"] == 8 and s["window"] == 4
        # mean and percentiles agree on the same (post-shift) window
        assert s["mean_ms"] == 9000.0 and s["p50_ms"] == 9000.0


class TestMicroBatcher:
    def test_single_request_round_trip(self):
        calls = []

        def dispatch(x, mask, n):
            calls.append(x.shape)
            return x * 2.0

        b = MicroBatcher(dispatch, max_batch=8, max_wait_ms=1.0)
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        np.testing.assert_array_equal(b.submit(x), x * 2.0)
        b.stop()
        assert calls == [(2, 4)]

    def test_concurrent_requests_coalesce_and_match_sequential(self):
        """ISSUE-3 satellite: N client threads against the batcher get
        the outputs sequential single-request calls give, and at least
        one dispatch carries more than one request.  The claim is
        "batching does not change the answer", held to 2 ULP: a request
        alone and the same rows inside a padded bucket are different
        batch shapes, so different XLA programs, and XLA on a CPU does
        not promise them the same last bit."""
        net = _mlp()
        reqs = _requests(48)
        sequential = [np.asarray(net.output(x)) for x in reqs]
        engine = ServingEngine(net, ladder=BucketLadder((1, 8, 16)),
                               max_wait_ms=25.0)
        engine.warmup(np.zeros((4,), np.float32))
        results = [None] * len(reqs)
        n_clients = 12
        barrier = threading.Barrier(n_clients)

        def client(cid):
            barrier.wait()   # all submit at once -> real coalescing
            for i in range(cid, len(reqs), n_clients):
                results[i] = engine.predict_proba(reqs[i], timeout=60)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = engine.stats()
        engine.stop()
        for want, got in zip(sequential, results):
            np.testing.assert_array_max_ulp(got, want, maxulp=2)
        assert stats["max_batch_occupancy"] > 1
        assert stats["dispatches"] < len(reqs)  # actually coalesced

    def test_oversized_request_rejected(self):
        b = MicroBatcher(lambda x, m, n: x, max_batch=4)
        with pytest.raises(ValueError, match="max_batch"):
            b.submit(np.zeros((5, 2), np.float32))
        b.stop()

    def test_dispatch_error_propagates_and_batcher_survives(self):
        state = {"fail": True}

        def dispatch(x, mask, n):
            if state["fail"]:
                raise RuntimeError("boom")
            return x

        b = MicroBatcher(dispatch, max_batch=4, max_wait_ms=1.0)
        with pytest.raises(RuntimeError, match="boom"):
            b.submit(np.zeros((1, 2), np.float32))
        state["fail"] = False
        out = b.submit(np.ones((1, 2), np.float32))
        np.testing.assert_array_equal(out, 1.0)
        b.stop()

    def test_mixed_shapes_never_share_a_dispatch(self):
        shapes = []
        done = threading.Barrier(3)

        def dispatch(x, mask, n):
            shapes.append(x.shape)
            return x

        b = MicroBatcher(dispatch, max_batch=8, max_wait_ms=50.0)

        def client(width):
            done.wait()
            b.submit(np.zeros((1, width), np.float32), timeout=60)

        threads = [threading.Thread(target=client, args=(w,))
                   for w in (3, 3, 5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b.stop()
        assert sorted(s[1] for s in shapes) in ([3, 5], [3, 3, 5])
        for s in shapes:
            assert s[1] in (3, 5)


class TestShapeBucketedCompilation:
    def test_warmup_then_storm_compiles_nothing(self):
        """ISSUE-3 acceptance: a mixed batch-size request storm after
        warmup() triggers ZERO XLA compiles, and the program count stays
        pinned to the bucket-ladder size (jax.monitoring, the
        test_fused_driver pattern)."""
        import jax.monitoring

        net = _mlp()
        ladder = BucketLadder((1, 8, 16))
        engine = ServingEngine(net, ladder=ladder, max_wait_ms=1.0)
        assert engine.warmup(np.zeros((4,), np.float32)) == 3
        assert net.forward_program_count() == len(ladder.batch_buckets)

        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        rng = np.random.default_rng(1)
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            # every batch size from 1 to the ladder top, shuffled
            for n in rng.permutation(np.r_[1:17, 1:17]):
                engine.predict_proba(
                    rng.normal(size=(int(n), 4)).astype(np.float32),
                    timeout=60)
        finally:
            jax.monitoring.clear_event_listeners()
            engine.stop()
        assert compiles == []
        assert net.forward_program_count() == len(ladder.batch_buckets)
        assert engine.stats()["compiled_programs"] == 3

    def test_compile_guard_refuses_unbudgeted_shapes(self):
        net = _mlp()
        engine = ServingEngine(net, ladder=BucketLadder((1, 8)),
                               max_programs=1, max_wait_ms=1.0)
        engine.predict_proba(np.zeros((1, 4), np.float32), timeout=60)
        with pytest.raises(RuntimeError, match="compile-count guard"):
            try:
                engine.predict_proba(np.zeros((2, 4), np.float32),
                                     timeout=60)
            finally:
                engine.stop()

    def test_offtype_requests_reuse_the_warmed_programs(self):
        """Client dtype drift (float64 lists, int features) must not
        compile a second program set behind the guard's back: the
        engine casts every request to the one input_dtype warmup()
        compiled."""
        net = _mlp()
        engine = ServingEngine(net, ladder=BucketLadder((1, 8)),
                               max_wait_ms=1.0)
        engine.warmup(np.zeros((4,), np.float32))
        out = engine.predict_proba(np.random.default_rng(0).normal(
            size=(2, 4)), timeout=60)           # float64 in
        assert out.shape == (2, 3)
        out = engine.predict_proba([[1, 2, 3, 4]], timeout=60)  # int in
        engine.stop()
        assert out.shape == (1, 3)
        assert net.forward_program_count() == 2  # still just the ladder

    def test_input_dtype_none_bounds_programs_per_dtype(self):
        """With input_dtype=None (raw-dtype models) each client dtype
        owns its own ladder-sized program budget — a second dtype after
        a full warmup must serve, not trip the guard."""
        net = _mlp()
        engine = ServingEngine(net, ladder=BucketLadder((1, 8)),
                               max_wait_ms=1.0, input_dtype=None)
        engine.warmup(np.zeros((4,), np.float32))   # fills float32 slots
        out = engine.predict_proba(
            np.zeros((2, 4), np.float64), timeout=60)  # new dtype: OK
        stats = engine.stats()
        engine.stop()
        assert out.shape == (2, 3)
        assert stats["compiled_programs"] == 3  # 2 warmed f32 + 1 f64

    def test_timed_out_request_is_cancelled_from_queue(self):
        started = threading.Event()
        release = threading.Event()
        dispatched = []

        def slow_dispatch(x, mask, n):
            started.set()
            release.wait(30)
            dispatched.append(x.shape[0])
            return x

        b = MicroBatcher(slow_dispatch, max_batch=4, max_wait_ms=0.0)
        t = threading.Thread(
            target=lambda: b.submit(np.zeros((1, 2), np.float32)))
        t.start()                        # occupies the worker
        assert started.wait(10)
        with pytest.raises(TimeoutError):
            b.submit(np.ones((1, 2), np.float32), timeout=0.05)
        release.set()
        t.join(timeout=10)
        b.stop()
        # the timed-out request was removed, never dispatched as zombie
        assert dispatched == [1]

    def test_length_bucketed_sequences_match_direct_and_stay_bounded(self):
        """ISSUE-3 acceptance, mixed batch-size/LENGTH storm: sequence
        inputs pad T up the pow2 ladder with per-example masks (masked
        LSTM steps carry state exactly), bucketed serving returns the
        same outputs as direct unpadded calls, and after warmup the
        whole storm compiles NOTHING — programs stay pinned to
        |batch buckets| x |length buckets|."""
        import jax.monitoring

        from deeplearning4j_tpu.nn.conf import (
            GravesLSTMConf,
            MultiLayerConfiguration,
            NeuralNetConfiguration,
            RnnOutputLayerConf,
        )

        conf = MultiLayerConfiguration(
            conf=NeuralNetConfiguration(seed=1, learning_rate=0.05),
            layers=(GravesLSTMConf(n_in=3, n_out=8),
                    RnnOutputLayerConf(n_in=8, n_out=2)))
        net = MultiLayerNetwork(conf).init()
        ladder = BucketLadder((1, 4), pow2_length_buckets(16, min_len=4))
        engine = ServingEngine(net, ladder=ladder, max_wait_ms=1.0)
        assert engine.warmup(np.zeros((1, 5, 3), np.float32)) == 6  # 2x3
        assert net.forward_program_count() == ladder.program_bound

        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        rng = np.random.default_rng(2)
        storm = [(2, 3), (1, 5), (4, 11), (3, 16), (2, 7),
                 (1, 4), (4, 15), (2, 12)]
        xs = [rng.normal(size=(n, t, 3)).astype(np.float32)
              for n, t in storm]
        # reference outputs via direct unpadded calls — compiled OUTSIDE
        # the monitored window (each distinct raw shape is a program,
        # which is precisely the leak the engine's ladder prevents)
        direct = [np.asarray(net.output(x)) for x in xs]
        programs_after_warmup = ladder.program_bound  # engine-path shapes
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for x, want in zip(xs, direct):
                served = engine.predict_proba(x, timeout=60)
                assert served.shape == want.shape
                np.testing.assert_allclose(served, want, atol=1e-6)
        finally:
            jax.monitoring.clear_event_listeners()
            engine.stop()
        assert compiles == []   # the storm compiled nothing new
        assert engine.stats()["compiled_programs"] == programs_after_warmup


class TestEvaluateTailBatch:
    def test_tail_slice_reuses_the_one_program(self):
        """ISSUE-3 satellite: evaluate(batch_size=...) pads the ragged
        final slice instead of compiling a second tail-shape program,
        and the metrics are unchanged."""
        rng = np.random.default_rng(0)
        y_cls = rng.integers(0, 3, 37)
        x = rng.normal(0, 0.3, (37, 4)).astype(np.float32) + y_cls[:, None]
        y = np.eye(3, dtype=np.float32)[y_cls]
        net = _mlp()
        net.fit_batch(x[:32], y[:32])
        batched = net.evaluate(x, y, batch_size=8)   # 4 full + tail of 5
        assert net.forward_program_count() == 1      # ONE compiled shape
        whole = net.evaluate(x, y)
        assert batched.stats() == whole.stats()
        assert float(batched.f1()) == float(whole.f1())


def _lm(max_len=24):
    from deeplearning4j_tpu.parallel import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=max_len)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class TestContinuousLM:
    def test_concurrent_greedy_matches_generate(self):
        """Slot decode == whole-sequence generate(), token for token,
        for concurrent prompts of different lengths sharing the pool."""
        from deeplearning4j_tpu.parallel.generation import generate

        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=3)
        prompts = [[1, 2, 3], [5, 6], [7, 8, 9, 10], [4], [11, 12]]
        want = [np.asarray(generate(cfg, params,
                                    np.asarray([p], np.int32), 6))[0].tolist()
                for p in prompts]
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
        # 5 requests over 3 slots: slots were freed and reused, and at
        # least one step decoded multiple lanes at once
        assert stats["max_batch_occupancy"] > 1
        assert stats["tokens"] == 6 * len(prompts)

    def test_midflight_join_does_not_disturb_running_request(self):
        """A prompt admitted while another request is decoding must not
        change the running request's output (its slot restarts at
        position 0; stale KV beyond each slot's position is masked)."""
        from deeplearning4j_tpu.parallel.generation import generate

        cfg, params = _lm(max_len=32)
        srv = ContinuousLMServer(cfg, params, slots=2)
        long_p, short_p = [1, 2, 3, 4], [9, 8]
        want_long = np.asarray(generate(
            cfg, params, np.asarray([long_p], np.int32), 20))[0].tolist()
        want_short = np.asarray(generate(
            cfg, params, np.asarray([short_p], np.int32), 4))[0].tolist()
        out = {}

        def late_client():
            out["short"] = srv.generate(short_p, 4, timeout=120)

        t = threading.Thread(target=late_client)

        def early_client():
            out["long"] = srv.generate(long_p, 20, timeout=120)

        t0 = threading.Thread(target=early_client)
        t0.start()
        # join mid-flight: the long request is (very likely) decoding
        t.start()
        t0.join()
        t.join()
        srv.stop()
        assert out["long"] == want_long
        assert out["short"] == want_short

    def test_more_requests_than_slots_all_complete(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2)
        outs = [srv.generate([i + 1], 4, timeout=120) for i in range(5)]
        srv.stop()
        for i, ids in enumerate(outs):
            assert len(ids) == 5 and ids[0] == i + 1
            assert all(0 <= t < cfg.vocab_size for t in ids)

    def test_sampling_is_seeded_per_request(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2)
        a = srv.generate([1, 2], 5, temperature=0.9, seed=7, timeout=120)
        b = srv.generate([1, 2], 5, temperature=0.9, seed=7, timeout=120)
        c = srv.generate([1, 2], 5, temperature=0.9, seed=8, timeout=120)
        srv.stop()
        assert a == b
        assert all(0 <= t < cfg.vocab_size for t in a)
        assert len(c) == len(a)

    def test_validation(self):
        cfg, params = _lm(max_len=16)
        srv = ContinuousLMServer(cfg, params, slots=1)
        with pytest.raises(ValueError, match="max_len"):
            srv.generate([1] * 10, 10)
        with pytest.raises(ValueError, match="at least one"):
            srv.generate([], 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            srv.generate([1], 0)
        # out-of-vocab (or int32-overflowing) tokens must fail at
        # validation, not inside the shared decode worker where they
        # would take down co-travelling requests
        with pytest.raises(ValueError, match="vocab"):
            srv.generate([cfg.vocab_size], 2)
        with pytest.raises(ValueError, match="vocab"):
            srv.generate([2 ** 40], 2)
        with pytest.raises(ValueError):
            ContinuousLMServer(cfg, params, slots=0)
        srv.stop()

    def test_huge_seed_is_folded_not_fatal(self):
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=1)
        out = srv.generate([1, 2], 3, temperature=0.7, seed=2 ** 35 + 11,
                           timeout=120)
        srv.stop()
        assert len(out) == 5
        assert all(0 <= t < cfg.vocab_size for t in out)

    def test_server_survives_a_failed_dispatch(self):
        """A dispatch that blows up fails the in-flight requests but the
        server keeps serving — including rebuilding the donated KV
        buffers the failed step consumed."""
        cfg, params = _lm()
        srv = ContinuousLMServer(cfg, params, slots=2)
        assert srv.generate([1, 2], 3, timeout=120)  # healthy first
        real_step = srv._step
        calls = {"n": 0}

        def exploding(*a, **kw):
            calls["n"] += 1
            raise RuntimeError("injected device fault")

        srv._step = exploding
        with pytest.raises(RuntimeError, match="injected"):
            srv.generate([3, 4], 3, timeout=120)
        srv._step = real_step
        out = srv.generate([1, 2], 3, timeout=120)  # still serves
        srv.stop()
        assert calls["n"] >= 1
        assert len(out) == 5


# ---------------------------------------------------------------------------
# HTTP surface

def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


class TestServingEndpoints:
    def test_model_predict_and_stats(self):
        from deeplearning4j_tpu.ui.server import UiServer

        net = _mlp()
        srv = UiServer(port=0).serve_model(
            net, max_batch=8, ladder=BucketLadder((1, 8)),
            warmup_example=np.zeros((4,), np.float32)).start()
        try:
            x = _requests(1, rows=3)[0]
            out = _post(srv.url + "/model/predict",
                        {"features": x.tolist()})
            want = np.asarray(net.output(x))
            assert out["predictions"] == want.argmax(-1).tolist()
            np.testing.assert_allclose(np.asarray(out["outputs"]), want,
                                       atol=1e-6)
            stats = _get(srv.url + "/serving/stats")
            assert stats["classifier"]["requests"] == 1
            assert stats["classifier"]["compiled_programs"] == 2
            assert "latency" in stats["classifier"]
            assert stats["lm"] is None
        finally:
            srv.stop()

    def test_model_predict_without_model_400(self):
        from deeplearning4j_tpu.ui.server import UiServer

        srv = UiServer(port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/model/predict", {"features": [[1, 2]]})
            assert exc.value.code == 400
        finally:
            srv.stop()

    def test_lm_generate_oversized_request_is_400_with_limit(self):
        """ISSUE-3 satellite: prompt_ids + max_new_tokens past
        cfg.max_len must be a 400 naming the limit — not a silently
        clipped/wedged dynamic_update_slice."""
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm(max_len=16)
        srv = UiServer(port=0).serve_lm(cfg, params).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/lm/generate",
                      {"prompt_ids": list(range(1, 11)),
                       "max_new_tokens": 10})
            assert exc.value.code == 400
            body = json.loads(exc.value.read())
            assert body["max_len"] == 16
            assert "max_len" in body["error"]
            # bad knob types are still client errors
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": None})
            assert exc.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(srv.url + "/lm/generate",
                      {"prompt_ids": [1, 2], "max_new_tokens": 0})
            assert exc.value.code == 400
            # out-of-vocab ids 400 on EVERY decode path — the top-k leg
            # would otherwise index-clamp them into a garbage 200
            for extra in ({}, {"temperature": 1.0, "top_k": 3},
                          {"beam_size": 2}):
                with pytest.raises(urllib.error.HTTPError) as exc:
                    _post(srv.url + "/lm/generate",
                          {"prompt_ids": [999], "max_new_tokens": 2,
                           **extra})
                assert exc.value.code == 400
                assert "vocab" in json.loads(exc.value.read())["error"]
            # knob ranges are validated up front on every path too —
            # top_p=2.0 must not be silently dropped by the slot pool
            for bad in ({"top_p": 2.0, "temperature": 0.5},
                        {"top_k": -1, "temperature": 0.5},
                        {"temperature": -0.1}):
                with pytest.raises(urllib.error.HTTPError) as exc:
                    _post(srv.url + "/lm/generate",
                          {"prompt_ids": [1, 2], "max_new_tokens": 2,
                           **bad})
                assert exc.value.code == 400
        finally:
            srv.stop()

    def test_cli_serve_boots_warms_and_serves(self):
        """`dl4j serve -model zoo:iris-mlp -warmup` boots the batched
        serving stack, answers /model/predict, and exits cleanly after
        -serve-seconds."""
        import contextlib
        import io
        import re
        import time

        from deeplearning4j_tpu.cli import main as cli_main

        out = io.StringIO()
        rc = {}

        def run():
            with contextlib.redirect_stdout(out):
                rc["rc"] = cli_main(
                    ["serve", "-model", "zoo:iris-mlp", "-port", "0",
                     "-warmup", "-buckets", "1,8",
                     "-serve-seconds", "6"])

        t = threading.Thread(target=run)
        t.start()
        url = None
        for _ in range(100):
            m = re.search(r"Serving on (http://\S+)", out.getvalue())
            if m:
                url = m.group(1)
                break
            time.sleep(0.1)
        assert url, out.getvalue()
        res = _post(url + "/model/predict",
                    {"features": [[0.1, 0.2, 0.3, 0.4]]})
        assert len(res["predictions"]) == 1
        stats = _get(url + "/serving/stats")
        assert stats["classifier"]["compiled_programs"] == 2  # warmed
        t.join(timeout=30)
        assert rc.get("rc") == 0
        assert "pre-compiled 2 bucket shapes" in out.getvalue()

    def test_lm_generate_routes_through_continuous_pool(self):
        from deeplearning4j_tpu.parallel.generation import generate
        from deeplearning4j_tpu.ui.server import UiServer

        cfg, params = _lm()
        srv = UiServer(port=0).serve_lm(cfg, params, slots=2).start()
        try:
            out = _post(srv.url + "/lm/generate",
                        {"prompt_ids": [1, 2, 3], "max_new_tokens": 4})
            want = np.asarray(generate(
                cfg, params, np.asarray([[1, 2, 3]], np.int32),
                4))[0].tolist()
            assert out["ids"] == want
            stats = _get(srv.url + "/serving/stats")
            assert stats["lm"]["requests"] == 1
            assert stats["lm"]["slots"] == 2
            assert stats["lm"]["tokens"] == 4
            # top-k request: legacy whole-sequence path, still serves
            sampled = _post(srv.url + "/lm/generate",
                            {"prompt_ids": [1, 2], "max_new_tokens": 3,
                             "temperature": 1.0, "top_k": 5})
            assert len(sampled["ids"]) == 5
        finally:
            srv.stop()
