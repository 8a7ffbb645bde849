"""The LM worker's round measured from inside (ISSUE-24): the phase clock
and its host-plane spans, the round counters of `ServingMetrics`, the
request's `prefill` span, warm-up by program, and the mesh trainer's
`params=` and `train:hybrid` key.
"""

import glob
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.obs import MetricsRegistry, TraceRecorder
from deeplearning4j_tpu.obs.trace import PhaseClock
from deeplearning4j_tpu.serving import ContinuousLMServer
from deeplearning4j_tpu.serving.metrics import (
    FEED_KINDS,
    ROUND_PHASES,
    ServingMetrics,
)

PAGE, CHUNK = 4, 4


def _lm(max_len=48):
    from deeplearning4j_tpu.parallel import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=max_len)
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def served():
    """One warmed paged server that has finished three requests, with what
    its stats read before and after them."""
    cfg, params = _lm()
    tracer = TraceRecorder()
    srv = ContinuousLMServer(cfg, params, slots=2,
                             page_size=PAGE, prefill_chunk=CHUNK,
                             tracer=tracer)
    srv.warmup()
    srv.generate([40, 41, 42], 2)       # the ledger appears with a round
    before = srv.stats()
    prompts = [list(range(1, 12)), [7, 8, 9], list(range(20, 30))]
    new = [5, 6, 3]
    t0 = time.perf_counter()
    outs = [srv.generate(p, n) for p, n in zip(prompts, new)]
    wall = time.perf_counter() - t0
    after = srv.stats()
    yield {"srv": srv, "before": before, "after": after, "tracer": tracer,
           "prompts": prompts, "new": new, "outs": outs, "wall": wall}
    srv.stop()


def _delta(served, *path):
    def at(stats):
        for key in path:
            stats = stats.get(key, {})
        return stats
    b = at(served["before"]) or 0
    return at(served["after"]) - b


# ---- the phase clock -------------------------------------------------------


def test_phase_clock_partitions_the_wall_time():
    """`a` sleeps 20 ms twice and `b` 2 ms once: on a host loaded by six
    test workers a 2 ms sleep has lasted 7.5 ms, more than two of them
    (a whole run of PR 38), so equal sleeps do not order the phases."""
    clock = PhaseClock("t:")
    t0 = time.perf_counter()
    for name in ("a", "b", "a", "c"):
        clock.to(name)
        time.sleep(0.02 if name == "a" else 0.002)
    clock.to(None)
    wall = time.perf_counter() - t0
    seconds = clock.take()
    assert set(seconds) == {"a", "b", "c"}
    assert seconds["a"] > seconds["b"] > 0
    assert sum(seconds.values()) == pytest.approx(wall, abs=2e-3)
    assert clock.take() == {}               # taken: starts anew


def test_the_helper_costs_little_with_no_profiler_session():
    """Always on: eight stamps, eight inert annotations and one
    `record_round` a round, against rounds of tens of milliseconds."""
    clock, metrics = PhaseClock("lm:"), ServingMetrics()
    fed = {"prefill": 3, "decode": 1, "draft": 0}
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        for phase in ROUND_PHASES:
            clock.to(phase)
        metrics.record_round(clock.take(), 8, 16, fed, 12)
    per_round = (time.perf_counter() - t0) / n
    assert per_round < 500e-6, per_round     # budget 50 us on a quiet core
    assert metrics.snapshot()["rounds"]["count"] == n


# ---- the round counters ----------------------------------------------------


@pytest.mark.parametrize("phase", ROUND_PHASES)
def test_every_phase_is_counted(served, phase):
    assert served["after"]["rounds"]["seconds"][phase] > 0.0


def test_phases_and_idle_sum_to_the_workers_wall_time(served):
    phases = sum(_delta(served, "rounds", "seconds", p)
                 for p in ROUND_PHASES)
    idle = _delta(served, "rounds", "idle_s")
    # the two snapshots lie a little outside the three requests, and the
    # open phase is not counted until it ends: within a round or two
    assert phases + idle == pytest.approx(served["wall"], abs=0.15)
    assert phases > 0.5 * served["wall"]


def test_round_host_time_is_every_phase_but_sync():
    metrics = ServingMetrics()
    seconds = dict.fromkeys(ROUND_PHASES, 0.001)
    seconds["sync"] = 0.5
    metrics.record_round(seconds, 1, 4, {"decode": 2}, 3)
    metrics.record_phase_seconds({"idle": 0.25, "admit": 0.001})
    rounds = metrics.snapshot()["rounds"]
    assert rounds["host_ms"]["mean"] == pytest.approx(7.0)
    assert rounds["seconds"]["sync"] == pytest.approx(0.5)
    assert rounds["seconds"]["admit"] == pytest.approx(0.002)
    assert rounds["idle_s"] == pytest.approx(0.25)


def test_rounds_by_width_sum_to_the_dispatches(served):
    by_width = {int(w): _delta(served, "rounds", "by_width", w)
                for w in served["after"]["rounds"]["by_width"]}
    assert set(by_width) == {1, CHUNK}
    assert by_width[1] > 0 and by_width[CHUNK] > 0
    assert sum(by_width.values()) == _delta(served, "dispatches")
    assert _delta(served, "rounds", "count") == _delta(served, "dispatches")
    # capacity: lanes x width of each round
    assert _delta(served, "rounds", "feed_capacity") == 2 * sum(
        w * n for w, n in by_width.items())


def test_fed_tokens_are_the_prompts_and_the_tokens_fed_back(served):
    """Sequential requests with nothing shared: every prompt token is fed
    once, and every generated token but a request's last is fed back."""
    assert set(served["after"]["rounds"]["fed_tokens"]) == set(FEED_KINDS)
    assert _delta(served, "rounds", "fed_tokens", "prefill") == sum(
        map(len, served["prompts"]))
    assert _delta(served, "rounds", "fed_tokens", "decode") == sum(
        n - 1 for n in served["new"])
    assert _delta(served, "rounds", "fed_tokens", "draft") == 0
    for out, prompt, n in zip(served["outs"], served["prompts"],
                              served["new"]):
        assert len(out) == len(prompt) + n


def test_live_pages_against_a_hand_count(served):
    """One lane at a time: a round that feeds f tokens at position pos
    reads ceil((pos + f) / page) pages."""
    want = 0
    for prompt, n in zip(served["prompts"], served["new"]):
        pos, left = 0, len(prompt)
        while left:                       # wide while a full chunk is left
            f = min(left, CHUNK) if left >= CHUNK else 1
            want += -(-(pos + f) // PAGE)
            pos, left = pos + f, left - f
        for _ in range(n - 1):            # decode rounds feed one token
            want += -(-(pos + 1) // PAGE)
            pos += 1
    assert _delta(served, "rounds", "live_pages") == want


def _walk_server(kind):
    """A toy server whose lanes' histories pass a block of the kernel's
    walk: the classic layer (the full-heads kernel), or grouped heads under
    a block mask of 4 (the grouped kernel, block rounds)."""
    from deeplearning4j_tpu.parallel import transformer as tfm

    if kind == "full":
        cfg, params = _lm(max_len=272)
        return ContinuousLMServer(cfg, params, slots=3, page_size=4,
                                  prefill_chunk=4)
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=48,
        max_len=320, dtype="float32", norm="rms", mlp="swiglu",
        head_width=8, kv_heads=2, rope=tfm.YarnRope(theta=1e6),
        block_length=4, mask_token=63)
    return ContinuousLMServer(cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)),
                              slots=3, page_size=8, prefill_chunk=16,
                              denoise_steps=2)


@pytest.mark.parametrize("kind", ["full", "grouped"])
def test_walk_blocks_against_a_count_of_what_was_dispatched(kind):
    """`walk_blocks`: over the lanes a round feeds, `ceil(pages / gp)` (a
    query block: one at these widths), `gp` the kernel's own pages a block
    at the server's shapes; counted here from the positions and feeds the
    step programs were handed, three lanes at once."""
    from deeplearning4j_tpu.parallel import paged_kernel as pk
    from deeplearning4j_tpu.parallel.generation import pool_layout

    srv = _walk_server(kind)
    try:
        srv.warmup()
        ps, mp = srv.page_size, srv.max_pages
        gp = pk._pages_per_block(ps, pool_layout(srv.cfg).row, 4, mp, True)
        assert 1 < gp < mp
        seen, step = [], srv._step

        def recorded(params, *args):
            seen.append((np.array(args[3]), np.array(args[4])))
            return step(params, *args)

        srv._step = recorded
        before = srv.stats().get("rounds", {})
        rng = np.random.default_rng(5)
        lengths = [ps * gp + 9, 7, 2 * ps * gp - 3]     # 2, 1 and 2 blocks
        with ThreadPoolExecutor(len(lengths)) as pool:
            for out in pool.map(
                    lambda p: srv.generate(p, 8, timeout=600),
                    [[int(t) for t in rng.integers(0, 48, n)]
                     for n in lengths]):
                assert len(out) >= 8
        rounds = srv.stats()["rounds"]
    finally:
        srv.stop()
    pages = [-(-(int(p) + int(f)) // ps)
             for pos, nf in seen for p, f in zip(pos, nf) if f]
    assert len(seen) == rounds["count"] - before.get("count", 0)
    assert sum(pages) == rounds["live_pages"] - before.get("live_pages", 0)
    want = sum(-(-n // gp) for n in pages)
    assert sum(pages) / gp < want < sum(pages)      # some tails, some wholes
    assert rounds["walk_blocks"] - before.get("walk_blocks", 0) == want


def test_the_new_series_are_on_metrics_with_their_labels(served):
    registry = MetricsRegistry()
    served["srv"].metrics.register_into(registry, plane="lm")
    text = registry.exposition()
    for phase in ROUND_PHASES:
        assert (f'serving_lm_round_seconds_total{{phase="{phase}",'
                f'plane="lm"}}') in text
    for line in (f'serving_lm_rounds_total{{plane="lm",width="{CHUNK}"}}',
                 'serving_lm_fed_tokens_total{kind="prefill",plane="lm"}',
                 'serving_lm_feed_capacity_total{plane="lm"}',
                 'serving_lm_live_pages_total{plane="lm"}',
                 'serving_lm_walk_blocks_total{plane="lm"}',
                 'serving_lm_idle_seconds_total{plane="lm"}',
                 'serving_lm_round_host_seconds_count{plane="lm"}'):
        assert line in text, line


# ---- the request's spans ---------------------------------------------------


def test_prefill_lies_inside_decode_and_ends_at_the_first_token(served):
    traces = served["tracer"].recent()[-3:]
    assert len(traces) == 3
    for tr, prompt in zip(traces, served["prompts"]):
        by = {s["name"]: s for s in tr["spans"]}
        assert list(by) == ["queue_wait", "decode", "prefill"] or \
            list(by) == ["queue_wait", "prefill", "decode"]
        wait, prefill, decode = by["queue_wait"], by["prefill"], by["decode"]
        assert prefill["t0_s"] == decode["t0_s"]
        assert prefill["dur_s"] <= decode["dur_s"]
        # queue_wait ends where prefill starts: together, enqueue to the
        # first committed token
        assert wait["t0_s"] + wait["dur_s"] == pytest.approx(
            prefill["t0_s"], abs=1e-9)
        attrs = prefill["attrs"]
        assert attrs["fed_tokens"] == len(prompt)
        wide = len(prompt) // CHUNK
        assert attrs["wide_rounds"] == wide
        assert attrs["rounds"] == wide + len(prompt) % CHUNK
        # the readers of the two older spans find what they found
        assert decode["attrs"]["prompt_tokens"] == len(prompt)


def test_queue_wait_plus_prefill_is_the_time_to_the_first_token():
    cfg, params = _lm()
    tracer = TraceRecorder()
    srv = ContinuousLMServer(cfg, params, slots=1,
                             page_size=PAGE, prefill_chunk=CHUNK,
                             tracer=tracer)
    try:
        srv.warmup()
        srv.generate([1, 2, 3, 4, 5], 3)
        by = {s["name"]: s["dur_s"] for s in tracer.recent()[-1]["spans"]}
        ttft = srv.metrics.ttft_hist.sum          # t_first - enqueued
        assert by["queue_wait"] + by["prefill"] == pytest.approx(ttft,
                                                                 abs=1e-9)
    finally:
        srv.stop()


# ---- warm-up by program ----------------------------------------------------


def test_warmup_is_reported_by_program(served):
    warm = served["after"]["warmup"]
    assert set(warm["programs"]) == {"lm:paged[w1]", f"lm:paged[w{CHUNK}]",
                                     "lm:page_copy"}
    assert len(warm["programs"]) == served["srv"].compiled_programs()
    assert all(s > 0 for s in warm["programs"].values())
    assert warm["total_s"] >= sum(warm["programs"].values())
    assert warm["compiles"] >= 0


# ---- the host plane of a profiler trace ------------------------------------


def test_a_profiler_capture_holds_the_phases_and_the_program_key(tmp_path):
    from jax.profiler import ProfileData

    cfg, params = _lm()
    srv = ContinuousLMServer(cfg, params, slots=2,
                             page_size=PAGE, prefill_chunk=CHUNK)
    try:
        srv.warmup()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            srv.generate([1, 2, 3], 4)
            time.sleep(0.12)                # an idle wait, then a yield
            srv.generate([4, 5, 6], 2)
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names |= {e.name for e in line.events
                          if e.name.startswith("lm:")}
    assert {f"lm:{p}" for p in ROUND_PHASES} <= names, names
    assert "lm:paged[w1]" in names and "lm:idle" in names


# ---- the mesh trainer ------------------------------------------------------


def test_hybrid_trainer_trains_from_the_weights_given():
    from deeplearning4j_tpu.obs.compilewatch import compile_watcher
    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import HybridParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    cfg = tfm.TransformerConfig(vocab_size=41, d_model=16, n_heads=4,
                                n_layers=2, d_ff=32, max_len=16)
    mesh = make_mesh((1, 2, 2), ("data", "seq", "model"),
                     devices=jax.devices()[:4])
    given = jax.tree_util.tree_map(
        lambda a: a + 0.01, tfm.init_params(cfg, jax.random.PRNGKey(7)))
    rng = np.random.default_rng(5)
    tok = rng.integers(0, cfg.vocab_size, (4, 8))
    tgt = rng.integers(0, cfg.vocab_size, (4, 8))

    a = HybridParallelTrainer(cfg, mesh, lr=0.01, seed=3, updater="adam",
                              params=given)
    for got, want in zip(jax.tree_util.tree_leaves(a.params),
                         jax.tree_util.tree_leaves(given)):
        assert got.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # placed as the trainer's own weights are
    own = HybridParallelTrainer(cfg, mesh, lr=0.01, seed=3, updater="adam")
    assert (jax.tree_util.tree_map(lambda x: x.sharding, a.params)
            == jax.tree_util.tree_map(lambda x: x.sharding, own.params))
    # the seed plays no part once weights are given; another seed, same run
    b = HybridParallelTrainer(cfg, mesh, lr=0.01, seed=99, updater="adam",
                              params=given)
    before = compile_watcher().counts().get("train:hybrid", 0)
    la = [a.fit_batch(tok, tgt) for _ in range(3)]
    lb = [b.fit_batch(tok, tgt) for _ in range(3)]
    assert la == lb and la[-1] < la[0]
    assert float(own.fit_batch(tok, tgt)) != la[0]
    # the step is dispatched under its key: its compiles are counted there
    assert compile_watcher().counts().get("train:hybrid", 0) > before
