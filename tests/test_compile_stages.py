"""The build account (ISSUE-40): every program the process builds is
accounted by key and by stage (`trace`, `lower`, `backend`, `cache_load`,
cache hits and misses), nested stages count once, nothing is unkeyed, and
the LM server's warm-up says what each program's seconds were.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring

from deeplearning4j_tpu.obs import MetricsRegistry, new_request_id
from deeplearning4j_tpu.obs.compilewatch import (
    STAGES,
    TRACE_EVENT,
    compile_scope,
    compile_watcher,
    over_keys,
)

pytestmark = pytest.mark.obs

BUILD = ("trace", "lower", "backend")


def _key() -> str:
    return f"test:{new_request_id()}"       # unique per run


def _fresh(salt: float):
    """A jitted function no other test builds."""
    return jax.jit(lambda x: jnp.tanh(x) * salt + 1.0)


@pytest.fixture(scope="module")
def scoped_build():
    """One jit built under a scope, then called again: the watcher's
    readings of each call."""
    w, key = compile_watcher(), _key()
    f, x = _fresh(1.234567), np.zeros((3, 5), np.float32)
    t0 = time.perf_counter()
    with compile_scope(key):
        jax.block_until_ready(f(x))
    t1 = time.perf_counter()
    with compile_scope(key):
        jax.block_until_ready(f(x))
    t2 = time.perf_counter()
    return {"key": key, "wall": t1 - t0,
            "first": w.stage_seconds(t0, t1).get(key, {}),
            "second": w.stage_seconds(t1, t2).get(key, {})}


@pytest.mark.parametrize("stage", BUILD)
def test_a_scoped_jit_is_accounted_by_stage_once(scoped_build, stage):
    assert scoped_build["first"][stage] > 0.0
    assert stage not in scoped_build["second"]
    assert sum(scoped_build["first"][s] for s in BUILD) <= (
        scoped_build["wall"])


def test_an_unscoped_jit_reads_under_its_function_never_unkeyed():
    w = compile_watcher()

    def only_this_test_builds_me(x):
        return x * 2.5 - 0.125

    t0 = time.perf_counter()
    jax.jit(only_this_test_builds_me)(np.zeros((2, 3), np.float32))
    built = w.stage_seconds(since=t0)
    # tracing names the function, lowering and the backend its module
    assert set(built["fn:only_this_test_builds_me"]) == set(BUILD)
    assert "" not in built and "" not in w.counts()
    assert w.counts()["fn:only_this_test_builds_me"] == 1


def test_nested_jits_count_once_and_under_the_outermost():
    """Three jitted functions traced inside a fourth's trace each fire
    their own trace event inside its interval: the key's seconds are the
    union, and the inner names make no key."""
    w = compile_watcher()
    inner = [jax.jit(lambda x, k=k: jnp.sin(x) * (k + 0.5), inline=False)
             for k in range(3)]

    def outer_of_three(x):
        for f in inner:
            x = f(x)
        return x

    heard = []

    def listener(event, duration, **kw):
        if event == TRACE_EVENT:
            heard.append(duration)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        t0 = time.perf_counter()
        jax.block_until_ready(
            jax.jit(outer_of_three)(np.zeros((4,), np.float32)))
        wall = time.perf_counter() - t0
    finally:
        monitoring.unregister_event_duration_listener(listener)
    built = w.stage_seconds(since=t0)
    assert len(heard) >= 4                  # the nested events did fire
    assert set(built) == {"fn:outer_of_three"}
    got = built["fn:outer_of_three"]
    assert 0.0 < got["trace"] <= max(heard) + 1e-9
    assert got["trace"] + got["lower"] + got["backend"] <= wall


def test_a_build_inside_a_trace_is_taken_out_of_it():
    """What a traced function computes at compile time is built there and
    then, inside the trace's interval: its stages are its own, and the
    stages still sum to no more than the wall time."""
    w, key = compile_watcher(), _key()

    def f(x):
        with jax.ensure_compile_time_eval():            # built now
            table = jnp.cumsum(jnp.arange(7.0) * 1.75)
        return x + table[3]

    t0 = time.perf_counter()
    with compile_scope(key):
        jax.block_until_ready(jax.jit(f)(np.zeros((5,), np.float32)))
    wall = time.perf_counter() - t0
    got = w.stage_seconds(since=t0)[key]
    assert w.counts()[key] >= 2             # the table's programs and f
    assert sum(got[s] for s in BUILD) <= wall


def test_persistent_cache_miss_then_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    w, key = compile_watcher(), _key()
    old = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        def build():
            t0 = time.perf_counter()
            with compile_scope(key):
                jax.block_until_ready(jax.jit(
                    lambda x: jnp.tanh(x) * 7.654321)(
                        np.zeros((7,), np.float32)))
            return (w.stage_seconds(since=t0).get(key, {}),
                    w.cache_results(since=t0).get(key))

        first, cache = build()
        if cache is None:
            pytest.skip("this backend does not use the persistent cache")
        assert cache == {"hit": 0, "miss": 1}
        assert "cache_load" not in first and first["backend"] > 0.0
        jax.clear_caches()
        second, cache = build()
        assert cache == {"hit": 1, "miss": 0}
        assert 0.0 < second["cache_load"] <= second["backend"]
        assert second["trace"] > 0.0 and second["lower"] > 0.0
    finally:
        for name, value in old.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    samples = [s for s in w.collector_samples()
               if s[0] == "compile_cache_total"
               and s[3]["program_key"] == key]
    assert {s[3]["result"]: s[4] for s in samples} == {"hit": 1.0,
                                                       "miss": 1.0}


def test_events_between_answers_for_the_backend_alone():
    w, key = compile_watcher(), _key()
    t0 = time.perf_counter()
    with compile_scope(key):
        jax.block_until_ready(_fresh(2.345678)(np.zeros((2,), np.float32)))
    t1 = time.perf_counter()
    mine = [e for e in w.events_between(t0, t1) if e[2] == key]
    assert len(mine) == 1 and w.any_since(t0)
    t_end, dur, _ = mine[0]
    assert t0 <= t_end - dur and t_end <= t1
    assert dur == pytest.approx(w.stage_seconds(t0, t1)[key]["backend"])
    assert w.events_between(t1, t1 + 1.0) == []


LISTENERS = {
    "duration": ("_listener", monitoring.get_event_duration_listeners),
    "scalar": ("_on_start", monitoring.get_scalar_listeners),
    "event": ("_on_event", monitoring.get_event_listeners),
}


@pytest.mark.parametrize("kind", sorted(LISTENERS))
def test_every_listener_survives_a_clear_and_registers_once(kind):
    import jax.monitoring

    name, held = LISTENERS[kind]
    w = compile_watcher()
    mine = getattr(w, name)
    jax.monitoring.clear_event_listeners()
    # (0.9.0's clear forgets its `global` for the scalar listeners)
    assert kind == "scalar" or mine not in held()
    w.ensure_installed()
    w.ensure_installed()
    assert held().count(mine) == 1


def test_placing_the_compile_cache_starts_the_account(monkeypatch):
    """Every entry point calls `enable_compile_cache` before its first
    compile: the watcher hears the process's builds from there."""
    import jax.monitoring

    from deeplearning4j_tpu.runtime import device

    w = compile_watcher()
    jax.monitoring.clear_event_listeners()
    monkeypatch.setenv(device.CACHE_ENV, "/some/dir")    # sets no config
    device.enable_compile_cache()
    assert w._listener in monitoring.get_event_duration_listeners()
    assert w._on_event in monitoring.get_event_listeners()


def test_a_stage_left_open_by_a_clear_does_not_rename_later_builds():
    """Listeners cleared between a stage's start and its end leave the
    start on the thread's stack; the re-install forgets it."""
    import jax.monitoring

    w = compile_watcher()
    w._on_start(TRACE_EVENT, 0.0, fun_name="ghost")
    jax.monitoring.clear_event_listeners()
    w = compile_watcher()

    def after_the_ghost(x):
        return x * 0.3125

    t0 = time.perf_counter()
    jax.jit(after_the_ghost)(np.zeros((2, 2), np.float32))
    built = w.stage_seconds(since=t0)
    assert set(built) == {"fn:after_the_ghost"}
    assert set(built["fn:after_the_ghost"]) == set(BUILD)


def test_metrics_label_the_seconds_by_key_and_stage():
    w, key = compile_watcher(), _key()
    with compile_scope(key):
        jax.block_until_ready(_fresh(3.456789)(np.zeros((3,), np.float32)))
    reg = MetricsRegistry()
    reg.register_collector(w.collector_samples)
    text = reg.exposition()
    for stage in BUILD:
        assert (f'compile_seconds_total{{program_key="{key}",'
                f'stage="{stage}"}}') in text
    assert f'compiles_total{{program_key="{key}"}} 1' in text
    mine = {s[3]["stage"]: s[4] for s in w.collector_samples()
            if s[0] == "compile_seconds_total"
            and s[3]["program_key"] == key}
    assert set(mine) == set(BUILD) and all(v > 0.0 for v in mine.values())
    assert all(s[3].get("program_key") != "" for s in w.collector_samples())


def test_over_keys_sums_what_the_watcher_gave():
    assert over_keys({"a": {"trace": 1.0, "lower": 2.0},
                      "b": {"trace": 0.5}}) == {"trace": 1.5, "lower": 2.0}
    assert over_keys({}) == {}


# ---- the readers inside the program ----------------------------------------


@pytest.fixture(scope="module")
def warmed():
    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.serving import ContinuousLMServer

    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=48)
    srv = ContinuousLMServer(cfg, tfm.init_params(cfg, jax.random.PRNGKey(0)),
                             slots=2, page_size=4, prefill_chunk=4)
    t0 = time.perf_counter()
    srv.warmup()
    yield {"warmup": srv.stats()["warmup"], "t0": t0,
           "t1": time.perf_counter()}
    srv.stop()


def test_warmup_says_what_each_programs_seconds_were(warmed):
    warm = warmed["warmup"]
    assert set(warm["stages"]) == set(warm["programs"]) == {
        "lm:paged[w1]", "lm:paged[w4]", "lm:page_copy"}
    for key, wall in warm["programs"].items():
        split = warm["stages"][key]
        assert set(split) == {*STAGES, "hits", "misses", "run"}
        assert all(split[s] > 0.0 for s in BUILD)
        assert split["run"] > 0.0
        assert sum(split[s] for s in BUILD) + split["run"] == (
            pytest.approx(wall))
        assert split["cache_load"] <= split["backend"]
    assert sum(warm["programs"].values()) <= warm["total_s"]


def test_the_pools_allocation_has_a_key_outside_warm_up(warmed):
    built = compile_watcher().stage_seconds(warmed["t0"], warmed["t1"])
    assert "kv:pool" in built and "" not in built
    lm = sum(sum(by[s] for s in BUILD if s in by)
             for key, by in built.items() if key.startswith("lm:"))
    assert 0.0 < lm <= warmed["warmup"]["total_s"]


def test_the_mesh_trainers_placement_has_a_key_of_its_own():
    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import HybridParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    cfg = tfm.TransformerConfig(vocab_size=43, d_model=16, n_heads=4,
                                n_layers=1, d_ff=32, max_len=16)
    mesh = make_mesh((1, 2, 2), ("data", "seq", "model"),
                     devices=jax.devices()[:4])
    t0 = time.perf_counter()
    trainer = HybridParallelTrainer(cfg, mesh, lr=0.01, seed=3,
                                    updater="adam")
    t1 = time.perf_counter()
    rng = np.random.default_rng(5)
    trainer.fit_batch(rng.integers(0, cfg.vocab_size, (4, 8)),
                      rng.integers(0, cfg.vocab_size, (4, 8)))
    w = compile_watcher()
    assert set(w.stage_seconds(t0, t1)) == {"train:place"}
    step = w.stage_seconds(since=t1)["train:hybrid"]
    assert all(step[s] > 0.0 for s in BUILD)
