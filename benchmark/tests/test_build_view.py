"""The readers of the program's build account (`build_trace_lower_s`,
`build_compile_s`, `build_cache_misses`) on tiny runs of a serve and a train
cell; and on a run, or a program, that has nothing for them."""

import argparse
import time

import pytest

from benchmark import readings_build, spec
from benchmark.observe import Run

READERS = ["build_trace_lower_s", "build_compile_s", "build_cache_misses"]
BUILD = ("trace", "lower", "backend")


def read(metric, run):
    return spec.reader("layer_metrics", metric).read(run)


@pytest.fixture(scope="module", params=["gpt2-large.chat",
                                        "gpt2-medium.train"])
def tiny(request):
    """A cell's driver at toy size on the CPU.  The account listens from
    the start, as it does in a run (`device.acquire` places the compile
    cache through the program's `enable_compile_cache`, which starts it)."""
    import jax

    from deeplearning4j_tpu.obs.compilewatch import compile_watcher

    cell = spec.load_cell(request.param, tiny=True)
    args = argparse.Namespace(seed=13, seconds=2.0, trace=0, tiny=True)
    t_start = time.perf_counter()
    compile_watcher()
    run, checks, attempted, failed, _ = spec.driver(cell.config).run(
        cell, args, t_start, jax.devices()[:1])
    assert attempted > 0 and failed == 0
    assert all(value <= limit for _, value, limit in checks)
    return run


def test_the_readers_find_their_numbers_within_setup(tiny):
    traced, compiled, misses = (read(m, tiny) for m in READERS)
    assert traced > 0.0 and compiled > 0.0
    assert traced + compiled <= tiny.setup_s
    assert misses >= 0 and misses == int(misses)


def test_no_build_of_setup_is_unkeyed_and_the_step_reads_by_name(tiny):
    from deeplearning4j_tpu.obs.compilewatch import compile_watcher

    built = compile_watcher().stage_seconds(tiny.t0 - tiny.setup_s, tiny.t0)
    assert "" not in built
    if tiny.cell.config["kind"] == "train":
        assert set(built["fn:step"]) >= set(BUILD)
        return
    # the serve programs under the keys they have, inside warm-up
    warm = tiny.counters["after"]["warmup"]
    assert set(warm["programs"]) <= set(built)
    lm = sum(by.get(stage, 0.0) for key, by in built.items()
             if key.startswith("lm:") for stage in BUILD)
    assert 0.0 < lm <= read("warmup_s", tiny)


@pytest.mark.parametrize("metric", READERS)
def test_a_run_with_nothing_built_gives_nothing(metric):
    """A `Run` no window was set on: nothing ended before its start."""
    assert read(metric, Run(cell=None, chips=1, peaks=None)) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_account_gives_nothing(metric, monkeypatch):
    """The parent of the PR that brought it: a watcher that counts
    compiles by key and has no stages."""
    from deeplearning4j_tpu.obs import compilewatch

    class Parent:
        def total(self):
            return 3

    monkeypatch.setattr(compilewatch, "compile_watcher", Parent)
    run = Run(cell=None, chips=1, peaks=None, t0=time.perf_counter(),
              setup_s=30.0)
    assert read(metric, run) is None
    assert readings_build.stage_seconds(run) is None
