"""What the readers of the program's build account share: the seconds, by
stage, of every program the process built before the window, and how many of
them missed the persistent compile cache.

The program's one watcher (`deeplearning4j_tpu.obs.compilewatch`) hears JAX's
own events for every program the process builds, whoever built it: `trace`
(Python tracing to a jaxpr), `lower` (the jaxpr to StableHLO), `backend` (the
compile, or the load from the persistent cache; JAX fires one event for
both), and the cache's hits and misses.  It is process-wide and on
`perf_counter`, the clock `run.t0` is on, so what ended between the run's
start and the window is set-up's, and the reference's programs, built after
it, are not counted.  A
stage nested in another is counted once (the union of the intervals), so the
stages sum to no more than the time that passed.

A program that has no such account (before PR 40) gives `None`, and so does a
watcher that heard nothing before the window.
"""

from __future__ import annotations


def _before_window(run, method: str):
    """`compile_watcher().<method>` over set-up, summed over its keys, or
    None where the program has no such method or it holds nothing."""
    try:
        from deeplearning4j_tpu.obs import compilewatch
    except ImportError:
        return None
    read = getattr(compilewatch.compile_watcher(), method, None)
    if read is None:
        return None
    by_key = read(since=run.t0 - run.setup_s, until=run.t0)
    return compilewatch.over_keys(by_key) if by_key else None


def stage_seconds(run):
    """{stage: seconds} of everything built before the window, or None."""
    return _before_window(run, "stage_seconds")


def cache_misses(run):
    """Programs compiled and written to the persistent cache before the
    window, because it did not hold them; None where nothing was built."""
    if stage_seconds(run) is None:
        return None
    return (_before_window(run, "cache_results") or {}).get("miss", 0)
