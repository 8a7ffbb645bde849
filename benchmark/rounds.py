"""Arithmetic on the LM worker's round counters (`lm.stats()["rounds"]`,
`ServingMetrics.record_round`), taken as after - before across the window:
what the readers of the scheduler's inside view share.  A program that has
no such counters (the parent of the PR that brought them) gives None."""

from __future__ import annotations

from benchmark import readings

SYNC = "sync"       # the one phase that is the wait for the device


def delta(run, *path):
    return readings.counter_delta(run, "rounds", *path)


def phase_seconds(run):
    """{phase: seconds across the window} of every phase the program
    counts (`serving/metrics.py:ROUND_PHASES`)."""
    phases = run.counters["after"].get("rounds", {}).get("seconds", {})
    return {phase: delta(run, "seconds", phase) for phase in phases}


def round_host_ms(run):
    """Host seconds of every phase but `sync`, over the rounds."""
    rounds, seconds = delta(run, "count"), phase_seconds(run)
    if not rounds or None in seconds.values():
        return None
    return 1e3 * sum(s for p, s in seconds.items() if p != SYNC) / rounds


def feed_fill_pct(run):
    """Tokens fed, all kinds, over lanes x width of the rounds: how much of
    what the step program paid for carried a token."""
    capacity = delta(run, "feed_capacity")
    kinds = run.counters["after"].get("rounds", {}).get("fed_tokens", {})
    fed = [delta(run, "fed_tokens", kind) for kind in kinds]
    if not capacity or None in fed:
        return None
    return 100.0 * sum(fed) / capacity


def live_pages_per_round(run):
    """KV pages the attention had to read, all lanes, mean over rounds."""
    rounds, pages = delta(run, "count"), delta(run, "live_pages")
    if not rounds or pages is None:
        return None
    return pages / rounds


def rounds_by_width(run):
    """{width: rounds dispatched at it across the window}; a width first
    dispatched inside the window has no count before it."""
    before, after = (run.counters[k].get("rounds", {}).get("by_width", {})
                     for k in ("before", "after"))
    return {int(w): n - before.get(w, 0) for w, n in after.items()}


def mean_width(run):
    """Mean width dispatched, from the rounds counted by width."""
    counts = rounds_by_width(run)
    total = sum(counts.values())
    if not total:
        return None
    return sum(w * n for w, n in counts.items()) / total
