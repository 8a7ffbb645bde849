"""Arithmetic the metric readers share: from a run's requests, counters,
spans and reduced trace to a number.  A reading that finds nothing gives
None, and the reader that asked then reports nothing."""

from __future__ import annotations

import bisect
import re

import numpy as np

# Both of the server's step programs and the train step are `jax.jit` of a
# function called `step`; the profiler names a launched program
# `jit_step(<fingerprint>)`.  Stable names are on PERF.md's list for the
# `tracing` issue.
STEP_PROGRAM = re.compile(r"^jit_step\b")


def percentile(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def ttfts_ms(run):
    """Due time to first token of every request issued in the window."""
    return [1e3 * (r.times[0] - r.due) for r in run.issued_in_window()
            if r.times]


def token_gaps_ms(run):
    """Every gap between consecutive tokens of those requests."""
    return [1e3 * g for r in run.issued_in_window()
            for g in np.diff(r.times)]


def tokens_in_window(run):
    return sum(run.in_window(t) for r in run.requests for t in r.times)


def counter_delta(run, *path):
    """after - before of the program's counter at `path` in `lm.stats()`."""
    def at(stats):
        for key in path:
            stats = stats.get(key, {}) if isinstance(stats, dict) else {}
        return stats if isinstance(stats, (int, float)) else None

    before, after = at(run.counters["before"]), at(run.counters["after"])
    if before is None or after is None:
        return None
    return after - before


def lane_occupancy_pct(run):
    """Active lanes per dispatch over the lanes there are."""
    rows = counter_delta(run, "rows")
    dispatches = counter_delta(run, "dispatches")
    if not dispatches:
        return None
    return 100.0 * rows / (dispatches * run.counters["after"]["slots"])


def span_durations_ms(run, name):
    return [1e3 * s["dur_s"] for t in run.traces for s in t["spans"]
            if s["name"] == name]


def idle_share_pct(run):
    if run.device_trace is None:
        return None
    return 100.0 * run.device_trace.idle_share()


def step_programs(run):
    """Device events of the launched step programs inside the traced
    window, one list per program."""
    if run.device_trace is None:
        return []
    groups = {}
    for e in run.device_trace.devices[0].modules:
        if STEP_PROGRAM.search(e.name):
            groups.setdefault(e.name, []).append(e)
    return list(groups.values())


def train_steps(run):
    """Events of the train step: of the `jit_step` programs in the traced
    window the one that took most of it."""
    return max(step_programs(run), key=lambda p: sum(e.dur for e in p),
               default=[])


def ops_within(run, events):
    """Device 0's operation events that lie inside any of `events`."""
    ops = run.device_trace.devices[0].ops
    starts = [e.start for e in ops]
    out = []
    for w in events:
        i = bisect.bisect_left(starts, w.start)
        while i < len(ops) and ops[i].start < w.end:
            if ops[i].end <= w.end:
                out.append(ops[i])
            i += 1
    return out


# The paged attention kernel in a TPU trace: a `custom-call` whose first
# operand is the block table, s32[lanes, pages per lane], and whose output is
# bf16[lanes, width, heads, head size] (taken from a v5e trace, PR 23).
PAGED_KERNEL = re.compile(
    r"= bf16\[\d+,(\d+),\d+,\d+\]\S* custom-call\(s32\[\d+,\d+\]")


def paged_programs(run):
    """{feed width: events} of the server's step programs that ran in the
    traced window.  Both are `jit_step`; the width is read off the paged
    kernel's output shape inside one launch of each."""
    out = {}
    for events in step_programs(run):
        for op in ops_within(run, events[:1]):
            m = PAGED_KERNEL.search(op.name)
            if m:
                out[int(m.group(1))] = events
                break
    return out


def mean_ms(events):
    return 1e3 * float(np.mean([e.dur for e in events])) if events else None


def op_seconds(run, pattern, within=None):
    """Seconds of device 0's operations whose name `pattern` finds, all of
    them or those inside the events `within`."""
    if run.device_trace is None:
        return None
    ops = (run.device_trace.devices[0].ops if within is None
           else ops_within(run, within))
    return float(sum(e.dur for e in ops if pattern.search(e.name)))
