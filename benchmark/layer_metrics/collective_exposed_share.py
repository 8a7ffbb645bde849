"""Collectives' exposed share of the mesh trainer's step: on each chip,
within the step program's events, the time in which a collective operation
runs and no other operation does, over the step's device time; mean of the
chips.

An operation event is named by its HLO instruction; a collective is one
whose opcode is `all-reduce`, `all-gather`, `reduce-scatter`,
`collective-permute`, `all-to-all` or `collective-broadcast`, or the
`-start` / `-done` half of one (the transfer of an asynchronous collective
runs between its halves, behind whatever operations lie there: only the
halves themselves, which wait, can be exposed).  Only leaf operations count:
a `while` or a `call` holds its body's operations nested inside it.  What is
hidden behind compute is not in this number; what a collective costs by
making the compute around it wait for data is."""

import re

from benchmark import readings, trace_reduce

NAME, UNIT, BETTER = "collective_exposed_share", "%", "lower"
LAYER, MOVES, SOURCE = "Mesh runtimes", "train_tokens_per_s", "device_trace"
COLLECTIVE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?\(")


def leaves(ops):
    """Of events sorted by start, those that hold no other event."""
    out = []
    for i, e in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= e.end or nxt.end > e.end:
            out.append(e)
    return out


def length(intervals):
    return sum(t - s for s, t in intervals)


def exposed_seconds(ops, lo, hi):
    """Within [lo, hi]: seconds a collective leaf runs and no other leaf
    does."""
    leaf = leaves(ops)
    coll = [e for e in leaf if COLLECTIVE.search(e.name)]
    rest = [e for e in leaf if not COLLECTIVE.search(e.name)]
    both = trace_reduce.union(sorted(coll + rest, key=lambda e: e.start),
                              lo, hi)
    return length(both) - length(trace_reduce.union(rest, lo, hi))


def device_share(dev):
    """(exposed seconds, step seconds) of one chip's traced steps."""
    groups = {}
    for e in dev.modules:
        if readings.STEP_PROGRAM.search(e.name):
            groups.setdefault(e.name, []).append(e)
    steps = max(groups.values(), key=lambda p: sum(e.dur for e in p),
                default=[])
    exposed = sum(exposed_seconds(
        [o for o in dev.ops if w.start <= o.start and o.end <= w.end],
        w.start, w.end) for w in steps)
    return exposed, sum(w.dur for w in steps)


def read(run):
    if run.device_trace is None:
        return None
    shares = [100.0 * exposed / total for exposed, total in
              map(device_share, run.device_trace.devices) if total]
    return sum(shares) / len(shares) if shares else None
