"""Device time of a block round's tail (`blocks:unmask` in the step program:
the head over a lane's B block columns, the float32 softmax over the
vocabulary and the unmasking choice) per dispatch of either step program.

A TPU operation event is named by its whole HLO instruction and carries no
scope, so the tail's operations are told by what they read or make: an
array whose LAST dimension is the vocabulary (the logits `[slots, B, V]`,
the head's weights `[d, V]`, the mask id's `[V]` comparison, and the
reductions that read them).  The embedding `[V, d]` has the vocabulary
first and is not in it.  An operation's time is its own, so a fusion nested
in another is counted once."""

import re

from benchmark import readings, readings_kda, trace_reduce

NAME, UNIT, BETTER = "unmask_ms_per_step", "ms", "lower"
LAYER, MOVES, SOURCE = ("Model step programs", "serve_tokens_per_s",
                        "device_trace")


def pattern(vocab: int):
    return re.compile(rf"[\[,]{int(vocab)}\]")


def read(run):
    if (getattr(run.model, "block_length", 1) < 2
            or run.device_trace is None):
        return None
    steps = [e for p in readings_kda.paged_programs(run).values() for e in p]
    if not steps:
        return None
    found = pattern(run.model.vocab_size)
    ops = sorted(readings.ops_within(run, steps), key=lambda e: e.start)
    own = trace_reduce.self_times(ops, float("-inf"), float("inf"))
    seconds = sum(sec for name, sec in own.items() if found.search(name))
    return 1e3 * seconds / len(steps)
