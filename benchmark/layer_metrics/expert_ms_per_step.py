"""Device time of the expert layers' grouped matmuls (`lax.ragged_dot`: the
TPU compiler's `ragged-dot` custom calls, three an expert layer: gate, up,
down over the routed pairs sorted by expert) per dispatch of either step
program in the traced window.  At width 1 (36 sorted rows at 6 lanes) the
compiler lowers `ragged_dot` to plain fusions instead, whose names say
nothing of where they came from, so a width-1 round adds a dispatch and no
time, and a traced window that holds no wide round reads 0 (PERF.md
sections 5 and 7).  The sort, the gather of the rows and the combine around
the matmuls are XLA fusions and are not in it."""

import re

from benchmark import readings

NAME, UNIT, BETTER = "expert_ms_per_step", "ms", "lower"
LAYER, MOVES, SOURCE = "Expert layer", "serve_tokens_per_s", "device_trace"

RAGGED_DOT = re.compile(r"^%?ragged-dot")


def read(run):
    if getattr(run.model, "experts", None) is None:
        return None
    programs = readings.paged_programs(run).values()
    steps = sum(len(events) for events in programs)
    if not steps:
        return None
    seconds = sum(readings.op_seconds(run, RAGGED_DOT, within=events)
                  for events in programs)
    return 1e3 * seconds / steps
