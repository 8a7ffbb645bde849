"""Device time of the state-row copy program (`generation.make_state_copy`:
a lane's state saved to its trailing row at a page boundary, a snapshot
restored into a lane, a row zeroed; `jit_state_copy` on the trace's modules
line) per dispatch of a step program in the traced window, either width."""

import re

from benchmark import readings_kda

NAME, UNIT, BETTER = "state_copy_ms_per_round", "ms", "lower"
LAYER, MOVES, SOURCE = ("KV cache manager", "serve_tokens_per_s",
                        "device_trace")

STATE_COPY = re.compile(r"^jit_state_copy\b")


def read(run):
    if getattr(run.model, "linear", None) is None:
        return None
    steps = sum(len(p) for p in readings_kda.paged_programs(run).values())
    if not steps:
        return None
    copies = [e for e in run.device_trace.devices[0].modules
              if STATE_COPY.search(e.name)]
    return 1e3 * sum(e.dur for e in copies) / steps
