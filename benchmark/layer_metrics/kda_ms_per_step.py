"""Device time of the gated delta rule (the chunked form in wide rounds, the
rank-1 update at width 1, the state rows read and written around them; all
recurrent layers) per dispatch of a step program, either width.  Which
operations those are: `benchmark/readings_kda.py`."""

from benchmark import readings_kda

NAME, UNIT, BETTER = "kda_ms_per_step", "ms", "lower"
LAYER, MOVES, SOURCE = ("Linear attention kernels", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    steps = [e for p in readings_kda.paged_programs(run).values() for e in p]
    seconds = readings_kda.kda_seconds(run, steps)
    return None if seconds is None else 1e3 * seconds / len(steps)
