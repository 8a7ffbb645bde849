"""Device time of the wide (prefill chunk) step program per dispatch, from
the profiler's program events."""

from benchmark import readings

NAME, UNIT, BETTER = "chunk_step_ms", "ms", "lower"
LAYER, MOVES, SOURCE = "Model step programs", "ttft_p90_ms", "device_trace"


def read(run):
    wide = [e for width, events in readings.paged_programs(run).items()
            if width > 1 for e in events]
    return readings.mean_ms(wide)
