"""Mean device time per dispatch of either step program."""

from benchmark import readings

NAME, UNIT, BETTER = "step_ms.sat", "ms", "lower"
LAYER, MOVES, SOURCE = ("Model step programs", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    steps = [e for p in readings.paged_programs(run).values() for e in p]
    return readings.mean_ms(steps)
