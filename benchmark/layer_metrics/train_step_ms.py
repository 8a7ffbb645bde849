"""Device time of the train step program per step, from the profiler's
program events."""

from benchmark import readings

NAME, UNIT, BETTER = "train_step_ms", "ms", "lower"
LAYER, MOVES, SOURCE = "Train step", "train_tokens_per_s", "device_trace"


def read(run):
    return readings.mean_ms(readings.train_steps(run))
