"""Device time of the mesh trainer's step program per step, on chip 0, from
the profiler's program events (`HybridParallelTrainer`'s program is a
`jit(step)` too, so `readings.train_steps` finds it)."""

from benchmark import readings

NAME, UNIT, BETTER = "mesh_step_ms", "ms", "lower"
LAYER, MOVES, SOURCE = "Mesh runtimes", "train_tokens_per_s", "device_trace"


def read(run):
    return readings.mean_ms(readings.train_steps(run))
