"""Model FLOP/s utilization: the operations the forward and backward
passes of one step require (`benchmark/flops.py`; recomputation does not
count) over the device time of the step program per step, as a share of the
chips' published bf16 peak.

The time is the traced steps' own, from the profiler's program events, not
the host clock's rate over the traced run's window: that window also holds
the profiler's start and stop, which cost seconds."""

from benchmark import flops, readings

NAME, UNIT, BETTER = "train_mfu", "%", "higher"
LAYER, MOVES, SOURCE = "Train step", "train_tokens_per_s", "device_trace"


def read(run):
    steps = readings.train_steps(run)
    if not steps or run.peaks is None:
        return None
    per_step = run.tokens_per_step * flops.train_flops_per_token(
        run.model, run.n_params, run.job.seq)
    step_s = sum(e.dur for e in steps) / len(steps)
    return 100.0 * per_step / step_s / (
        run.chips * run.peaks["bf16_flops_per_s"])
