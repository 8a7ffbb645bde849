"""Device time of the paged attention kernel's calls (one per layer) per
dispatch of a step program, either width."""

from benchmark import readings

NAME, UNIT, BETTER = "paged_kernel_ms_per_step", "ms", "lower"
LAYER, MOVES, SOURCE = ("Paged attention kernel", "tpot_p95_ms",
                        "device_trace")


def read(run):
    steps = [e for p in readings.paged_programs(run).values() for e in p]
    if not steps:
        return None
    seconds = readings.op_seconds(run, readings.PAGED_KERNEL, within=steps)
    return 1e3 * seconds / len(steps)
