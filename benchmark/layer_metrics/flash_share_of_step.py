"""Device time of the flash attention kernels (forward, dK/dV, dQ) over
the device time of the train step program."""

import re

from benchmark import readings

NAME, UNIT, BETTER = "flash_share_of_step", "%", "lower"
LAYER, MOVES, SOURCE = ("Flash attention kernels", "train_tokens_per_s",
                        "device_trace")
# every Pallas kernel of the train step is a flash attention kernel
KERNELS = re.compile(r" custom-call\(")


def read(run):
    steps = readings.train_steps(run)
    if not steps:
        return None
    kernels = readings.op_seconds(run, KERNELS, within=steps)
    return 100.0 * kernels / sum(e.dur for e in steps)
