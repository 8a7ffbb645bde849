"""Share of the traced window in which no operation ran on the device:
1 - the union of the device's operation intervals over the window, mean
over the chips."""

from benchmark import readings

NAME, UNIT, BETTER = "idle_share.train-4chip", "%", "lower"
LAYER, MOVES, SOURCE = "Device", "train_tokens_per_s", "device_trace"


def read(run):
    return readings.idle_share_pct(run)
