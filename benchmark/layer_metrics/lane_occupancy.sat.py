"""Active lanes per dispatch over the lanes there are, from the program's
counters (`ServingMetrics.record_dispatch`) across the window."""

from benchmark import readings

NAME, UNIT, BETTER = "lane_occupancy.sat", "%", "higher"
LAYER, MOVES, SOURCE = "LM scheduler", "serve_tokens_per_s", "program_counter"


def read(run):
    return readings.lane_occupancy_pct(run)
