"""How long a request waited for a lane: the program's `queue_wait` span
(`serving/lm.py:_trace_request`, admission to slot install), 90th
percentile over the requests that entered in the window."""

from benchmark import readings

NAME, UNIT, BETTER = "queue_wait_p90_ms", "ms", "lower"
LAYER, MOVES, SOURCE = "LM scheduler", "ttft_p90_ms", "program_span"


def read(run):
    return readings.percentile(
        readings.span_durations_ms(run, "queue_wait"), 90)
