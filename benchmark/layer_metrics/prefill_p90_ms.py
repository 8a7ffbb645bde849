"""How long a request's prompt took once it had a lane: the program's
`prefill` span (`serving/lm.py:_trace_request`, slot install to first
committed token), 90th percentile over the requests that entered in the
window.  With `queue_wait` before it, it is the server's share of the time
to the first token."""

from benchmark import readings

NAME, UNIT, BETTER = "prefill_p90_ms", "ms", "lower"
LAYER, MOVES, SOURCE = "LM scheduler", "ttft_p90_ms", "program_span"


def read(run):
    return readings.percentile(
        readings.span_durations_ms(run, "prefill"), 90)
