"""Time from a request's due time (open loop: a stall is charged to the
requests it delays) to its first token, 90th percentile over the requests
issued in the window.  p90 because a window holds a few hundred requests:
beyond p95 there would be a handful.  The median is printed beside it,
not judged: it moves in steps of one round, 7 % of it."""

from benchmark import readings
from benchmark.observe import say

NAME, UNIT, BETTER, SOURCE = "ttft_p90_ms", "ms", "lower", "host_clock"


def read(run):
    ttft = readings.ttfts_ms(run)
    say(NAME, samples=len(ttft), median=readings.percentile(ttft, 50),
        p80=readings.percentile(ttft, 80), p99=readings.percentile(ttft, 99))
    return readings.percentile(ttft, 90)
