"""Gap between consecutive output tokens as the client's thread receives
them, 95th percentile over all gaps of all requests issued in the window
(thousands of samples): it sees a prefill round stalling every decoding
lane."""

from benchmark import readings
from benchmark.observe import say

NAME, UNIT, BETTER, SOURCE = "tpot_p95_ms", "ms", "lower", "host_clock"


def read(run):
    gaps = readings.token_gaps_ms(run)
    say(NAME, samples=len(gaps), median=readings.percentile(gaps, 50),
        p99=readings.percentile(gaps, 99))
    return readings.percentile(gaps, 95)
