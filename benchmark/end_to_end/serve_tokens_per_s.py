"""Output tokens delivered to clients inside the window over its length,
with more load offered than the chip sustains: the capacity a chip-hour
buys.  All tokens count, whichever request they belong to."""

from benchmark import readings

NAME, UNIT, BETTER, SOURCE = ("serve_tokens_per_s", "tokens/s", "higher",
                              "host_clock")


def read(run):
    if not run.requests:
        return None
    return readings.tokens_in_window(run) / run.window_s
