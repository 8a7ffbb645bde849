"""Process start to the first measured request or step: loading, weights,
warm-up, compilation where the cache is cold, and the mix's pre-roll.  The
reference's time is not in it: it runs after the window."""

NAME, UNIT, BETTER, SOURCE = "setup_s", "s", "lower", "host_clock"


def read(run):
    return run.setup_s
