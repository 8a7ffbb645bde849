"""Host time of one scheduling round of the LM worker: the program's own
phase seconds (`serving_lm_round_seconds_total`, every phase but `sync`,
which is the wait for the device) across the window, over the rounds it
dispatched.  The inside view of the device's between-round gaps."""

from benchmark import rounds

NAME, UNIT, BETTER = "round_host_ms.chat", "ms", "lower"
LAYER, MOVES, SOURCE = "LM scheduler", "tpot_p95_ms", "program_counter"


def read(run):
    return rounds.round_host_ms(run)
