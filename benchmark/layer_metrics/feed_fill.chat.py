"""Share of the token columns the step programs paid for (lanes x width of
each round, `serving_lm_feed_capacity_total`) that carried a token
(`serving_lm_fed_tokens_total`, all kinds), across the window."""

from benchmark import rounds

NAME, UNIT, BETTER = "feed_fill.chat", "%", "higher"
LAYER, MOVES, SOURCE = "LM scheduler", "ttft_p90_ms", "program_counter"


def read(run):
    return rounds.feed_fill_pct(run)
