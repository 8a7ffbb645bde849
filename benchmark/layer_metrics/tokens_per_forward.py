"""Tokens committed over the lane-rounds of a block model's decode phase,
denoise rounds and commit passes alike (`serving_lm_block_rounds_total`),
across the window: what one forward over a lane's block yields.  With `B`
positions a block, `S` denoise steps and the commit pass it is `B / (S + 1)`
by construction where the prompt ends on a block boundary (4 / 3 here), and a
little less where the first and the last block of an answer are shared with
the prompt and with what is dropped past the answer's end; the number a
change to the schedule, or a commit fused with the next block's first
denoise round, moves."""

from benchmark import readings

NAME, UNIT, BETTER = "tokens_per_forward", "tokens", "higher"
LAYER, MOVES, SOURCE = "LM scheduler", "serve_tokens_per_s", "program_counter"


def read(run):
    rounds_ = [readings.counter_delta(run, "blocks", "rounds", kind)
               for kind in ("denoise", "commit")]
    tokens = readings.counter_delta(run, "tokens")
    if None in rounds_ or not sum(rounds_) or tokens is None:
        return None
    return tokens / sum(rounds_)
