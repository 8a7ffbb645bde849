"""Tokens of the steps completed in the window over its length, the whole
job on all the cell's chips.  A step is complete when `block_until_ready`
on its loss returns; the window ends with its last step."""

NAME, UNIT, BETTER, SOURCE = ("train_tokens_per_s", "tokens/s", "higher",
                              "host_clock")


def read(run):
    if not run.step_ends:
        return None
    return len(run.step_ends) * run.tokens_per_step / run.window_s
