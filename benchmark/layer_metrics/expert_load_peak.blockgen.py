"""`expert_load_peak.docqa` on the block-generation cell: the largest load
among the 128 experts, all held, over their mean load, mean over the
window's rounds (the accepted reader's counters and arithmetic).  1 is even;
a round's number is the largest over the layers (`generation.expert_load`).
A narrow round of 32 lanes routes 1,024 pairs over 128 experts a layer, 8 an
expert at the mean, so every expert's weights are read nearly every round
and the peak says how uneven random routing is at that fill."""

from benchmark import spec

NAME, UNIT, BETTER = "expert_load_peak.blockgen", "x", "lower"
LAYER, MOVES, SOURCE = "Expert layer", "serve_tokens_per_s", "program_counter"

_read = spec.reader("layer_metrics", "expert_load_peak.docqa").read


def read(run):
    if getattr(run.model, "block_length", 1) < 2:
        return None
    return _read(run)
