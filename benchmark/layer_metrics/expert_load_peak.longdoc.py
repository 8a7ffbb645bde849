"""`expert_load_peak.docqa` on the long-document cell: the largest load among
the 40 experts held here over their mean load, mean over the window's rounds
(the accepted reader's counters and arithmetic).  1 is even; a round's
number is the largest over the layers (`generation.expert_load`).  The
router is 320 wide and 8 a token, so a round of 4 decoding lanes sends about
4 pairs a layer to the held eighth, and the layer where one or two fall
reads 40 or 20 (26.2 over a window, my chip run, PR 38): the number says how
far the cut's experts are from a deployment's load, where each would see
eight chips' tokens."""

from benchmark import spec

NAME, UNIT, BETTER = "expert_load_peak.longdoc", "x", "lower"
LAYER, MOVES, SOURCE = "Expert layer", "serve_tokens_per_s", "program_counter"

read = spec.reader("layer_metrics", "expert_load_peak.docqa").read
