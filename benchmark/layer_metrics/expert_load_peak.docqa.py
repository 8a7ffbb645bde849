"""The largest load among the experts held here over their mean load, mean
over the rounds of the window (`serving_lm_expert_load_peak_total` over
`serving_lm_expert_rounds_total`: each round's step program reports, with
its sampled tokens, the most routed pairs any held expert got in any layer
against an even share).  1 is even; the grouped matmul reads a hit expert's
47 MB whether it got one row or fifty."""

from benchmark import readings

NAME, UNIT, BETTER = "expert_load_peak.docqa", "x", "lower"
LAYER, MOVES, SOURCE = "Expert layer", "serve_tokens_per_s", "program_counter"


def read(run):
    rounds_ = readings.counter_delta(run, "experts", "rounds")
    peak = readings.counter_delta(run, "experts", "load_peak_sum")
    if not rounds_ or peak is None:
        return None
    return peak / rounds_
