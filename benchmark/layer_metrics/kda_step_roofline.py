"""The width-1 delta-rule update's share of its roofline: the least time the
chip could take to read and write the state rows and convolution tails of a
round's active lanes (`flops_kda.kda_step_bytes` over the HBM bandwidth; a
rank-1 update a head is bound by bytes: 2 operations a state value) over the
update's device time per width-1 dispatch (`readings_kda`).

Two windows meet here, as in `paged_kernel_roofline`: the time is the
trace's (4 s), the lanes are the program's counter over the whole window
(`serving_lm_kda_rows_total{round="w1"}`: active lanes, summed over width-1
rounds), taken per round."""

from benchmark import flops_kda, readings, readings_kda, rounds

NAME, UNIT, BETTER = "kda_step_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Linear attention kernels", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    steps = readings_kda.paged_programs(run).get(1)
    spent = readings_kda.kda_seconds(run, steps)
    n = rounds.rounds_by_width(run).get(1)
    lanes = readings.counter_delta(run, "state", "kda_rows", "w1")
    if not spent or not n or not lanes or run.peaks is None:
        return None
    cfg, la = run.model, run.model.linear
    least = flops_kda.kda_step_bytes(
        lanes / n, readings_kda.layers(cfg), la.heads, la.k_dim, la.v_dim,
        la.conv_taps, readings_kda.itemsize(cfg),
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (spent / len(steps))
