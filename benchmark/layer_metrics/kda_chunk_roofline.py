"""The chunked delta rule's share of its roofline in WIDE rounds: the least
time the chip could take for what a round's fed tokens need, the larger of
its operations over the bf16 peak and its bytes over the HBM bandwidth
(`flops_kda.kda_chunk_flops`, `kda_chunk_bytes`), over the rule's device time
per wide dispatch (`readings_kda`).  The operations are float32 at `HIGHEST`
(six bf16 passes on the MXU), counted once each against the bf16 peak: a
sixth is the most this share can read while the form is computed so.

Two windows meet here: the time is the trace's (4 s); the fed tokens and the
lanes are the program's counters over the whole window, per wide round: the
tokens fed in wide rounds are all fed tokens less those of width-1 rounds
(one an active lane: `serving_lm_kda_rows_total{round="w1"}`), the lanes
`..._kda_rows_total{round="wide"}`.  Padding columns of the scheduler's
`[lanes, width]` layout are computed and not counted: they are the round's
cost, not its need.

NO ENTRY IN `BENCHMARK.json` LISTS THIS READER (PR 38): the traced 4 s of
`solar-open2.longdoc-sat` begin 3 s into the window, and under the mix's
order of arrivals (`trace_seed` 27) no prompt misses the cache between the
pre-roll and the window's tenth second, so the trace holds no wide launch
and there is nothing to read; a listed metric that a traced run's line lacks
refuses the run.  It read 1.81-1.94 % in the traced runs of other orders
(PERF.md section 5).  A cell whose traced seconds hold a wide round can list
it as it is."""

from benchmark import flops_kda, readings, readings_kda, rounds

NAME, UNIT, BETTER = "kda_chunk_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Linear attention kernels", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    programs = readings_kda.paged_programs(run)
    steps = [e for w, p in programs.items() if w > 1 for e in p]
    spent = readings_kda.kda_seconds(run, steps)
    by_width = rounds.rounds_by_width(run)
    n = sum(c for w, c in by_width.items() if w > 1)
    kinds = run.counters["after"].get("rounds", {}).get("fed_tokens", {})
    fed = [rounds.delta(run, "fed_tokens", kind) for kind in kinds]
    w1 = readings.counter_delta(run, "state", "kda_rows", "w1")
    lanes = readings.counter_delta(run, "state", "kda_rows", "wide")
    if (not spent or not n or not lanes or None in fed or w1 is None
            or run.peaks is None):
        return None
    cfg, la = run.model, run.model.linear
    depth = readings_kda.layers(cfg)
    tokens = (sum(fed) - w1) / n
    least = max(
        flops_kda.kda_chunk_flops(tokens, depth, la.heads, la.k_dim,
                                  la.v_dim) / run.peaks["bf16_flops_per_s"],
        flops_kda.kda_chunk_bytes(tokens, lanes / n, depth, la.heads,
                                  la.k_dim, la.v_dim, la.conv_taps,
                                  readings_kda.itemsize(cfg))
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (spent / len(steps))
