"""The latent paged attention kernel's share of its roofline in WIDTH-1
rounds: the least time the chip could take for what a round's attention
needs, the larger of its bytes over the HBM bandwidth and its operations
over the bf16 peak (`flops_latent`: 128 heads against one 576-value row put
the two within a few per cent of each other on a v5e, 240 flop/B against a
ridge of 240), over the kernel's device time per width-1 dispatch.

Two windows meet here, as in `paged_kernel_roofline`: the time is the
trace's (4 s), the rows and pairs are the program's counters over the whole
window (`serving_lm_attn_rows_total{round="w1"}`, `..._attn_pairs_total`:
per width-1 round, over active lanes, cache rows read and (column, row)
pairs scored), taken per round.  Wide rounds are left out: their mix of fed
widths in 4 s is not the window's.  The rows counted are the 576 values a
token needs, not the 640 lanes the pool holds them in."""

from benchmark import flops_latent, readings, rounds

NAME, UNIT, BETTER = "latent_kernel_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Latent attention kernel", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    cfg = run.model
    latent = getattr(cfg, "latent", None)
    if latent is None or run.peaks is None:
        return None
    steps = readings.paged_programs(run).get(1)
    n = rounds.rounds_by_width(run).get(1)
    rows = rounds.delta(run, "attn_rows", "w1")
    pairs = rounds.delta(run, "attn_pairs", "w1")
    if not steps or not n or not rows or not pairs:
        return None
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    least = cfg.n_layers * max(
        flops_latent.latent_attention_bytes(
            rows / n, latent.row_values, itemsize)
        / run.peaks["hbm_bytes_per_s"],
        flops_latent.latent_attention_flops(
            pairs / n, cfg.n_heads, latent.row_values, latent.kv_rank)
        / run.peaks["bf16_flops_per_s"])
    spent = readings.op_seconds(run, readings.PAGED_KERNEL,
                                within=steps) / len(steps)
    return 100.0 * least / spent if spent else None
