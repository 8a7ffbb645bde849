"""The grouped-query paged attention kernel under the block mask, as a share
of its roofline: the least time the chip could take to read the K and V
pages a dispatch needs (`flops_sdar.block_paged_bytes` over the HBM
bandwidth: live pages x 2 pools x `page_size` rows of `kv_heads x head_dim`
values, every layer) over the kernel's device time per dispatch, either
width.

Two windows meet here, as in `gqa_kernel_roofline`: the time is the trace's
(4 s), the pages are the program's counter over the whole window
(`serving_lm_live_pages_total`, mean a round).  A round of B query columns a
lane does B x G rows of matmul a page and is bound by the walk, a page a
step, not by the bytes; a wide round walks the pages once a query block.
The bytes counted are what the round needs, not what the kernel moves."""

from benchmark import flops_sdar, readings, readings_kda, rounds

NAME, UNIT, BETTER = "gqa_block_kernel_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Paged attention kernel", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    cfg = run.model
    if (getattr(cfg, "block_length", 1) < 2 or run.peaks is None
            or run.device_trace is None):
        return None
    steps = [e for p in readings_kda.paged_programs(run).values() for e in p]
    pages = rounds.live_pages_per_round(run)
    if not steps or not pages:
        return None
    least = flops_sdar.block_paged_bytes(
        pages, run.counters["after"]["kv"]["page_size"], cfg.n_kv_heads,
        cfg.head_dim, cfg.n_layers, readings_kda.itemsize(cfg),
    ) / run.peaks["hbm_bytes_per_s"]
    spent = readings.op_seconds(run, readings.PAGED_KERNEL,
                                within=steps) / len(steps)
    return 100.0 * least / spent if spent else None
