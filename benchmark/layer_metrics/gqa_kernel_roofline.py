"""The grouped-query paged attention kernel's share of its roofline: the
least time the chip could take to read the K and V pages a dispatch needs
(`flops_kda.gqa_paged_bytes` over the HBM bandwidth: live pages x 2 pools x
`page_size` rows of `kv_heads x head_dim` values; one layer of a period has
pages) over the kernel's device time per dispatch, either width.

Two windows meet here, as in `paged_kernel_roofline`: the time is the
trace's (4 s), the pages are the program's counter over the whole window
(`serving_lm_live_pages_total`, mean a round).  A wide round's kernel is
bound by its matmuls and walks the pages once a query block, so the share
falls with the wide rounds in the traced part; the bytes counted are what
the round needs, not what the kernel moves."""

from benchmark import flops_kda, readings, readings_kda, rounds

NAME, UNIT, BETTER = "gqa_kernel_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Paged attention kernel", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    cfg = run.model
    if getattr(cfg, "kv_heads", None) is None or run.peaks is None:
        return None
    steps = [e for p in readings_kda.paged_programs(run).values() for e in p]
    pages = rounds.live_pages_per_round(run)
    if not steps or not pages:
        return None
    kv = run.counters["after"]["kv"]
    full = sum(kind == "full" for kind in cfg.mixer_kinds())
    least = flops_kda.gqa_paged_bytes(
        pages, kv["page_size"], cfg.kv_heads, cfg.head_dim, layers=full,
        itemsize=readings_kda.itemsize(cfg),
    ) / run.peaks["hbm_bytes_per_s"]
    spent = readings.op_seconds(run, readings.PAGED_KERNEL,
                                within=steps) / len(steps)
    return 100.0 * least / spent if spent else None
