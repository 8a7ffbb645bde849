"""The paged attention kernel's share of its roofline: the least time the
chip could take to read the K and V pages a dispatch needs
(`flops.paged_hbm_bytes` over the HBM bandwidth; the kernel is bound by
bytes: one query row a lane against whole pages) over the kernel's device
time per dispatch.

Two windows meet here, and they differ.  The time is the device trace's, as
`paged_kernel_ms_per_step` reads it, over the 4 traced seconds.  The bytes
come from the program's counters over the whole window: the mean, per lane
and round, of the pages the attention had to read
(`serving_lm_live_pages_total`: over active lanes,
ceil((pos + n_feed) / page_size)).  Load is even over tens of seconds
(PERF.md section 4), so the mean stands for the traced part; a mix whose
histories grow through the window would not allow it."""

from benchmark import flops, readings, rounds

NAME, UNIT, BETTER = "paged_kernel_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Paged attention kernel", "tpot_p95_ms",
                        "device_trace")


def read(run):
    steps = [e for p in readings.paged_programs(run).values() for e in p]
    pages = rounds.live_pages_per_round(run)
    if not steps or pages is None or run.peaks is None:
        return None
    stats, cfg = run.counters["after"], run.model
    lanes, kv = stats["slots"], stats["kv"]
    itemsize = stats["kv_bytes"]["per_token"] // (
        2 * cfg.n_layers * cfg.n_heads * cfg.head_dim)
    least = flops.paged_hbm_bytes(
        cfg.n_layers, lanes, pages / lanes, kv["max_pages_per_seq"],
        kv["page_size"], cfg.n_heads, cfg.head_dim, itemsize,
        kernel=True) / run.peaks["hbm_bytes_per_s"]
    spent = readings.op_seconds(run, readings.PAGED_KERNEL,
                                within=steps) / len(steps)
    return 100.0 * least / spent if spent else None
