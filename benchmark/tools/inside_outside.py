"""One traced run of a serve cell, in this process, and the program's inside
view held against the outside one (PERF.md section 5):

    python3 benchmark/tools/inside_outside.py <workload> <seconds> <seed> [DIR]

- the worker's phase seconds and its idle waits against the window's length;
- `round_host_ms` against the median between-round gap of the device trace;
- p90 of `queue_wait` + `prefill` against the clients' `ttft_p90_ms`;
- the mean width from `rounds.by_width` against the widths the device trace
  shows (`readings.paged_programs`);
- the cell's end-to-end metrics under the profiler session (a `--trace 1`
  run's line leaves them out) and its per-layer metrics;
- with DIR, the trace is kept there and `gap_dump` reads it.

Not part of a run; the cell's own readers print nothing of this.
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(argv) -> int:
    from benchmark import device, readings, rounds, spec, trace_reduce
    from benchmark.observe import say
    from benchmark.tools import gap_dump

    cell = spec.load_cell(argv[0])
    devices = device.acquire(cell.chips, tiny=False)
    trace_reduce.KEEP_DIR = argv[3] if len(argv) > 3 else None
    args = argparse.Namespace(seed=int(argv[2]), seconds=float(argv[1]),
                              trace=1, tiny=False, control=None)
    run, checks, attempted, failed, _ = spec.driver(cell.config).run(
        cell, args, time.perf_counter(), devices)
    say("program", attempted=attempted, failed=failed,
        **{name: value for name, value, _ in checks})
    for directory, names in (("end_to_end", cell.end_to_end),
                             ("layer_metrics", cell.per_layer)):
        say(directory, **{name: spec.reader(directory, name).read(run)
                          for name in names})

    phases = rounds.phase_seconds(run)
    idle = rounds.delta(run, "idle_s")
    say("phases", seconds=phases, idle_s=idle, window_s=run.window_s,
        phases_plus_idle_over_window=(sum(phases.values()) + idle)
        / run.window_s, rounds=rounds.delta(run, "count"),
        host_ms=run.counters["after"]["rounds"]["host_ms"])
    gaps = [1e3 * s for _, s in run.device_trace.gaps if s >= 0.5e-3]
    say("round_host", round_host_ms=rounds.round_host_ms(run),
        device_gaps_over_half_ms=len(gaps),
        device_gap_median_ms=float(np.median(gaps)) if gaps else None,
        device_gap_max_ms=max(gaps, default=None))
    inside = []
    for t in run.traces:
        by = {s["name"]: s["dur_s"] for s in t["spans"]}
        if "prefill" in by:
            inside.append(1e3 * (by["queue_wait"] + by["prefill"]))
    say("first_token", inside_p90_ms=readings.percentile(inside, 90),
        inside_requests=len(inside),
        ttft_p90_ms=readings.percentile(readings.ttfts_ms(run), 90),
        clients=len(readings.ttfts_ms(run)))
    say("width", mean_width_by_counters=rounds.mean_width(run),
        by_width=rounds.rounds_by_width(run),
        traced_programs={w: len(e) for w, e in
                         readings.paged_programs(run).items()},
        feed_fill_pct=rounds.feed_fill_pct(run),
        live_pages_per_round=rounds.live_pages_per_round(run),
        warmup=run.counters["after"].get("warmup"))
    if trace_reduce.KEEP_DIR:
        gap_dump.main([trace_reduce.KEEP_DIR])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
