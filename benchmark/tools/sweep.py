"""Find a serve mix's knee, once, on the chip: offer the mix at each of a few
fixed rates to one server and print what came back.

    python3 benchmark/tools/sweep.py <workload> <seconds> <rate> [<rate> ...]

The knee is the highest rate at which at least 99 % of the requests issued in
the window complete and the backlog at the window's end (issued, not yet
finished) stays under the lane count.  The cell's rate is then written into
the mix's file as a number: 0.8 of the knee where tails are judged, 1.3 where
tokens per second are.  Every rate gets the mix's own pre-roll and a drain of
up to 30 s; the sweep stops at the first rate whose backlog passes twice the
lanes.  Not part of a run: `run.py` never searches for a rate.
"""

from __future__ import annotations

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(argv) -> int:
    import numpy as np

    from benchmark import device, generators, load, spec
    from benchmark.observe import Run
    from benchmark import readings
    from deeplearning4j_tpu.ui import UiServer

    cell = spec.load_cell(argv[0])
    seconds = float(argv[1])
    devices = device.acquire(cell.chips, tiny=False)
    config = cell.config
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=False)
    params = adapter.make_params(cfg, 1, config["dtype"])
    srv = UiServer(port=0)
    srv.serve_lm(cfg, params, **config["serve"])
    srv.start()
    lm = srv.state.lm_server
    try:
        lm.warmup()
        for n, rate in enumerate(float(a) for a in argv[2:]):
            mix = {**cell.traffic, "rate_per_s": rate, "drain": "finish",
                   "drain_s": 30.0}
            schedule = generators.build(mix, 100 + n, seconds,
                                        cfg.vocab_size, cfg.max_len)
            offer = load.Offer(lm, schedule, seconds)
            marks = {}
            offer.run(lambda: marks.update(a=lm.stats()), lambda: None,
                      lambda: marks.update(b=lm.stats()))
            run = Run(cell=cell, chips=1, peaks=None, t0=offer.t0,
                      t_end=offer.t_end, requests=offer.requests,
                      counters={"before": marks["a"], "after": marks["b"]})
            issued = run.issued_in_window()
            done = [r for r in issued if r.status == "ok"]
            backlog = sum(r.due < run.t_end and (
                r.status != "ok" or r.done > run.t_end)
                for r in run.requests)
            ttft, gaps = readings.ttfts_ms(run), readings.token_gaps_ms(run)
            row = {"rate_per_s": rate, "issued": len(issued),
                   "completed_share": len(done) / max(len(issued), 1),
                   "backlog_at_end": backlog,
                   "queue_depth_at_end": marks["b"]["queue_depth"],
                   "tokens_per_s": readings.tokens_in_window(run) / seconds,
                   "ttft_ms": [readings.percentile(ttft, q)
                               for q in (50, 90, 99)],
                   "tpot_ms": [readings.percentile(gaps, q)
                               for q in (50, 95, 99)],
                   "lane_occupancy_pct": readings.lane_occupancy_pct(run),
                   "late_ms_max": 1e3 * float(np.max(offer.lateness_s))}
            print("sweep " + json.dumps(row), flush=True)
            if backlog > 2 * lm.n_slots:
                break
    finally:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
