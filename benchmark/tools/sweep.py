"""Find a serve mix's knee, once, on the chip: offer the mix at each of a few
fixed rates to one server and print what came back.

    python3 benchmark/tools/sweep.py <workload> <seconds> <rate> [<rate> ...] \
        [--seed N]

The rule (one for every serve mix; `benchmark/README.md`, "The knee, and
finding it again"): a rate SUSTAINS while at least 99 % of the requests issued
in the window complete, the backlog at the window's end (issued, not yet
finished) is under the lane count, and no request is queued at that moment.
The knee lies between the highest rate that sustains and the first that does
not, and that first failing rate must also show the lanes over 90 % occupied
or a queue: a backlog that reaches the lane count with lanes to spare and
nothing queued is Little's law meeting the rule (6 lanes hold 6 turns at any
rate), not the server giving out, and the sweep goes on.  A rate whose
arrivals left the generator more than 5 ms late at the median was not offered
and counts for nothing.  The cell's rate is then written into the mix's file
as a number: 0.8 of the knee where tails are judged, 1.3 where tokens per
second are.

The mix is offered as the cell offers it: cut into blocks and ordered by its
`trace_seed`, with its own pre-roll; a drain of up to 30 s takes the place of
the mix's own.  The sweep stops after the second rate whose backlog passes
twice the lanes: two rates over the knee show whether tokens per second has
reached its plateau (1.3 and 1.6 of the knee have to agree within 2 %).
Beside each rate: lane occupancy, the worker's share of the window with no
lane active, a round's mean wall and host time and its phases (ms a round:
where a run's pace comes from), the requests in the server at each fifth of
the window (steady from its first second, or the pre-roll is too short), and
the generator's lateness; a reading the program's counters do not give is
null, never 0.  `--seed` makes the weights (1 without it): the same rate in
several processes, a seed each, shows what differs between runs of one cell.
Not part of a run: `run.py` never searches for a rate.
"""

from __future__ import annotations

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

COMPLETED = 0.99        # share of the window's requests that must finish
OCCUPIED_PCT = 90.0     # lanes in use, or a queue, at the first failing rate
LATE_MS = 5.0           # the generator's median lateness that voids a rate


def in_server(run, t) -> int:
    """Requests due by `t` and not finished by then: in a lane or queued."""
    return sum(r.due < t and (r.status != "ok" or r.done > t)
               for r in run.requests)


def in_server_by_fifth(run) -> list:
    """`in_server` at the window's start, each fifth of it and its end: a
    window that opens on a server in steady state reads alike all through
    (under the knee), or rises from a first number that is already high."""
    return [in_server(run, run.t0 + k * run.window_s / 5) for k in range(6)]


def per_round_ms(seconds: dict, rounds):
    """{phase: ms a round} and their sum, the round's wall while lanes were
    active; (None, None) where the program counts no rounds or phases."""
    if not rounds or not seconds or None in seconds.values():
        return None, None
    phases = {p: 1e3 * s / rounds for p, s in seconds.items()}
    return phases, sum(phases.values())


def sustains(row: dict, lanes: int) -> bool:
    return (row["completed_share"] >= COMPLETED
            and row["backlog_at_end"] < lanes
            and row["queue_depth_at_end"] == 0)


def verdict(row: dict, lanes: int) -> str:
    """`sustains`, `fails`, `littles_law` (the rule is met with lanes to
    spare and nothing queued: no knee) or `not_offered`."""
    if row["late_ms"]["p50"] > LATE_MS:
        return "not_offered"
    if sustains(row, lanes):
        return "sustains"
    if (row["lane_occupancy_pct"] > OCCUPIED_PCT
            or row["queue_depth_at_end"] > 0
            or row["completed_share"] < COMPLETED):
        return "fails"
    return "littles_law"


def knee(rows, lanes: int):
    """(highest rate that sustains, first that fails above it) by the rule
    above, either None where the rows do not bracket the knee."""
    below = above = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        word = verdict(row, lanes)
        if word == "sustains" and above is None:
            below = row["rate_per_s"]
        elif word == "fails" and above is None:
            above = row["rate_per_s"]
    return below, above


def main(argv) -> int:
    import argparse

    import numpy as np

    from benchmark import device, generators, load, readings, rounds, spec
    from benchmark.observe import Run
    from deeplearning4j_tpu.ui import UiServer

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("seconds", type=float)
    ap.add_argument("rates", type=float, nargs="+")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell, seconds = spec.load_cell(args.workload), args.seconds
    device.acquire(cell.chips, tiny=False)
    config = cell.config
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=False)
    params = adapter.make_params(cfg, args.seed, config["dtype"])
    srv = UiServer(port=0)
    srv.serve_lm(cfg, params, **config["serve"])
    srv.start()
    lm = srv.state.lm_server
    rows, overloaded = [], 0
    try:
        lm.warmup()
        for n, rate in enumerate(args.rates):
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
            ttft, gaps = readings.ttfts_ms(run), readings.token_gaps_ms(run)
            late = 1e3 * np.asarray(offer.lateness_s)
            idle_s = rounds.delta(run, "idle_s")
            phases, wall = per_round_ms(rounds.phase_seconds(run),
                                        rounds.delta(run, "count"))
            row = {"rate_per_s": rate, "seed": args.seed,
                   "issued": len(issued),
                   "completed_share": len(done) / max(len(issued), 1),
                   "backlog_at_end": in_server(run, run.t_end),
                   "queue_depth_at_end": marks["b"]["queue_depth"],
                   "tokens_per_s": readings.tokens_in_window(run) / seconds,
                   "ttft_ms": [readings.percentile(ttft, q)
                               for q in (50, 90, 99)],
                   "tpot_ms": [readings.percentile(gaps, q)
                               for q in (50, 95, 99)],
                   "lane_occupancy_pct": readings.lane_occupancy_pct(run),
                   "worker_idle_pct": (None if idle_s is None
                                       else 100.0 * idle_s / seconds),
                   "round_wall_ms": wall,
                   "round_host_ms": rounds.round_host_ms(run),
                   "round_phase_ms": phases,
                   "rounds_by_width": rounds.rounds_by_width(run),
                   "in_server_by_fifth": in_server_by_fifth(run),
                   "pages_in_use_at_end": marks["b"]["kv"]["pages_in_use"],
                   "late_ms": {"p50": float(np.median(late)),
                               "max": float(np.max(late))}}
            row["verdict"] = verdict(row, lm.n_slots)
            rows.append(row)
            print("sweep " + json.dumps(row), flush=True)
            overloaded += row["backlog_at_end"] > 2 * lm.n_slots
            if overloaded == 2:
                break
        below, above = knee(rows, lm.n_slots)
        print("sweep " + json.dumps({"lanes": lm.n_slots,
                                     "highest_sustained": below,
                                     "first_failing": above}), flush=True)
    finally:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
