"""Choose a serve mix's `trace_seed` on the CPU: the scheduler modelled round
by round, 40 orders of the mix played through it, and the orders ranked by how
near their modelled judged metrics lie to the median of the 40.

    python3 benchmark/tools/round_model.py <workload> --w1-ms 2.3 \
        --wide-ms 3.6 --gap-ms 3.0 [--page-us 6.2] [--rate R] [--seconds S] \
        [--orders 40]

The rule that chose every `trace_seed` in `traffic/`: the order nearest the
median stands for the mix; if a set of runs on the chip spreads over half the
metric's bound, the next-nearest is taken, and no other parameter moves.  The
mix's file records the arguments and the rank taken under `trace_seed_is`,
and `tests/test_round_model.py` finds the same order from them.

What is modelled (`deeplearning4j_tpu/serving/lm.py`, as its comments say it):
requests are admitted to free lanes in the order they were due, each granted
the pages of its whole prompt and answer, the radix tree's full prompt pages
matched first (the system prompt, and a session's earlier prompt while it has
not been evicted; least recently used goes first); a round is wide while some
lane has a whole chunk of prompt left, and then every prefilling lane feeds up
to a chunk and every decoding lane one token; otherwise every lane feeds one;
the round that feeds a prompt's last token yields the first answer token.  A
round costs its program's time (`--w1-ms` / `--wide-ms`, a traced run's
`jit_step` means), `--page-us` for each live page its attention reads, and
the gap to the next round (`--gap-ms`); a client sees the round's tokens when
it ends.  Not modelled: the host's jitter, what the clients' threads cost the
scheduler, a round's time against its lanes in use.  So the model ranks
orders; its absolute numbers are not the chip's and are never written under a
metric's name.  It imports no JAX and nothing of the program.
"""

from __future__ import annotations

import argparse
import dataclasses
import heapq
import json
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

ORDERS = 40


class Tree:
    """The radix tree's full prompt pages, as counts: one entry a system
    prompt and one a session (its pages beyond the system prompt's), each
    with the time it was last matched and how many lanes pin it."""

    def __init__(self):
        self.pages, self.used, self.pins = {}, {}, {}

    def held(self) -> int:
        return sum(self.pages.values())

    def touch(self, key, t, pin):
        self.used[key] = t
        self.pins[key] = self.pins.get(key, 0) + pin

    def evict(self, need: int) -> int:
        """Free up to `need` pages, sessions before the system prompts they
        hang from, least recently used first; returns the pages freed."""
        freed = 0
        loose = sorted((k for k in self.pages if not self.pins.get(k)),
                       key=lambda k: (k[0] == "sys", self.used[k]))
        for key in loose:
            if freed >= need:
                break
            if key[0] == "sys" and any(
                    k[0] == "sess" and k[2] == key[1] for k in self.pages):
                continue
            take = min(self.pages[key], need - freed)
            freed += take
            self.pages[key] -= take
            if not self.pages[key]:
                del self.pages[key]
        return freed


@dataclasses.dataclass(slots=True)
class Lane:
    due: float
    sess: int
    turn: int
    prompt: int             # tokens of the prompt, and how many are fed
    fed: int
    left: int               # answer tokens still to come
    own: int                # pages only this lane holds
    keys: list              # the tree's entries it pins
    pos: int
    first: float = None     # when its first token was seen
    times: list = dataclasses.field(default_factory=list)


def simulate(schedule, seconds, lanes, chunk, page, pages, w1_s, wide_s,
             gap_s, page_s):
    """Play `schedule` through the modelled server.  Returns the requests as
    (due, first-token time or None, token times) and nothing else."""
    t_end = float(seconds)
    cancel = schedule.drain == "cancel"
    limit = schedule.context_limit
    sessions = schedule.sessions
    history = [0] * len(sessions)          # tokens after the system prompt
    arrivals = [(s.arrival_s, s.index, 0) for s in sessions]
    heapq.heapify(arrivals)
    queue, active, done = [], [], []
    tree, own_total = Tree(), 0
    t = arrivals[0][0] if arrivals else 0.0

    def tree_keys(i):
        """The tree's entries for session `i`: its system prompt's, or None
        where it has none, and its own."""
        prefix = sessions[i].prefix
        sys_key = ("sys", prefix.tobytes()) if len(prefix) >= page else None
        return sys_key, ("sess", i, sys_key and sys_key[1])

    def offer(due, i, n):
        sess = sessions[i]
        if n >= len(sess.turns) or due >= t_end:
            return
        turn = sess.turns[n]
        plen = len(sess.prefix) + history[i] + len(turn.user)
        if plen + turn.max_new > limit:
            return
        heapq.heappush(arrivals, (due, i, n))

    def seat(due, i, n):
        """A lane for the request, or None while the pool cannot give its
        pages."""
        nonlocal own_total
        sess, turn = sessions[i], sessions[i].turns[n]
        plen = len(sess.prefix) + history[i] + len(turn.user)
        total = -(-(plen + turn.max_new) // page)
        sys_key, sess_key = tree_keys(i)
        keys, matched = [], 0
        if sys_key in tree.pages:
            keys.append(sys_key)
            matched = tree.pages[sys_key]
        if sess_key in tree.pages and (sys_key is None or keys):
            keys.append(sess_key)
            matched += tree.pages[sess_key]
        matched = min(matched, (plen - 1) // page)
        need = total - matched
        for key in keys:                    # what the plan matched is pinned
            tree.touch(key, t, 1)
        free = pages - tree.held() - own_total
        if free < need:
            free += tree.evict(need - free)
        if free < need:
            for key in keys:
                tree.touch(key, t, -1)
            return None
        own_total += need
        return Lane(due=due, sess=i, turn=n, prompt=plen, fed=matched * page,
                    left=turn.max_new, own=need, keys=keys,
                    pos=matched * page)

    def prompt_done(lane):
        """Its full prompt pages go to the tree; what was the lane's own is
        now the tree's."""
        nonlocal own_total
        sys_pages = len(sessions[lane.sess].prefix) // page
        sys_key, sess_key = tree_keys(lane.sess)
        full = lane.prompt // page
        before = sum(tree.pages.get(k, 0) for k in (sys_key, sess_key))
        if sys_key and sys_key not in tree.pages:
            tree.pages[sys_key] = sys_pages
        if full > sys_pages:
            tree.pages[sess_key] = max(tree.pages.get(sess_key, 0),
                                       full - sys_pages)
        for key in (sys_key, sess_key):
            if key in tree.pages and key not in lane.keys:
                lane.keys.append(key)
                tree.touch(key, t, 1)
        moved = min(lane.own, sum(tree.pages.get(k, 0) for k in (
            sys_key, sess_key)) - before)
        lane.own -= moved
        own_total -= moved

    def finish(lane, now):
        nonlocal own_total
        own_total -= lane.own
        for key in lane.keys:
            tree.touch(key, now, -1)
        sess = sessions[lane.sess]
        turn = sess.turns[lane.turn]
        history[lane.sess] += len(turn.user) + turn.max_new
        done.append((lane.due, lane.first, lane.times))
        offer(now + turn.think_s, lane.sess, lane.turn + 1)

    while True:
        while arrivals and arrivals[0][0] <= t:
            queue.append(heapq.heappop(arrivals))
        while queue and len(active) < lanes:
            lane = seat(*queue[0])
            if lane is None:
                break
            queue.pop(0)
            active.append(lane)
        if cancel and t >= t_end:
            break
        if not active:
            if not arrivals:
                break
            t = max(t, arrivals[0][0])
            continue
        wide = any(l.prompt - l.fed >= chunk for l in active)
        width = chunk if wide else 1
        live = 0
        for l in active:
            f = min(l.prompt - l.fed, width) if l.fed < l.prompt else 1
            live += -(-(l.pos + f) // page)
        t += (wide_s if wide else w1_s) + page_s * live + gap_s
        still = []
        for l in active:
            if l.fed < l.prompt:
                f = min(l.prompt - l.fed, width)
                l.fed += f
                l.pos += f
                if l.fed < l.prompt:
                    still.append(l)
                    continue
                prompt_done(l)
                l.first = t
            else:
                l.pos += 1
            l.times.append(t)
            l.left -= 1
            if l.left:
                still.append(l)
            else:
                finish(l, t)
        active = still
    for l in active:                        # cancelled at the window's end
        done.append((l.due, l.first, l.times))
    return done


def metrics(done, seconds):
    """The modelled end-to-end metrics, under the benchmark's names: a
    ranking's inputs, not readings."""
    from benchmark.readings import percentile

    inside = [d for d in done if 0 <= d[0] < seconds]
    ttft = [1e3 * (first - due) for due, first, _ in inside
            if first is not None]
    gaps = [1e3 * (b - a) for _, _, times in inside
            for a, b in zip(times, times[1:])]
    tokens = sum(0 <= x < seconds for _, _, times in done for x in times)
    return {"ttft_p90_ms": percentile(ttft, 90),
            "tpot_p95_ms": percentile(gaps, 95),
            "serve_tokens_per_s": tokens / seconds,
            "ttft_p50_ms": percentile(ttft, 50), "requests": len(inside)}


def rank(mix, judged, seconds, orders=ORDERS, max_len=1024, **server):
    """([(trace_seed, distance, metrics)] nearest first, the medians): the
    distance is the root of the summed squares of each judged metric's share
    off the median of the orders."""
    from benchmark import generators

    rows = []
    for order in range(orders):
        schedule = generators.build({**mix, "trace_seed": order}, 0, seconds,
                                    64, max_len)
        rows.append((order, metrics(simulate(schedule, seconds, **server),
                                    seconds)))
    middle = {name: statistics.median(m[name] for _, m in rows)
              for name in judged}
    out = [(order, sum((m[name] / middle[name] - 1.0) ** 2
                       for name in judged) ** 0.5, m) for order, m in rows]
    return sorted(out, key=lambda row: (row[1], row[0])), middle


def server_of(config, args) -> dict:
    """The modelled server's sizes: the configuration's lanes, the
    program's defaults for the rest (page 16, chunk 8, 64 pages a lane)
    unless the configuration's `serve` group names them."""
    serve = config["serve"]
    lanes = int(serve["slots"])
    page = int(serve.get("page_size", 16))
    return {"lanes": lanes, "chunk": int(serve.get("prefill_chunk", 8)),
            "page": page,
            "pages": int(serve.get("pages") or lanes * 1024 // page),
            "w1_s": args["w1_ms"] / 1e3, "wide_s": args["wide_ms"] / 1e3,
            "gap_s": args["gap_ms"] / 1e3,
            "page_s": args.get("page_us", 0.0) / 1e6}


def choose(workload, args, root=None):
    """The ranking for a cell of `BENCHMARK.json` under `args` (`w1_ms`,
    `wide_ms`, `gap_ms`, and optionally `page_us`, `rate_per_s`, `seconds`,
    `orders`): what `main` prints and the test compares."""
    from benchmark import spec

    root = root or spec.ROOT
    cell = spec.load_cell(workload, root=root)
    window = json.loads((root / "BENCHMARK.json").read_text())["run_seconds"]
    mix = dict(cell.traffic)
    if args.get("rate_per_s"):
        mix["rate_per_s"] = float(args["rate_per_s"])
    judged = [n for n in ("ttft_p90_ms", "tpot_p95_ms", "serve_tokens_per_s")
              if n in cell.end_to_end]
    return rank(mix, judged, float(args.get("seconds", window)),
                int(args.get("orders", ORDERS)),
                int(cell.config.get("n_positions", 1024)),
                **server_of(cell.config, args))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--w1-ms", type=float, required=True)
    ap.add_argument("--wide-ms", type=float, required=True)
    ap.add_argument("--gap-ms", type=float, required=True)
    ap.add_argument("--page-us", type=float, default=0.0)
    ap.add_argument("--rate", type=float, default=None, dest="rate_per_s")
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window; BENCHMARK.json's run_seconds")
    ap.add_argument("--orders", type=int, default=ORDERS)
    args = {k: v for k, v in vars(ap.parse_args(argv)).items()
            if v is not None}
    ranking, middle = choose(args.pop("workload"), args)
    print("round_model median " + json.dumps(middle))
    for order, distance, m in ranking:
        print("round_model " + json.dumps(
            {"trace_seed": order, "distance": distance, **m}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
