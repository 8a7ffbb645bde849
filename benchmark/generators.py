"""The one general traffic generator.  A mix is a data file
(`traffic/<mix>.json`) naming a `kind` below and its parameters; the
generator turns it, a seed and a window length into a schedule.

Every seed gets the same work.  Lengths, gaps, turn counts and think times
are not drawn: each is the set of evenly spaced quantiles of its
distribution, as many as the window needs, shuffled.  The token ids always
come from the seed.  The order comes from the mix's `trace_seed` where the
file gives one, and then every seed replays one trace of arrivals and
lengths with other tokens and weights: with 70 requests in a window, which
long prompt meets which busy stretch moved a 90th percentile by 5-7 % from
seed to seed, and which sessions came first moved tokens per second by
4-6 %, while two runs of one seed agreed within 1 % (PERF.md section 5).
A mix without a `trace_seed` is shuffled by the run's seed: two seeds then
differ as two hours of the same traffic do.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def _points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / max(n, 1)


def lognormal_set(n, median, sigma, min, max) -> np.ndarray:  # noqa: A002
    """n whole numbers: the quantiles of a lognormal, clipped."""
    z = np.array([NormalDist().inv_cdf(q) for q in _points(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), min, max).astype(int)


def exponential_set(n, mean) -> np.ndarray:
    return -mean * np.log1p(-_points(n))


def geometric_set(n, mean) -> np.ndarray:
    """n whole numbers >= 1 with the given mean."""
    if mean <= 1:
        return np.ones(n, int)
    p = 1.0 / mean
    return np.maximum(1, np.ceil(np.log1p(-_points(n))
                                 / math.log1p(-p))).astype(int)


def zipf_set(n, count, s) -> np.ndarray:
    """n ranks in [0, count): rank r about n / (r+1)**s times."""
    weight = 1.0 / np.arange(1, count + 1) ** s
    share = n * weight / weight.sum()
    times = np.floor(share).astype(int)
    for r in np.argsort(-(share - times))[:n - times.sum()]:
        times[r] += 1
    return np.repeat(np.arange(count), times)


def arrivals(n_expected_per_s, span_s, order) -> np.ndarray:
    """Arrival times in [0, span_s) of a Poisson process whose rate is
    `n_expected_per_s`: the exponential's quantile set of gaps in the order
    `order` shuffles them into."""
    n = int(round(n_expected_per_s * span_s))
    times = np.cumsum(exponential_set(n, 1.0)[order(n)]) / n_expected_per_s
    return times[times < span_s]


@dataclasses.dataclass
class Turn:
    user: np.ndarray        # the new tokens of this turn's prompt
    max_new: int
    think_s: float          # after the answer ends, before the next turn


@dataclasses.dataclass
class Session:
    index: int
    arrival_s: float        # of its first turn, from the window's start
    prefix: np.ndarray      # what it shares with other sessions
    turns: list


@dataclasses.dataclass
class Schedule:
    sessions: list
    context_limit: int      # a turn that would pass it ends the session
    preroll_s: float        # offered before the window, counted as set-up
    drain: str              # after the window: "finish" or "cancel"
    drain_s: float


@dataclasses.dataclass
class TrainJob:
    batch: int
    seq: int
    vocab_size: int
    seed: int

    def batch_at(self, step: int):
        """(tokens, targets) [batch, seq] of the step: rows all differ."""
        rng = np.random.default_rng([abs(self.seed), step])
        rows = rng.integers(0, self.vocab_size, (self.batch, self.seq + 1),
                            dtype=np.int32)
        return rows[:, :-1], rows[:, 1:]


def _rngs(mix, seed, stream):
    """(order, ids): the generator that shuffles the mix's sets, and the one
    that draws token ids.  Only the second is always the run's seed's."""
    order = np.random.default_rng([abs(int(mix.get("trace_seed", seed))),
                                   stream])
    return order, np.random.default_rng([abs(seed), stream, 1])


def _schedule(mix, seconds, sessions, context_limit):
    return Schedule(sessions=sessions, context_limit=context_limit,
                    preroll_s=float(mix["preroll_s"]),
                    drain=mix["drain"], drain_s=float(mix["drain_s"]))


def _arrivals_around(mix, seconds, order):
    """Arrival times from the window's start: the pre-roll's (negative) and
    the window's.  Each part is a whole quantile set of its own, so the
    window of every seed holds the same number of arrivals and, drawn
    alongside, the same lengths: none is lost to the pre-roll."""
    pre = arrivals(mix["rate_per_s"], mix["preroll_s"], order)
    win = arrivals(mix["rate_per_s"], seconds, order)
    return pre - mix["preroll_s"], win


def dealt(values, hands: int, rng) -> list:
    """`values` dealt into `hands` hands that each span their whole range:
    sorted, cut into runs of `hands` neighbours, one of each run to each
    hand as the seed decides; then each hand shuffled."""
    values = np.sort(np.asarray(values))
    out = [[] for _ in range(hands)]
    for i in range(0, len(values), hands):
        run = values[i:i + hands]
        for v, h in zip(run, rng.permutation(hands)[:len(run)]):
            out[h].append(v)
    return [rng.permutation(np.array(h, values.dtype)) for h in out]


def _block_arrivals(count: int, length: float, rng) -> np.ndarray:
    """`count` arrivals inside a block of `length` seconds: the exponential's
    quantile gaps in the order `rng` gives, scaled so the block is filled."""
    gaps = rng.permutation(exponential_set(count + 1, 1.0))
    return length * np.cumsum(gaps[:count]) / gaps.sum()


def open_loop_requests(mix, seed, seconds, vocab_size, max_len):
    """Independent requests, nothing shared: one-turn sessions.

    Not a Poisson process: the window is cut into blocks of about `block_s`
    seconds and every block is given the same traffic, a fixed count of
    arrivals (its share of `rate_per_s`) and of the window's prompt and
    answer lengths one from every stretch of their range (`dealt`).  Inside a
    block the gaps are the exponential's quantiles in the trace's order, so
    arrivals bunch as Poisson's do over seconds; over tens of seconds the
    load is even, which real traffic is not.  The order decides which lengths
    and when, but no order can pile the long prompts into one part of the
    window: with Poisson gaps over the whole window, runs of one mix
    differed from seed to seed ten times as much as two runs of one seed did
    (PERF.md section 5).  Bursts belong to a mix and a cell of their own
    (PERF.md section 7)."""
    rng, ids = _rngs(mix, seed, 1)
    empty = np.zeros(0, np.int32)
    out = []

    def add(at, prompts, outputs):
        for t, p, o in zip(at, prompts, outputs):
            out.append(Session(len(out), float(t), empty, [Turn(
                ids.integers(0, vocab_size, int(p), dtype=np.int32),
                int(o), 0.0)]))

    def lengths(n):
        return (lognormal_set(n, **mix["prompt_tokens"]),
                lognormal_set(n, **mix["output_tokens"]))

    n_pre = int(round(mix["rate_per_s"] * mix["preroll_s"]))
    add(_block_arrivals(n_pre, mix["preroll_s"], rng) - mix["preroll_s"],
        *(rng.permutation(x) for x in lengths(n_pre)))
    blocks = max(1, int(round(seconds / mix["block_s"])))
    prompts, outputs = (dealt(x, blocks, rng) for x in lengths(
        int(round(mix["rate_per_s"] * seconds))))
    for b in range(blocks):
        at = _block_arrivals(len(prompts[b]), seconds / blocks, rng)
        add(at + b * seconds / blocks, prompts[b], outputs[b])
    return _schedule(mix, seconds, out, max_len)


def sessions(mix, seed, seconds, vocab_size, max_len):
    """Chat sessions over a few shared system prompts: each turn's prompt is
    the system prompt, the whole history and a new user message."""
    rng, ids = _rngs(mix, seed, 2)
    sp = mix["system_prompts"]
    systems = [ids.integers(0, vocab_size, sp["tokens"], dtype=np.int32)
               for _ in range(sp["count"])]
    out = []
    for at in _arrivals_around(mix, seconds, rng.permutation):
        n = len(at)
        which = rng.permutation(zipf_set(n, sp["count"], sp["zipf_s"]))
        n_turns = rng.permutation(geometric_set(n, mix["turns_mean"]))
        total = int(n_turns.sum())
        user = rng.permutation(lognormal_set(total, **mix["user_tokens"]))
        answer = rng.permutation(lognormal_set(total,
                                               **mix["answer_tokens"]))
        think = rng.permutation(exponential_set(total, mix["think_s_mean"]))
        t = 0
        for i in range(n):
            turns = [Turn(ids.integers(0, vocab_size, user[j],
                                       dtype=np.int32),
                          int(answer[j]), float(think[j]))
                     for j in range(t, t + n_turns[i])]
            t += n_turns[i]
            out.append(Session(len(out), float(at[i]), systems[which[i]],
                               turns))
    return _schedule(mix, seconds, out, min(mix["context_limit"], max_len))


def train_job(mix, seed, seconds, vocab_size, max_len):
    if mix["seq"] > max_len:
        raise ValueError(f"seq {mix['seq']} passes the model's {max_len}")
    return TrainJob(batch=mix["batch"], seq=mix["seq"],
                    vocab_size=vocab_size, seed=seed)


KINDS = {f.__name__: f for f in (open_loop_requests, sessions, train_job)}


def build(mix: dict, seed: int, seconds: float, vocab_size: int,
          max_len: int):
    if mix["kind"] not in KINDS:
        raise ValueError(f"traffic kind {mix['kind']!r}: the generator "
                         f"knows {sorted(KINDS)}")
    return KINDS[mix["kind"]](mix, int(seed), float(seconds),
                              int(vocab_size), int(max_len))
