"""The traffic generator: the same seed gives the same bytes; another seed
the same work, at the same times where the mix fixes its trace and in
another order where it does not."""

import json

import numpy as np
import pytest

from benchmark import generators, spec

SEEDS = (0, 7, 3_000_000_019)       # one past 2**31, as the driver's are


def mix(name, tiny=False):
    path = spec.HERE / "traffic" / f"{name}.json"
    return spec._with_tiny(json.loads(path.read_text()), tiny)


def flat(schedule):
    """Everything a schedule would send, as bytes."""
    parts = []
    for s in schedule.sessions:
        parts += [np.float64(s.arrival_s).tobytes(), s.prefix.tobytes()]
        for t in s.turns:
            parts += [t.user.tobytes(), np.int64(t.max_new).tobytes(),
                      np.float64(t.think_s).tobytes()]
    return b"".join(parts)


def shape(schedule):
    """A schedule's trace: when, and how long, whatever the tokens."""
    return [(s.arrival_s, len(s.prefix),
             [(len(t.user), t.max_new, t.think_s) for t in s.turns])
            for s in schedule.sessions]


@pytest.mark.parametrize("name", ["chat", "sessions-sat"])
def test_a_trace_seed_fixes_the_order_and_the_seed_the_tokens(name):
    m = mix(name)
    assert "trace_seed" in m
    a, b = (generators.build(m, s, 30.0, 50304, 1024) for s in SEEDS[1:])
    assert shape(a) == shape(b)
    assert flat(a) != flat(b)
    other = generators.build({**m, "trace_seed": m["trace_seed"] + 1},
                             SEEDS[1], 30.0, 50304, 1024)
    assert shape(other) != shape(a)


@pytest.mark.parametrize("name", ["chat", "sessions-sat"])
def test_same_seed_same_bytes_other_seed_same_work(name):
    """Without a `trace_seed` the run's seed orders the sets."""
    free = {k: v for k, v in mix(name).items() if k != "trace_seed"}
    built = {s: generators.build(free, s, 30.0, 50304, 1024)
             for s in SEEDS}
    assert len({json.dumps(shape(b)) for b in built.values()}) == len(SEEDS)
    again = generators.build(free, SEEDS[-1], 30.0, 50304, 1024)
    assert flat(built[SEEDS[-1]]) == flat(again)
    assert len({flat(b) for b in built.values()}) == len(SEEDS)

    def work(schedule):
        turns = [t for s in schedule.sessions for t in s.turns]
        return (len(schedule.sessions),
                sorted(len(t.user) for t in turns),
                sorted(t.max_new for t in turns))

    assert len({json.dumps(work(b)) for b in built.values()}) == 1


def test_chat_lengths_follow_the_file():
    m = mix("chat")
    sched = generators.build(m, 1, 200.0, 50304, 1024)
    prompts = [len(s.turns[0].user) for s in sched.sessions]
    outs = [s.turns[0].max_new for s in sched.sessions]
    assert min(prompts) >= m["prompt_tokens"]["min"]
    assert max(prompts) <= m["prompt_tokens"]["max"]
    assert abs(np.median(prompts) - m["prompt_tokens"]["median"]) <= 3
    assert abs(np.median(outs) - m["output_tokens"]["median"]) <= 2
    span = m["preroll_s"] + 200.0
    assert abs(len(sched.sessions) - m["rate_per_s"] * span) <= 2
    at = np.array([s.arrival_s for s in sched.sessions])
    assert at.min() >= -m["preroll_s"] and at.max() < 200.0
    assert all(0 <= t < 50304 for s in sched.sessions[:5]
               for t in s.turns[0].user)


def test_every_block_of_a_blocked_mix_holds_the_same_traffic():
    m = mix("chat")
    for seed in SEEDS:
        sched = generators.build(m, seed, 50.0, 50304, 1024)
        window = [s for s in sched.sessions if s.arrival_s >= 0]
        assert len(window) == round(m["rate_per_s"] * 50.0)
        blocks = [[s for s in window
                   if b * 10.0 <= s.arrival_s < (b + 1) * 10.0]
                  for b in range(5)]
        assert [len(b) for b in blocks] == [round(m["rate_per_s"] * 10)] * 5
        longest = [max(len(s.turns[0].user) for s in b) for b in blocks]
        # each block got one of the five longest prompts, and one of the
        # five shortest
        assert min(longest) >= sorted(len(s.turns[0].user)
                                      for s in window)[-5]
        tokens = [sum(len(s.turns[0].user) for s in b) for b in blocks]
        assert max(tokens) < 1.25 * min(tokens)


BLOCKED = sorted(p.stem for p in (spec.HERE / "traffic").glob("*.json")
                 if "block_s" in json.loads(p.read_text()))


@pytest.mark.parametrize("name", BLOCKED)
def test_a_blocked_mix_gives_every_block_a_whole_number_of_arrivals(name):
    """`rate_per_s` times `block_s` is a whole number, and the window the
    cells run is a whole number of blocks: otherwise the blocks' counts
    differ and the load is no longer even."""
    m = mix(name)
    per_block = m["rate_per_s"] * m["block_s"]
    assert per_block == pytest.approx(round(per_block), abs=1e-9)
    assert round(per_block) >= 1
    seconds = json.loads((spec.ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    assert seconds % m["block_s"] == 0
    sched = generators.build(m, 7, float(seconds), 50304, 1024)
    at = np.array([s.arrival_s for s in sched.sessions])
    counts = np.histogram(at[at >= 0], bins=np.arange(
        0, seconds + m["block_s"], m["block_s"]))[0]
    assert set(counts) == {round(per_block)}


def test_session_arrivals_are_the_rate_times_the_span():
    m = mix("sessions-sat")
    sched = generators.build(m, 2, 50.0, 50304, 1024)
    at = np.array([s.arrival_s for s in sched.sessions])
    # the last of a set's gaps may end past the span
    assert 0 <= round(m["rate_per_s"] * 50.0) - np.sum(at >= 0) <= 2
    assert 0 <= round(m["rate_per_s"] * m["preroll_s"]) - np.sum(at < 0) <= 2
    assert at.min() >= -m["preroll_s"] and at.max() < 50.0


def test_sessions_share_system_prompts_by_popularity():
    m = mix("sessions-sat")
    sched = generators.build(m, 3, 120.0, 50304, 1024)
    prefixes = [s.prefix.tobytes() for s in sched.sessions]
    counts = sorted((prefixes.count(p) for p in set(prefixes)), reverse=True)
    assert len(counts) == m["system_prompts"]["count"]
    assert counts[0] > 2 * counts[3] > 0            # Zipf, s = 1
    turns = [len(s.turns) for s in sched.sessions]
    assert abs(np.mean(turns) - m["turns_mean"]) < 0.3
    assert sched.drain == "cancel" and sched.context_limit == 928


def test_train_job_rows_all_differ_and_repeat():
    job = generators.build(mix("pretrain-b16s1024"), SEEDS[-1], 10.0, 50304,
                           1024)
    tok, tgt = job.batch_at(0)
    assert tok.shape == tgt.shape == (16, 1024)
    assert (tok[:, 1:] == tgt[:, :-1]).all()
    assert len({r.tobytes() for r in tok}) == 16
    assert (job.batch_at(0)[0] == tok).all()
    assert not (job.batch_at(1)[0] == tok).all()


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError, match="traffic kind"):
        generators.build({"kind": "replay"}, 0, 1.0, 10, 10)
