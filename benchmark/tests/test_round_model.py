"""`tools/round_model.py`: the scheduler modelled round by round on the CPU,
which chooses a serve mix's `trace_seed`; and `tools/sweep.py`'s rule for the
knee.  Neither needs JAX or the program to be imported."""

import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark import generators, spec
from benchmark.tools import round_model, sweep

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
RATED = [w["name"] for w in BENCH["workloads"]
         if "trace_seed_is" in spec.load_cell(w["name"]).traffic]
SERVER = dict(lanes=4, chunk=8, page=16, pages=64, w1_s=0.002, wide_s=0.004,
              gap_s=0.003, page_s=0.0)


def test_the_model_imports_no_jax_and_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.tools import round_model, sweep; "
            "round_model.choose('gpt2-large.chat', dict(w1_ms=2, wide_ms=4, "
            "gap_ms=3, seconds=2, orders=2)); "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith('deeplearning4j_tpu')]; assert not bad, bad"
            % str(spec.ROOT))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_re_rated_cell_records_how_its_trace_was_chosen():
    assert "gpt2-large.chat" in RATED


@pytest.mark.parametrize("workload", RATED)
def test_the_model_finds_the_files_trace_seed_from_the_files_numbers(
        workload):
    mix = spec.load_cell(workload).traffic
    chosen = mix["trace_seed_is"]
    ranking, middle = round_model.choose(workload, chosen["args"])
    assert len(ranking) == chosen["of"] == round_model.ORDERS
    assert ranking[chosen["rank"]][0] == mix["trace_seed"]
    assert [d for _, d, _ in ranking] == sorted(d for _, d, _ in ranking)
    assert set(middle) == set(chosen["judged"])


def sessions_mix():
    path = spec.HERE / "traffic" / "sessions-sat.json"
    return spec._with_tiny(json.loads(path.read_text()), True)


def test_every_token_of_a_finished_schedule_is_delivered_once():
    mix = {**sessions_mix(), "drain": "finish", "drain_s": 30.0}
    schedule = generators.build(mix, 5, 4.0, 64, 128)
    done = round_model.simulate(schedule, 4.0, **SERVER)
    first_turns = sum(s.arrival_s < 4.0 for s in schedule.sessions)
    assert len(done) >= first_turns
    for due, first, times in done:
        assert first is not None and first > due
        assert times == sorted(times) and times[0] == first
    asked = sorted(t.max_new for s in schedule.sessions for t in s.turns)
    got = sorted(len(times) for _, _, times in done)
    # every answer delivered whole; later turns than were due in the window
    # are not asked
    assert all(n in asked for n in got)


def test_a_cancelled_backlog_stops_at_the_windows_end():
    mix = {**sessions_mix(), "rate_per_s": 40.0}
    schedule = generators.build(mix, 5, 3.0, 64, 128)
    assert schedule.drain == "cancel"
    done = round_model.simulate(schedule, 3.0, **SERVER)
    last = max(t for _, _, times in done for t in times)
    assert last < 3.0 + 0.02
    m = round_model.metrics(done, 3.0)
    # four lanes, a token a lane a round of 5 ms at the least
    assert 0 < m["serve_tokens_per_s"] <= 4 / 0.005


def test_a_shared_prefix_is_prefilled_once():
    """Two one-turn sessions over one system prompt: the second feeds only
    what the tree does not hold, so its first token comes rounds sooner."""
    prefix = np.arange(64, dtype=np.int32)

    def session(i, at):
        return generators.Session(i, at, prefix, [generators.Turn(
            np.arange(8, dtype=np.int32), 4, 0.0)])

    schedule = generators.Schedule([session(0, 0.0), session(1, 1.0)], 128,
                                   0.0, "finish", 30.0)
    done = sorted(round_model.simulate(schedule, 2.0, **SERVER))
    first, second = (f - due for due, f, _ in done)
    # 72 tokens in 9 wide rounds against 8 in one
    assert first == pytest.approx(9 * 0.007) and second == pytest.approx(0.007)


ROW = {"completed_share": 1.0, "backlog_at_end": 3, "queue_depth_at_end": 0,
       "lane_occupancy_pct": 50.0, "late_ms": {"p50": 0.2, "max": 3.0}}


@pytest.mark.parametrize("change, word", [
    ({}, "sustains"),
    ({"backlog_at_end": 16, "lane_occupancy_pct": 96.0}, "fails"),
    ({"backlog_at_end": 9, "queue_depth_at_end": 2}, "fails"),
    ({"completed_share": 0.9}, "fails"),
    # the backlog reaches the lanes with lanes to spare and nothing queued
    ({"backlog_at_end": 6, "lane_occupancy_pct": 58.0}, "littles_law"),
    ({"late_ms": {"p50": 7.0, "max": 90.0}}, "not_offered"),
])
def test_the_knee_rule(change, word):
    lanes = 6 if word == "littles_law" else 16
    assert sweep.verdict({**ROW, **change}, lanes) == word


def test_the_knee_lies_between_the_last_sustained_and_the_first_failing():
    def row(rate, **change):
        return {**ROW, "rate_per_s": rate, **change}

    rows = [row(2.0), row(4.0),
            row(6.0, backlog_at_end=17, lane_occupancy_pct=70.0),
            row(8.0, backlog_at_end=30, queue_depth_at_end=14,
                lane_occupancy_pct=99.0), row(10.0, completed_share=0.5)]
    # 6.0 meets the old rule by Little's law alone: it is neither
    assert sweep.knee(rows, 16) == (4.0, 8.0)
    assert sweep.knee(rows[:2], 16) == (4.0, None)


def test_a_reading_the_counters_do_not_give_is_none_not_nought():
    assert sweep.per_round_ms({}, 10) == (None, None)
    assert sweep.per_round_ms({"sync": 1.0, "fold": None}, 10) == (None, None)
    assert sweep.per_round_ms({"sync": 1.0}, None) == (None, None)
    phases, wall = sweep.per_round_ms({"sync": 0.5, "fold": 0.25}, 100)
    assert phases == {"sync": 5.0, "fold": 2.5} and wall == 7.5
