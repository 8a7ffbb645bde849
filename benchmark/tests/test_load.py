"""The open loop: a request is timed from when it was due, the generator's
lateness is reported, a late answer fails, the backlog of a mix that cancels
is neither attempted nor failed."""

import time

import numpy as np

from benchmark import generators, load


class SlowLM:
    """Stands in for the program's server: one token every `gap` seconds,
    one request at a time."""

    def __init__(self, gap):
        import threading

        self.gap, self.lock = gap, threading.Lock()

    def generate_stream(self, prompt, max_new, timeout=None):
        deadline = time.perf_counter() + timeout
        with self.lock:
            for i in range(max_new):
                time.sleep(self.gap)
                if time.perf_counter() > deadline:
                    raise TimeoutError("too late")
                yield (prompt[-1] + i) % 100


def schedule(n, every, drain, drain_s):
    sessions = [generators.Session(i, i * every, np.zeros(0, np.int32), [
        generators.Turn(np.array([i, i + 1], np.int32), 4, 0.0)])
        for i in range(n)]
    return generators.Schedule(sessions, 64, 0.0, drain, drain_s)


def test_ttft_counts_from_due_time_and_lateness_is_reported():
    offer = load.Offer(SlowLM(0.02), schedule(6, 0.01, "finish", 5.0), 0.3)
    marks = []
    offer.run(lambda: marks.append("start"), lambda: marks.append("open"),
              lambda: marks.append("end"))
    assert marks[0] == "start" and marks[-1] == "end"
    assert len(offer.lateness_s) == 6 and min(offer.lateness_s) >= 0
    reqs = sorted(offer.requests, key=lambda r: r.due)
    assert [r.status for r in reqs] == ["ok"] * 6
    # requests queue behind one another: the last waited for five others,
    # and that wait is charged to it because it is timed from its due time
    ttft = [r.times[0] - r.due for r in reqs]
    assert ttft[-1] > ttft[0] + 4 * 4 * 0.02 * 0.8
    assert all(r.issued >= r.due for r in reqs)


def test_a_late_answer_fails_and_a_cancelled_backlog_is_neither():
    slow = load.Offer(SlowLM(0.05), schedule(4, 0.01, "finish", 0.1), 0.2)
    slow.run(lambda: None, lambda: None, lambda: None)
    assert sorted(r.status for r in slow.requests).count("failed") >= 1
    sat = load.Offer(SlowLM(0.05), schedule(4, 0.01, "cancel", 0.0), 0.3)
    sat.run(lambda: None, lambda: None, lambda: None)
    statuses = [r.status for r in sat.requests]
    assert "cancelled" in statuses and "failed" not in statuses
