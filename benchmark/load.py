"""Offer a schedule to the served model in process, open loop: one thread per
session, each request timed from when it was due, every token stamped as the
client's thread receives it.  One process, few busy threads: the generator
sleeps between arrivals and a client sleeps between tokens.
"""

from __future__ import annotations

import threading
import time

from benchmark.observe import Request

POLL_S = 0.2


class Offer:
    """`schedule` offered to `lm` (the program's `ContinuousLMServer`) around
    a window of `seconds` that starts once the pre-roll has been offered."""

    def __init__(self, lm, schedule, seconds: float):
        self.lm = lm
        self.schedule = schedule
        self.seconds = float(seconds)
        self.requests = []
        self.lateness_s = []
        self._lock = threading.Lock()
        self._threads = []
        self.t0 = self.t_end = 0.0

    def _limit(self) -> float:
        """When a request stops being waited for: the window's end plus the
        mix's drain."""
        return self.t_end + self.schedule.drain_s

    def _ask(self, req: Request) -> None:
        req.issued = time.perf_counter()
        try:
            stream = self.lm.generate_stream(
                req.prompt, req.asked,
                timeout=max(0.05, self._limit() - req.issued))
            for token in stream:
                req.times.append(time.perf_counter())
                req.tokens.append(int(token))
        except Exception as e:  # noqa: BLE001 — a request's failure is a count, not the run's
            req.error = f"{type(e).__name__}: {e}"
        req.done = time.perf_counter()
        if req.error is None and len(req.tokens) == req.asked:
            req.status = "ok"
        elif self.schedule.drain == "cancel" and req.done >= self.t_end:
            req.status = "cancelled"    # the backlog at the window's end
        else:
            req.status = "failed"
        with self._lock:
            self.requests.append(req)

    def _session(self, sess) -> None:
        history = []
        due = self.t0 + sess.arrival_s
        for n, turn in enumerate(sess.turns):
            prompt = [*sess.prefix.tolist(), *history, *turn.user.tolist()]
            if len(prompt) + turn.max_new > self.schedule.context_limit:
                return
            if due >= self.t_end:
                return
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            req = Request(session=sess.index, turn=n, due=due, prompt=prompt,
                          asked=turn.max_new)
            self._ask(req)
            if req.status != "ok":
                return
            history = prompt[len(sess.prefix):] + req.tokens
            due = req.done + turn.think_s

    def _generate(self) -> None:
        for sess in sorted(self.schedule.sessions, key=lambda s: s.arrival_s):
            due = self.t0 + sess.arrival_s
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.lateness_s.append(time.perf_counter() - due)
            t = threading.Thread(target=self._session, args=(sess,),
                                 daemon=True, name=f"client-{sess.index}")
            t.start()
            self._threads.append(t)

    def run(self, at_start, while_open, at_end) -> None:
        """Offer the pre-roll, then the window.  `at_start()` and `at_end()`
        are called on this thread at the window's two ends, `while_open()`
        every `POLL_S` in between; all three are the driver's readings and
        do no device work."""
        self.t0 = time.perf_counter() + self.schedule.preroll_s + 0.05
        self.t_end = self.t0 + self.seconds
        generator = threading.Thread(target=self._generate, daemon=True,
                                     name="generator")
        generator.start()
        time.sleep(max(0.0, self.t0 - time.perf_counter()))
        at_start()
        while time.perf_counter() < self.t_end - POLL_S:
            time.sleep(POLL_S)
            while_open()
        time.sleep(max(0.0, self.t_end - time.perf_counter()))
        at_end()
        generator.join(self.schedule.drain_s + 30)
        for t in self._threads:
            t.join(max(0.1, self._limit() + 30 - time.perf_counter()))
        alive = [t.name for t in [generator, *self._threads] if t.is_alive()]
        if alive:
            raise RuntimeError(f"client threads did not end: {alive[:5]}")
