"""What a run observed, handed to the metric readers; and how its numbers
are printed."""

from __future__ import annotations

import dataclasses
import json


@dataclasses.dataclass
class Request:
    """One request as its client saw it (host clock, `perf_counter`)."""
    session: int
    turn: int
    due: float                      # when it was to be sent
    issued: float = 0.0             # when it was
    prompt: list = None
    asked: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)   # of each token
    done: float = 0.0
    error: str = None
    status: str = "open"            # ok | failed | cancelled


@dataclasses.dataclass
class Run:
    cell: object                    # spec.Cell
    chips: int
    peaks: dict                     # published peaks of one chip
    setup_s: float = 0.0
    t0: float = 0.0                 # the window, on the host's clock
    t_end: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    lateness_s: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    traces: list = dataclasses.field(default_factory=list)  # the program's
    step_ends: list = dataclasses.field(default_factory=list)
    tokens_per_step: int = 0
    model: object = None            # the program's configuration
    n_params: int = 0
    job: object = None              # generators.TrainJob
    device_trace: object = None     # trace_reduce.Reduction, traced runs

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t_end

    def issued_in_window(self):
        return [r for r in self.requests
                if self.in_window(r.due) and r.status != "cancelled"]


def say(what: str, **fields) -> None:
    """One earlier line of output: a name and its numbers, unrounded."""
    print(f"benchmark {what}: " + json.dumps(fields, default=float),
          flush=True)
