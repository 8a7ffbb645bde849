"""The reduction and the readers' patterns on a piece of a real trace: two
rounds of the wide step program of `gpt2-large.chat` on a TPU v5e
(`data/chat_two_rounds.json.gz`; names cut to 150 characters).  What the
reducer gives is held against sums worked out here another way."""

import gzip
import json
import pathlib

import numpy as np
import pytest

from benchmark import readings, trace_reduce
from benchmark.observe import Run

DATA = pathlib.Path(__file__).parent / "data" / "chat_two_rounds.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        raw = json.load(f)

    def events(rows):
        return [trace_reduce.Event(n, s * 1e-9, d * 1e-9) for n, s, d in rows]

    device = trace_reduce.DeviceTrace("/device:TPU:0", events(raw["ops"]),
                                      events(raw["modules"]))
    window = trace_reduce.Event(trace_reduce.WINDOW_SPAN, 0.0,
                                raw["window_ns"] * 1e-9)
    return raw, trace_reduce.reduce([device], [window])


def test_busy_time_is_the_union_of_the_operations(recorded):
    raw, red = recorded
    ticks = np.zeros(raw["window_ns"] // 1000 + 2, bool)    # microseconds
    for _, start, dur in raw["ops"]:
        ticks[start // 1000:(start + dur) // 1000 + 1] = True
    assert red.busy_s == pytest.approx(ticks.sum() * 1e-6, rel=0.02)
    assert red.window_s == pytest.approx(raw["window_ns"] * 1e-9)
    assert 0.0 < red.idle_share() < 0.15
    # a few operations overlap their neighbour without being nested in it
    assert sum(red.op_self_s.values()) == pytest.approx(red.busy_s, rel=0.01)


def test_the_paged_kernel_is_found_and_gives_the_programs_width(recorded):
    raw, red = recorded
    run = Run(cell=None, chips=1, peaks=None, device_trace=red)
    programs = readings.paged_programs(run)
    assert list(programs) == [8]                 # the wide program, 2 rounds
    assert len(programs[8]) == 2
    kernel = [d for n, _, d in raw["ops"]
              if " custom-call(s32[" in n and n.startswith("%step.")]
    assert len(kernel) == 2 * 36                 # one call a layer a round
    assert readings.op_seconds(run, readings.PAGED_KERNEL) == pytest.approx(
        sum(kernel) * 1e-9)
    assert readings.mean_ms(programs[8]) == pytest.approx(
        np.mean([d for _, _, d in raw["modules"]]) * 1e-6)


def test_the_breakdown_adds_the_layers_up_under_one_name(recorded):
    _, red = recorded
    ops = dict(map(tuple, red.breakdown()["device_ops"]))
    kernel = "custom-call:step bf16[16,8,20,64]"
    assert kernel in ops
    # copies of the whole pool and of its per-layer slices lead the list:
    # the step rebuilds the pool (ROADMAP S4)
    pool = [k for k in ops if "1025,16,20,64]" in k or "[16400,20,64]" in k]
    assert sum(ops[k] for k in pool) > 3 * ops[kernel]
