"""The readers of the program's inside view (round counters, the `prefill`
span, warm-up by program) on a tiny run; the trace readers of the mesh cell
on a made-up trace whose sums are known; `paged_kernel_roofline` on the
recorded piece of a real trace; `gap_dump` on made-up spans; and a `--tiny`
run of the four-chip cell on four virtual CPU devices."""

import argparse
import gzip
import json
import os
import pathlib
import subprocess
import sys
import time
import types

import pytest

from benchmark import flops, readings, rounds, spec, trace_reduce
from benchmark.observe import Run
from benchmark.trace_reduce import DeviceTrace, Event

DATA = pathlib.Path(__file__).parent / "data" / "chat_two_rounds.json.gz"
COUNTER_READERS = ["round_host_ms.chat", "round_host_ms.sat",
                   "feed_fill.chat", "feed_fill.sat", "warmup_s",
                   "prefill_p90_ms"]


def read(metric, run):
    return spec.reader("layer_metrics", metric).read(run)


@pytest.fixture(scope="module")
def tiny_chat():
    """The chat cell's driver at toy size on the CPU: what the readers are
    handed, less the device trace."""
    import jax

    cell = spec.load_cell("gpt2-large.chat", tiny=True)
    args = argparse.Namespace(seed=11, seconds=2.0, trace=0, tiny=True)
    run, checks, attempted, failed, _ = spec.driver(cell.config).run(
        cell, args, time.perf_counter(), jax.devices()[:1])
    assert attempted > 0 and failed == 0
    assert all(value <= limit for _, value, limit in checks)
    return run


@pytest.mark.parametrize("metric", COUNTER_READERS)
def test_counter_and_span_readers_find_their_numbers(tiny_chat, metric):
    value = read(metric, tiny_chat)
    assert value is not None and value > 0.0
    if metric.startswith("feed_fill"):
        assert value <= 100.0


def test_the_inside_view_adds_up(tiny_chat):
    run = tiny_chat
    assert rounds.delta(run, "count") == readings.counter_delta(
        run, "dispatches")
    phases = rounds.phase_seconds(run)
    assert len(phases) == 8 and rounds.SYNC in phases
    # phases and idle waits partition the worker's time: the window's
    # length, but for the phase that was open when each snapshot was cut
    assert sum(phases.values()) + rounds.delta(run, "idle_s") == pytest.approx(
        run.window_s, abs=0.2)
    assert 1.0 <= rounds.mean_width(run) <= 8.0
    spans = [{s["name"]: s["dur_s"] for s in t["spans"]}
             for t in run.traces]
    assert spans and all({"queue_wait", "prefill", "decode"} <= set(s)
                         for s in spans)
    assert all(s["prefill"] <= s["decode"] for s in spans)


def test_a_width_first_dispatched_inside_the_window_is_counted():
    def at(by_width):
        return {"rounds": {"by_width": by_width}}

    run = Run(cell=None, chips=1, peaks=None, counters={
        "before": at({"8": 40}), "after": at({"1": 19, "8": 523})})
    assert rounds.rounds_by_width(run) == {1: 19, 8: 483}
    assert rounds.mean_width(run) == pytest.approx((19 + 8 * 483) / 502)


@pytest.mark.parametrize("metric", COUNTER_READERS + [
    "paged_kernel_roofline", "collective_exposed_share", "mesh_step_ms",
    "idle_share.train-4chip"])
def test_a_program_without_the_counters_gives_nothing(metric):
    """The parent of the PR that brought them: `stats()` has no `rounds`
    and no `warmup`, the traces no `prefill`; no trace was taken."""
    run = Run(cell=None, chips=1, peaks={"hbm_bytes_per_s": 819e9},
              counters={"before": {"dispatches": 1}, "after":
                        {"dispatches": 9, "slots": 16}},
              traces=[{"spans": [{"name": "queue_wait", "dur_s": 0.1}]}])
    assert read(metric, run) is None


def test_paged_kernel_roofline_on_the_recorded_rounds():
    """Two wide rounds of `gpt2-large.chat` on a v5e, with counters that
    say 140 live pages a round: bytes over bandwidth over the kernel's
    recorded time."""
    with gzip.open(DATA, "rt") as f:
        raw = json.load(f)

    def events(rows):
        return [Event(n, s * 1e-9, d * 1e-9) for n, s, d in rows]

    red = trace_reduce.reduce(
        [DeviceTrace("/device:TPU:0", events(raw["ops"]),
                     events(raw["modules"]))],
        [Event(trace_reduce.WINDOW_SPAN, 0.0, raw["window_ns"] * 1e-9)])
    model = types.SimpleNamespace(n_layers=36, n_heads=20, head_dim=64)
    stats = {"slots": 16, "kv": {"page_size": 16, "max_pages_per_seq": 64},
             "kv_bytes": {"per_token": 2 * 36 * 20 * 64 * 2}}

    def at(rounds_, pages):
        return {**stats, "rounds": {"count": rounds_, "live_pages": pages}}

    run = Run(cell=None, chips=1, peaks={"hbm_bytes_per_s": 819e9},
              device_trace=red, model=model,
              counters={"before": at(100, 14_000), "after": at(600, 84_000)})
    kernel_s = sum(d for n, _, d in raw["ops"]
                   if " custom-call(s32[" in n and n.startswith("%step.")
                   ) * 1e-9 / 2
    least_s = 140 * 16 * 2 * 36 * 20 * 64 * 2 / 819e9
    assert flops.paged_hbm_bytes(36, 16, 140 / 16, 64, 16, 20, 64, 2,
                                 kernel=True) == pytest.approx(
        least_s * 819e9)
    got = read("paged_kernel_roofline", run)
    assert got == pytest.approx(100.0 * least_s / kernel_s, rel=1e-6)
    assert 0.0 < got < 100.0


def mesh_trace():
    """Two chips, two steps of 100 ms each of a `jit_step` program.  In each
    step: a `while` from 10 to 90 ms that holds a fusion 10-40, a
    collective-permute-start 40-41, a kernel 41-60 (the transfer rides
    behind it), a collective-permute-done 60-65; then an all-reduce 90-96
    outside the loop that a fusion 94-99 overlaps for 2 ms.  Exposed:
    1 + 5 + 4 = 10 ms of 100.  Chip 1 runs the same less the all-reduce:
    6 ms."""
    def step(t0, with_all_reduce):
        ms = 1e-3
        ops = [
            Event("%while.1 = (s32[], bf16[8,512]) while(%tuple.3)",
                  t0 + 10 * ms, 80 * ms),
            Event("%fusion.7 = bf16[8,512,1280] fusion(%p.1)",
                  t0 + 10 * ms, 30 * ms),
            Event("%collective-permute-start.2 = (bf16[4,512,10,64]) "
                  "collective-permute-start(%k.1)", t0 + 40 * ms, 1 * ms),
            Event("%checkpoint.4 = bf16[40,512,64] custom-call(%q.1)",
                  t0 + 41 * ms, 19 * ms),
            Event("%collective-permute-done.2 = bf16[4,512,10,64] "
                  "collective-permute-done(%collective-permute-start.2)",
                  t0 + 60 * ms, 5 * ms),
        ]
        if with_all_reduce:
            ops += [Event("%all-reduce.9 = f32[1280,5120] all-reduce("
                          "%fusion.8)", t0 + 90 * ms, 6 * ms),
                    Event("%fusion.11 = f32[1280] fusion(%p.2)",
                          t0 + 94 * ms, 5 * ms)]
        return ops

    devices = []
    for chip in range(2):
        ops = step(0.0, chip == 0) + step(0.1, chip == 0)
        devices.append(DeviceTrace(
            f"/device:TPU:{chip}", sorted(ops, key=lambda e: e.start),
            [Event("jit_step(123)", 0.0, 0.1), Event("jit_step(123)", 0.1,
                                                     0.1),
             Event("jit_norms(9)", 0.2, 0.001)]))
    return trace_reduce.reduce(devices, [Event(trace_reduce.WINDOW_SPAN,
                                               0.0, 0.25)])


def test_the_mesh_cells_trace_readers_on_a_made_up_trace():
    run = Run(cell=None, chips=2, peaks=None, device_trace=mesh_trace())
    assert read("mesh_step_ms", run) == pytest.approx(100.0)
    assert read("collective_exposed_share", run) == pytest.approx(
        (10.0 + 6.0) / 2)
    # busy: chip 0 is 80 + 9 ms of each 100 ms step, chip 1 80 ms; the
    # window is 250 ms
    assert read("idle_share.train-4chip", run) == pytest.approx(
        100.0 * (1 - (0.178 + 0.160) / 2 / 0.25))


def test_gap_dump_names_the_spans_that_cover_a_gap():
    from benchmark.tools import gap_dump

    ms = 1e-3
    ops = [Event("%fusion.1 = f32[8] fusion()", 0.0, 90 * ms),
           Event("%fusion.2 = f32[8] fusion()", 94 * ms, 90 * ms),
           Event("%fusion.3 = f32[8] fusion()", 184.2 * ms, 10 * ms)]
    spans = [Event("lm:sync", 50 * ms, 40.5 * ms),
             Event("lm:fold", 90.5 * ms, 1.5 * ms),
             Event("lm:yield", 92 * ms, 0.5 * ms),
             Event("lm:admit", 92.5 * ms, 0.5 * ms),
             Event("lm:dispatch", 93 * ms, 2 * ms),
             Event("lm:paged[w8]", 93.2 * ms, 1.5 * ms)]
    lines = []
    total, named, by_name = gap_dump.report(
        [DeviceTrace("/device:TPU:0", ops, [])], spans,
        Event(trace_reduce.WINDOW_SPAN, 0.0, 200 * ms), min_s=0.5 * ms,
        out=lines.append)
    # the 4 ms gap is listed; the 0.2 ms one is under the threshold, the
    # tail after the last operation is nobody's
    assert total == pytest.approx((4 + 5.8) * ms)
    assert named == pytest.approx(4 * ms)
    assert by_name["lm:fold"] == pytest.approx(1.5 * ms)
    assert by_name["lm:dispatch"] == pytest.approx(1 * ms)
    assert by_name["lm:paged[w8]"] == pytest.approx(0.8 * ms)
    assert any("covered 100.0 %" in line for line in lines)


def test_tiny_run_of_the_four_chip_cell_on_virtual_devices(root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    done = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "gpt2-large.train-4chip", "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--tiny"], capture_output=True, text=True,
        timeout=600, env=env, cwd=root)
    assert done.returncode == 0, done.stderr[-3000:]
    out = done.stdout.strip().splitlines()
    last = json.loads(out[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["count"] == 4
    compared = {c["number"]: c for c in (
        json.loads(line.split(": ", 1)[1]) for line in out
        if line.startswith("benchmark compared: "))}
    assert {"compiles_in_window", "loss_gap_max",
            "first_grad_norm_gap_worst_leaf",
            "change_norm_gap_worst_leaf"} <= set(compared)
    # float32 at toy size: the sharded reference and the mesh trainer agree
    assert compared["first_grad_norm_gap_worst_leaf"]["value"] < 1e-4


def test_the_four_chip_cell_needs_its_devices(root, monkeypatch):
    from conftest import run_cell

    monkeypatch.setenv("XLA_FLAGS", "")         # one CPU device
    code, out, err = run_cell(root, "--workload", "gpt2-large.train-4chip",
                              "--seed", "1", "--seconds", "1", "--trace",
                              "0", "--tiny")
    assert code != 0 and "needs 4 devices" in err
    assert not any(line.startswith("{") for line in out)
