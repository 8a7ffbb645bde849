"""The reduction from a profiler trace to busy time, idle gaps and kernel
sums, on a trace small enough to work out by hand."""

import re

import pytest

from benchmark import trace_reduce

# one device, times in microseconds from the window's start:
#   program A  [0, 400)   ops: fusion [0,100)  while [100,400) holding
#                               kernel [120,220) and kernel [240,340)
#   idle       [400, 500)  the benchmark's own span "bench:feed" covers it
#   program A  [500, 700)  ops: fusion [500,700)
#   idle       [700, 1000) nothing of the benchmark's: unattributed
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000000 }
    events { metadata_id: 1 offset_ps: 500000000 duration_ps: 200000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 100000000 duration_ps: 300000000 }
    events { metadata_id: 4 offset_ps: 120000000 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 240000000 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 500000000 duration_ps: 200000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(123)" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "while.2" } }
  event_metadata { key: 4 value { id: 4 name: "paged_attn_kernel.3" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 390000000 duration_ps: 120000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "bench:feed" } }
}
"""


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return trace_reduce.reduce(*trace_reduce.planes_to_events(profile))


def test_busy_idle_and_window(reduction):
    assert reduction.window_s == pytest.approx(1000e-6)
    assert reduction.busy_s == pytest.approx(600e-6)
    assert reduction.idle_share() == pytest.approx(0.4)


def test_own_time_of_nested_operations(reduction):
    own = reduction.op_self_s
    assert own["paged_attn_kernel.3"] == pytest.approx(200e-6)
    assert own["while.2"] == pytest.approx(100e-6)      # 300 less its kernels
    assert own["fusion.1"] == pytest.approx(300e-6)
    assert sum(own.values()) == pytest.approx(reduction.busy_s)


def test_gaps_are_named_by_the_benchmarks_own_spans(reduction):
    gaps = sorted(reduction.gaps, key=lambda g: -g[1])
    assert gaps[0] == ("unattributed", pytest.approx(300e-6))
    assert gaps[1] == ("feed", pytest.approx(100e-6))
    out = reduction.breakdown()
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(300e-6)]
    assert out["idle_gaps"][0][0] == "unattributed"
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_program_events_and_kernel_sums_for_the_readers(reduction):
    from benchmark import readings
    from benchmark.observe import Run

    run = Run(cell=None, chips=1, peaks=None, device_trace=reduction)
    programs = readings.step_programs(run)
    assert [len(p) for p in programs] == [2]
    assert readings.mean_ms(programs[0]) == pytest.approx(0.3)
    kernel = re.compile("paged_attn")
    assert readings.op_seconds(run, kernel) == pytest.approx(200e-6)
    first = programs[0][:1]
    assert readings.op_seconds(run, kernel, within=first) == pytest.approx(
        200e-6)
    assert readings.op_seconds(run, kernel, within=programs[0][1:]) == 0.0
    assert readings.idle_share_pct(run) == pytest.approx(40.0)


def test_train_mfu_is_the_steps_device_time_whatever_the_window(reduction):
    import types

    from benchmark import flops, spec
    from benchmark.generators import TrainJob
    from benchmark.observe import Run

    model = types.SimpleNamespace(n_layers=2, d_model=64)
    peaks = {"bf16_flops_per_s": 1e12}
    read = spec.reader("layer_metrics", "train_mfu").read
    want = 100.0 * 128 * flops.train_flops_per_token(model, 1000, 32) / (
        300e-6 * 1e12)                      # the two steps last 400 and 200
    for window_s in (1.0, 7.0):             # the profiler's start and stop
        run = Run(cell=None, chips=1, peaks=peaks, device_trace=reduction,
                  model=model, n_params=1000, tokens_per_step=128,
                  job=TrainJob(4, 32, 512, 0), t0=0.0, t_end=window_s,
                  step_ends=[0.5, window_s])
        assert read(run) == pytest.approx(want)
    run.device_trace = None
    assert read(run) is None


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert trace_reduce.reduce([], []) is None
