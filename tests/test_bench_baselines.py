"""Unit tests for bench.py's baseline-pinning rules.

The pin file is the denominator of every vs_baseline ratio the judge
reads, so its invariants get their own tests: backend keying (a CPU run
must never ratio against a TPU pin), first-pin-wins and no_pin mechanical
rows — plus the harness's honesty rules: peaks come from one table keyed by
device_kind, and a row that raises fails the run.
"""

import importlib.util
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_mod",
                                                  REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_mod"] = spec.loader.exec_module(mod) or mod
    monkeypatch.setattr(mod, "REPO", tmp_path)  # never touch the real pins
    return mod


def _pins(tmp_path):
    p = tmp_path / ".bench_baseline.json"
    return json.loads(p.read_text())["pinned"] if p.exists() else {}


def test_canonical_run_pins_first_value(bench, tmp_path):
    rows = [{"metric": "m", "value": 100.0}]
    bench._apply_baselines(rows, canonical=True, backend="cpu")
    assert _pins(tmp_path)["m"] == {"cpu": 100.0}
    assert rows[0]["vs_baseline"] == 1.0


def test_pins_are_backend_keyed_and_never_cross(bench, tmp_path):
    bench._apply_baselines([{"metric": "m", "value": 100.0}],
                           canonical=True, backend="cpu")
    rows = [{"metric": "m", "value": 500.0}]
    bench._apply_baselines(rows, canonical=True, backend="tpu")
    # TPU value gets its OWN pin — not a 5x "speedup" over the CPU pin
    assert rows[0]["vs_baseline"] == 1.0
    assert _pins(tmp_path)["m"] == {"cpu": 100.0, "tpu": 500.0}


def test_existing_pin_is_never_overwritten(bench, tmp_path):
    bench._apply_baselines([{"metric": "m", "value": 100.0}],
                           canonical=True, backend="cpu")
    rows = [{"metric": "m", "value": 80.0}]
    bench._apply_baselines(rows, canonical=True, backend="cpu")
    assert _pins(tmp_path)["m"] == {"cpu": 100.0}
    assert rows[0]["vs_baseline"] == 0.8


def test_noncanonical_run_never_pins(bench, tmp_path):
    rows = [{"metric": "m", "value": 100.0}]
    bench._apply_baselines(rows, canonical=False, backend="cpu")
    assert _pins(tmp_path) == {}
    assert rows[0]["vs_baseline"] is None


def test_no_pin_rows_are_never_pinned_or_ratioed(bench, tmp_path):
    rows = [{"metric": "plumbing", "value": 0.17, "no_pin": True}]
    bench._apply_baselines(rows, canonical=True, backend="cpu")
    assert _pins(tmp_path) == {}
    assert rows[0]["vs_baseline"] is None


def test_cpu_pin_from_other_host_is_not_a_regression(bench, tmp_path,
                                                     monkeypatch):
    """CPU throughput scales with host cores: a pin from an N-core box
    must not read as a perf regression on an M-core box."""
    (tmp_path / ".bench_baseline.json").write_text(json.dumps({
        "pinned": {"m": {"cpu": 100.0}},
        "pin_hosts": {"m": {"cpu": 8}},
    }))
    monkeypatch.setattr(bench.os, "cpu_count", lambda: 1)
    rows = [{"metric": "m", "value": 41.0}]
    bench._apply_baselines(rows, canonical=True, backend="cpu")
    assert rows[0]["vs_baseline"] is None
    assert rows[0]["vs_pin_other_host"] == 0.41
    assert rows[0]["pin_host_cpus"] == 8


def test_legacy_cpu_pin_without_host_still_compares(bench, tmp_path):
    (tmp_path / ".bench_baseline.json").write_text(json.dumps({
        "pinned": {"m": {"cpu": 100.0}},
    }))
    rows = [{"metric": "m", "value": 90.0}]
    bench._apply_baselines(rows, canonical=True, backend="cpu")
    assert rows[0]["vs_baseline"] == 0.9


def test_tpu_pins_are_never_host_gated(bench, tmp_path, monkeypatch):
    (tmp_path / ".bench_baseline.json").write_text(json.dumps({
        "pinned": {"m": {"tpu": 100.0}},
        "pin_hosts": {"m": {"tpu": 8}},
    }))
    monkeypatch.setattr(bench.os, "cpu_count", lambda: 1)
    rows = [{"metric": "m", "value": 99.0}]
    bench._apply_baselines(rows, canonical=True, backend="tpu")
    assert rows[0]["vs_baseline"] == 0.99


def test_new_pin_records_host_cpus(bench, tmp_path, monkeypatch):
    monkeypatch.setattr(bench.os, "cpu_count", lambda: 4)
    bench._apply_baselines([{"metric": "m", "value": 10.0}],
                           canonical=True, backend="cpu")
    data = json.loads((tmp_path / ".bench_baseline.json").read_text())
    assert data["pin_hosts"]["m"]["cpu"] == 4


def test_peak_flops_come_from_one_table_and_an_unknown_device_is_an_error(
        bench, monkeypatch):
    import jax

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda: [Dev("TPU v5 lite")])
    assert bench._peak_flops() == bench.PEAK_BF16_FLOPS["TPU v5 lite"]
    monkeypatch.setattr(jax, "devices", lambda: [Dev("TPU v99")])
    with pytest.raises(KeyError, match="TPU v99"):
        bench._peak_flops()


def test_cpu_rows_carry_no_mfu(bench):
    assert bench._mfu_fields(1e12, 1.0, target=0.3) == {}


def test_run_suite_fails_when_a_requested_row_raises(bench, monkeypatch,
                                                     capsys):
    def broken():
        raise RuntimeError("row blew up")

    monkeypatch.setattr(bench, "BENCHES", {
        "good": lambda: {"metric": "g", "value": 1.0, "unit": "u"},
        "broken": broken})
    monkeypatch.setattr(bench, "ONLY", ["good", "broken"])
    # placed from outside: the helper then sets nothing in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/unused")
    assert bench.run_suite() == 1
    err = capsys.readouterr().err
    assert "platform=cpu" in err.splitlines()[0]     # names its device
    assert "RuntimeError: row blew up" in err
    monkeypatch.setattr(bench, "ONLY", ["good"])
    assert bench.run_suite() == 0
