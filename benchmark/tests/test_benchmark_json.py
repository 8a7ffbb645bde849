"""`BENCHMARK.json` against the contract's format and against the files it
names: every metric has a reader that declares the same unit, direction,
source, layer and `moves`."""

import json
import re

import pytest

from benchmark import spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric_has_its_reader(metric):
    reader = spec.reader("end_to_end", metric["name"])
    assert (reader.NAME, reader.UNIT, reader.BETTER, reader.SOURCE) == (
        metric["name"], metric["unit"], metric["better"], metric["source"])
    assert UNIT.match(metric["unit"])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_its_reader_and_moves_a_reported_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    reader = spec.reader("layer_metrics", metric["name"])
    assert (reader.NAME, reader.UNIT, reader.BETTER, reader.SOURCE,
            reader.LAYER, reader.MOVES) == (
        metric["name"], metric["unit"], metric["better"], metric["source"],
        metric["layer"], metric["moves"])
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    for name in metric["workloads"]:
        cell = spec.load_cell(name)
        assert metric["moves"] in cell.end_to_end
        assert metric["name"] in cell.per_layer


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_metric_and_a_layer(cell):
    loaded = spec.load_cell(cell["name"])
    assert "setup_s" in loaded.end_to_end and len(loaded.end_to_end) >= 2
    assert loaded.per_layer
    assert spec.driver(loaded.config) and spec.adapter(loaded.config)


def test_run_py_and_the_drivers_name_no_cell_config_mix_or_metric():
    words = [w["name"] for w in BENCH["workloads"]]
    words += [c["name"] for c in BENCH["configs"]]
    words += [w["traffic"] for w in BENCH["workloads"]]
    words += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    here = spec.HERE
    files = [here / "run.py", here / "spec.py", here / "load.py",
             here / "generators.py", *sorted((here / "drivers").glob("*.py"))]
    for f in files:
        text = f.read_text()
        found = [w for w in words if re.search(
            rf"(?<![\w.\-]){re.escape(w)}(?![\w.\-])", text)]
        assert not found, (f.name, found)
