"""Whole runs on the CPU at the toy sizes the data files keep: a well-formed
last line with no device metric; no result without a TPU; a new cell added as
files alone."""

import json
import shutil

import pytest

from conftest import run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELLS = ["gpt2-large.chat", "gpt2-large.sessions-sat", "gpt2-medium.train"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_ends_in_a_well_formed_line_without_metrics(root, cell,
                                                              trace):
    code, out, err = run_cell(root, "--workload", cell, "--seed",
                              "3000000019", "--seconds", "2", "--trace",
                              str(trace), "--tiny")
    assert code == 0, err[-3000:]
    last = json.loads(out[-1])
    assert KEYS <= set(last) and last["rehearsal"] is True
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metrics"] == {}            # no number under a device's name
    assert last["device"]["platform"] == "cpu"
    compared = [json.loads(line.split(": ", 1)[1]) for line in out
                if line.startswith("benchmark compared: ")]
    assert len(compared) >= 4 and all("limit" in c for c in compared)


def test_without_a_tpu_there_is_no_result(root):
    code, out, err = run_cell(root, "--workload", CELLS[0], "--seed", "1",
                              "--seconds", "1", "--trace", "0")
    assert code == 2
    assert "needs 1 TPU chip" in err
    assert not any(line.startswith("{") for line in out)


def test_a_directory_without_the_program_gives_no_result(root, tmp_path):
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code, out, err = run_cell(tmp_path, "--workload", CELLS[0], "--seed",
                              "1", "--seconds", "1", "--trace", "0")
    assert code != 0 and not any(line.startswith("{") for line in out)


def test_a_new_cell_is_added_as_files_and_entries_alone(root, tmp_path):
    """A throw-away configuration, mix, cell and per-layer metric, each a new
    file beside the old ones and an entry in `BENCHMARK.json`; no file that
    was there is edited."""
    shutil.copytree(root / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "deeplearning4j_tpu").symlink_to(root / "deeplearning4j_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    base = tmp_path / "benchmark"
    config = json.loads((base / "configs/gpt2-large-serve.json").read_text())
    config["tiny"]["n_layer"] = 1
    (base / "configs/throwaway.json").write_text(json.dumps(config))
    mix = json.loads((base / "traffic/chat.json").read_text())
    mix["tiny"]["rate_per_s"] = 6.0
    (base / "traffic/throwaway-mix.json").write_text(json.dumps(mix))
    (base / "layer_metrics/throwaway_requests.py").write_text(
        'NAME, UNIT, BETTER = "throwaway_requests", "requests", "higher"\n'
        'LAYER, MOVES, SOURCE = "LM scheduler", "tpot_p95_ms", '
        '"program_counter"\n\n\n'
        'def read(run):\n    return float(len(run.requests))\n')
    bench["configs"].append({
        "name": "throwaway", "source": "test", "reduced": [],
        "file": "benchmark/configs/throwaway.json", "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.cell", "config": "throwaway",
        "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p95_ms"):
            m["workloads"].append("throwaway.cell")
    bench["per_layer"].append({
        "name": "throwaway_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "LM scheduler",
        "moves": "tpot_p95_ms", "workloads": ["throwaway.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    code, out, err = run_cell(tmp_path, "--workload", "throwaway.cell",
                              "--seed", "5", "--seconds", "2", "--trace",
                              "1", "--tiny")
    assert code == 0, err[-3000:]
    last = json.loads(out[-1])
    assert last["correct"] is True and last["attempted"] > 0
    assert all(p.read_bytes() == data for p, data in before.items())

    from benchmark import spec
    cell = spec.load_cell("throwaway.cell", tiny=True, root=tmp_path)
    assert cell.config["n_layer"] == 1
    assert "throwaway_requests" in cell.per_layer
    reader = spec.reader("layer_metrics", "throwaway_requests", tmp_path)
    assert reader.MOVES in cell.end_to_end
