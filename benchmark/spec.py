"""What a run is made of, found by name: the cell in `BENCHMARK.json`, its
configuration's file, its traffic mix's file, the driver of the
configuration's kind, the family's adapter and plain reference, and one
reader for each metric.  Nothing here names a cell, a configuration, a mix
or a metric: a later PR adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file, as run
    traffic_name: str
    traffic: dict           # the mix's file
    end_to_end: tuple       # names of the end-to-end metrics it reports
    per_layer: tuple        # names of the per-layer metrics it may report


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = _merged(out[key], value) if both else value
    return out


def _with_tiny(data: dict, tiny: bool) -> dict:
    """A data file's `tiny` group laid over it for a CPU rehearsal, and
    taken out of what a chip run sees."""
    over = data.get("tiny", {})
    data = {k: v for k, v in data.items() if k != "tiny"}
    return _merged(data, over) if tiny else data


def load_cell(name: str, tiny: bool = False,
              root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / bench["paths"][0] / "traffic"
                      / f"{cell['traffic']}.json").read_text())

    def reported(metric):
        return name in metric.get("workloads", [name])

    e2e = tuple(m["name"] for m in bench["end_to_end"] if reported(m))
    layer = tuple(m["name"] for m in bench["per_layer"]
                  if reported(m) and m["moves"] in e2e)
    return Cell(name=name, chips=int(cell["chips"]),
                config_name=cell["config"], config=_with_tiny(config, tiny),
                traffic_name=cell["traffic"], traffic=_with_tiny(mix, tiny),
                end_to_end=e2e, per_layer=layer)


def driver(config: dict):
    return importlib.import_module(f"benchmark.drivers.{config['kind']}")


def adapter(config: dict):
    return importlib.import_module(f"benchmark.adapters.{config['adapter']}")


def reference(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def reader(directory: str, metric: str, root: pathlib.Path = ROOT):
    """The module `<paths[0]>/<directory>/<metric>.py`, loaded by its path:
    a metric's name may hold dots."""
    path = root / HERE.name / directory / f"{metric}.py"
    if not path.is_file():
        raise SystemExit(f"metric {metric!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{metric.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module
