"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix. The configuration's
file is the ``file`` of its ``configs`` entry; the traffic mix is
``bench/traffic/<traffic>.json``. The configuration names its graph
generator (``bench/generators/<generator>.py``), the traffic names its
algorithm, whose plain reference is ``bench/reference/<algorithm>.py``,
and each metric is read by ``bench/metrics/<metric>.py``. Adding a
cell, configuration, traffic mix or metric therefore adds files and
entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

__all__ = ["Cell", "load_cell", "load_module"]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # BENCHMARK.json metric entries this cell reports
    per_layer: tuple
    root: Path

    def module(self, kind: str, name: str) -> ModuleType:
        """``bench/<kind>/<name>.py`` of this checkout."""
        return load_module(self.root / "bench" / kind / f"{name}.py")


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.parent.name}_{path.stem.replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(entries, cell: str) -> tuple:
    return tuple(e for e in entries
                 if "workloads" not in e or cell in e["workloads"])


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name), root=root)
