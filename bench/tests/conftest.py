"""Fixtures for the benchmark's tests, run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) to which configurations and cells of a few hundred
vertices were added the way a later change would add them: new files
and new entries, no edit to a file that was there. Each tiny cell is
added to the ``workloads`` of every metric entry whose cells run its
algorithm, so it reports what the chip cells of that algorithm report.
"""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIGS = {
    "tiny-urand": {"graph_seed": 0, "generator": "urand", "scale": 8,
                   "degree": 4},
    "tiny-kron": {"graph_seed": 0, "generator": "kronecker", "scale": 8,
                  "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19},
}
TINY_CELLS = {
    "tiny-urand-bfs": ("tiny-urand", "bfs-64-keys"),
    "tiny-kron-bfs": ("tiny-kron", "bfs-64-keys"),
    "tiny-urand-pr": ("tiny-urand", "pagerank-gap"),
}


def copy_benchmark(dest: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dest / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "tests"))
    return dest


def algorithm(root: Path, traffic: str) -> str:
    """The algorithm that the traffic mix ``traffic`` runs."""
    return json.loads((root / "bench" / "traffic" / f"{traffic}.json")
                      .read_text())["algorithm"]


def add_entries(root: Path, configs=(), workloads=(), per_layer=(),
                end_to_end=()) -> None:
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"] += list(configs)
    bench["workloads"] += list(workloads)
    bench["per_layer"] += list(per_layer)
    bench["end_to_end"] += list(end_to_end)
    path.write_text(json.dumps(bench, indent=1))


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    root = copy_benchmark(tmp_path)
    configs, cells = [], []
    for name, cfg in TINY_CONFIGS.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        configs.append({"name": name, "source": "test", "reduced": [],
                        "file": f"bench/configs/{name}.json", "why": "test"})
    for name, (config, traffic) in TINY_CELLS.items():
        cells.append({"name": name, "config": config, "traffic": traffic,
                      "chips": 1, "why": "test"})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    runs = {w["name"]: algorithm(root, w["traffic"])
            for w in bench["workloads"]}
    for entry in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in entry:
            algs = {runs[c] for c in entry["workloads"]}
            entry["workloads"] += [c for c, (_, traffic) in TINY_CELLS.items()
                                   if algorithm(root, traffic) in algs]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_entries(root, configs=configs, workloads=cells)
    return root


def run_tiny(root: Path, cell: str, seed: int = 7, seconds: float = 0.2,
             trace: bool = False, **kw) -> dict:
    from bench.harness import run_cell
    from bench.spec import load_cell
    return run_cell(load_cell(root, cell), seed, seconds, trace,
                    t0=time.perf_counter(), require_tpu=False,
                    log=lambda *a: None, **kw)
