"""Fixtures for the benchmark's tests, run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) to which configurations and cells of a few hundred
vertices were added the way a later change would add them: new files
and new entries, no edit to a file that was there. Its PageRank cell
brings the PageRank metrics, whose readers, traffic mix and reference
are in ``bench/`` and which no chip cell reports yet.
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

# PageRank's metrics, as the cell that measures it on the chip would add them
PR_END_TO_END = [{"name": "pr_iter_ms", "unit": "ms", "better": "lower",
                  "bound": 0.01, "source": "host_clock",
                  "workloads": ["tiny-urand-pr"]}]
PR_PER_LAYER = [
    {"name": name, "unit": "%", "better": better, "source": "device_trace",
     "layer": layer, "moves": "pr_iter_ms", "workloads": ["tiny-urand-pr"]}
    for name, better, layer in (
        ("pr_roofline", "higher", "exchange backend"),
        ("device_idle.pr", "lower", "device"))]


def copy_benchmark(dest: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dest / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "tests"))
    return dest


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
    for entry in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in entry:
            entry["workloads"] += [c for c in TINY_CELLS if c.endswith("bfs")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_entries(root, end_to_end=PR_END_TO_END, per_layer=PR_PER_LAYER)
    add_entries(root, configs=configs, workloads=cells)
    return root


def run_tiny(root: Path, cell: str, seed: int = 7, seconds: float = 0.2,
             trace: bool = False, **kw) -> dict:
    from bench.harness import run_cell
    from bench.spec import load_cell
    return run_cell(load_cell(root, cell), seed, seconds, trace,
                    t0=time.perf_counter(), require_tpu=False,
                    log=lambda *a: None, **kw)
