"""A cell, configuration, traffic mix and metric added as new files and
new entries only: the harness finds them and no existing file changes."""

from __future__ import annotations

import hashlib
import json

import pytest

from conftest import REPO, add_entries, copy_benchmark, run_tiny
from bench.spec import load_cell


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_files_and_entries_are_found(tmp_path):
    root = copy_benchmark(tmp_path)
    before = _digests(root)
    bench = root / "bench"
    (bench / "configs" / "new-kron-9.json").write_text(json.dumps(
        {"graph_seed": 1, "generator": "kronecker", "scale": 9,
         "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}))
    (bench / "traffic" / "bfs-hubs.json").write_text(json.dumps(
        {"driver": "closed_loop", "algorithm": "bfs", "policy": "push", "backend": "dense",
         "params": {}, "source_key": "root", "min_degree": 40, "keys": 8,
         "check": {"sample": 8,
                   "limits": {"level_mismatches": 0, "parent_errors": 0}}}))
    (bench / "metrics" / "max_steps.bfs.py").write_text(
        "def read(run):\n"
        "    return max(s.steps for s in run.solves)\n")
    (bench / "metrics" / "roots_done.py").write_text(
        "def read(run):\n"
        "    return len(run.solves)\n")
    add_entries(
        root,
        configs=[{"name": "new-kron-9", "source": "test", "reduced": [],
                  "file": "bench/configs/new-kron-9.json", "why": "test"}],
        workloads=[{"name": "kron9-hubs", "config": "new-kron-9",
                    "traffic": "bfs-hubs", "chips": 1, "why": "test"}],
        end_to_end=[{"name": "roots_done", "unit": "roots",
                     "better": "higher", "bound": 0.05,
                     "source": "host_clock", "workloads": ["kron9-hubs"]}],
        per_layer=[{"name": "max_steps.bfs", "unit": "steps",
                    "better": "lower", "source": "program_counter",
                    "layer": "engine loop", "moves": "roots_done",
                    "workloads": ["kron9-hubs"]}])
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    cell = load_cell(root, "kron9-hubs")
    assert cell.config["scale"] == 9 and cell.traffic["min_degree"] == 40
    assert [e["name"] for e in cell.per_layer] == ["max_steps.bfs"]
    assert {e["name"] for e in cell.end_to_end} == {
        "roots_done", "peak_hbm_gb", "setup_s"}
    line = run_tiny(root, "kron9-hubs")
    assert line["correct"] is True
    assert line["metrics"]["roots_done"]["value"] == line["attempted"]
    # the cells that were there do not pick up the new cell's metrics
    assert "roots_done" not in {e["name"] for e in
                                load_cell(root, "kron16-bfs").end_to_end}


BURST = '''
import numpy as np


def make(traffic, degree, label, graph_rng, run_rng):
    return Burst(traffic, label[np.flatnonzero(degree > 0)], run_rng)


class Burst:
    """``burst`` searches started together, then waited for."""

    def __init__(self, traffic, roots, rng):
        self.burst, self.roots, self.rng = traffic["burst"], roots, rng

    def warmup(self):
        return {"root": int(self.roots[0])}

    def run(self, start, finish, seconds):
        first = None
        while True:
            started = [start({"root": int(self.rng.choice(self.roots))})
                       for _ in range(self.burst)]
            done = [finish(s) for s in started]
            first = done[0].t_call if first is None else first
            if done[-1].t_end - first >= seconds:
                return
'''


def test_new_traffic_driver_is_found(tmp_path):
    """Traffic of another kind: a driver file and a mix that names it."""
    root = copy_benchmark(tmp_path)
    before = _digests(root)
    bench = root / "bench"
    (bench / "traffic" / "burst.py").write_text(BURST)
    (bench / "traffic" / "bfs-bursts.json").write_text(json.dumps(
        {"driver": "burst", "burst": 4, "algorithm": "bfs",
         "policy": None, "backend": "dense", "params": {},
         "check": {"sample": 6,
                   "limits": {"level_mismatches": 0, "parent_errors": 0}}}))
    (bench / "configs" / "new-urand-8.json").write_text(json.dumps(
        {"graph_seed": 2, "generator": "urand", "scale": 8, "degree": 4}))
    add_entries(
        root,
        configs=[{"name": "new-urand-8", "source": "test", "reduced": [],
                  "file": "bench/configs/new-urand-8.json", "why": "test"}],
        workloads=[{"name": "urand8-bursts", "config": "new-urand-8",
                    "traffic": "bfs-bursts", "chips": 1, "why": "test"}])
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    line = run_tiny(root, "urand8-bursts", seconds=0.05)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["attempted"] % 4 == 0


def test_unknown_cell_is_refused(tmp_path):
    root = copy_benchmark(tmp_path)
    with pytest.raises(KeyError, match="no workload"):
        load_cell(root, "no-such-cell")


BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_of_the_benchmark_resolves(name):
    """Each cell finds its configuration, traffic, generator, driver,
    reference and readers by the names it gives, and reports set-up, an
    end-to-end metric besides it, and a per-layer metric."""
    cell = load_cell(REPO, name)
    assert cell.config["generator"] and cell.traffic["algorithm"]
    cell.module("generators", cell.config["generator"]).generate
    cell.module("traffic", cell.traffic["driver"]).make
    ref = cell.module("reference", cell.traffic["algorithm"])
    assert callable(ref.compare) and callable(ref.control)
    for entry in cell.end_to_end + cell.per_layer:
        assert callable(cell.module("metrics", entry["name"]).read)
    e2e = {e["name"] for e in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    assert e2e >= {e["moves"] for e in cell.per_layer}


def test_every_metric_has_its_reader_and_names_only_cells_that_exist():
    for entry in METRICS:
        assert (REPO / "bench" / "metrics" / f"{entry['name']}.py").is_file()
        assert set(entry.get("workloads", ())) <= set(CELLS), entry["name"]
    names = [e["name"] for e in METRICS]
    assert len(names) == len(set(names))


def test_pagerank_metrics_go_to_the_pagerank_cell_alone():
    def e2e(name):
        return {e["name"] for e in load_cell(REPO, name).end_to_end}
    assert e2e("urand21-pr") >= {"pr_iter_ms", "peak_hbm_gb", "setup_s"}
    assert {e["name"] for e in load_cell(REPO, "urand21-pr").per_layer} >= {
        "pr_roofline", "device_idle.pr", "pull_iter_ms.pr"}
    for name in ("urand21-bfs", "kron16-bfs"):
        assert "pr_iter_ms" not in e2e(name)
