"""A whole run of each tiny cell on the CPU, the look for a chip skipped."""

from __future__ import annotations

import json
import subprocess
import sys

import pytest

from conftest import REPO, TINY_CELLS, copy_benchmark, run_tiny

E2E = {"tiny-urand-bfs": {"bfs_teps", "bfs_p90_ms", "peak_hbm_gb", "setup_s"},
       "tiny-kron-bfs": {"bfs_teps", "bfs_p90_ms", "peak_hbm_gb", "setup_s"},
       "tiny-urand-pr": {"pr_iter_ms", "peak_hbm_gb", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_run_is_correct_and_reports_its_metrics(tiny_root, cell):
    line = run_tiny(tiny_root, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    for check in line["checks"].values():
        assert check["value"] <= check["limit"]
    # the CPU reports no peak memory, so peak_hbm_gb is left out there
    assert set(line["metrics"]) == E2E[cell] - {"peak_hbm_gb"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


def test_keys_come_in_passes_in_an_order_of_the_seed():
    import numpy as np
    from bench.traffic.closed_loop import ClosedLoop, draw_keys
    traffic = {"params": {}, "source_key": "root", "min_degree": 2,
               "keys": 8}
    degree = np.array([0, 1, 2, 3] * 8)
    keys = draw_keys(traffic, degree, np.random.default_rng(0))
    assert len(set(keys)) == 8 and np.all(degree[keys] >= 2)

    def stream(seed):
        req = ClosedLoop(traffic, keys, np.random.default_rng(seed))
        assert req.warmup()["root"] in keys    # takes nothing from a pass
        return [req.next()["root"] for _ in range(24)]

    big = 2**31 + 12345
    a = stream(big)
    assert a == stream(big) and a != stream(big + 1)
    for p in range(3):
        assert sorted(a[8 * p:8 * p + 8]) == sorted(keys)


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_window_holds_whole_passes_over_the_keys(tiny_root, seed):
    line = run_tiny(tiny_root, "tiny-kron-bfs", seed=seed, seconds=0.05)
    assert line["attempted"] > 0 and line["attempted"] % 64 == 0


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_every_seed_runs_the_same_graph_relabelled(tiny_root, cell):
    """Two seeds give two labelings of one graph and one key set."""
    import numpy as np
    from bench.harness import instance
    from bench.reference.bfs import levels
    from bench.spec import load_cell
    c = load_cell(tiny_root, cell)
    (h1, r1, _), (h2, r2, _) = (instance(c, s) for s in (3, 2**33 + 1))
    assert h1.m == h2.m and not np.array_equal(h1.src, h2.src)
    np.testing.assert_array_equal(np.sort(h1.degree), np.sort(h2.degree))
    if r1.key:
        def depths(h, r):
            lev = levels(h, list(r.keys))
            return np.sort(np.where(lev < 2**31 - 1, lev, -1).max(axis=1))
        np.testing.assert_array_equal(depths(h1, r1), depths(h2, r2))


def test_traced_run_off_the_chip_refuses(tiny_root):
    """On the CPU there is no TPU plane to read: a traced run refuses
    rather than report device numbers it cannot have."""
    with pytest.raises(ValueError, match="no 'XLA Ops' line"):
        run_tiny(tiny_root, "tiny-urand-bfs", trace=True)


def test_command_refuses_a_host_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron16-bfs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == "" or not proc.stdout.strip(
        ).splitlines()[-1].startswith("{")
    assert "needs a TPU" in proc.stderr


def test_command_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    copy_benchmark(tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron16-bfs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert "{" not in proc.stdout
