"""The plain references against ``api.solve`` on small graphs (CPU),
the controls against the references, and the ``pr_roofline`` bytes."""

from __future__ import annotations

import numpy as np
import pytest

from conftest import REPO, TINY_CONFIGS
from bench.edges import symmetric_sorted
from bench.harness import HostGraph
from bench.spec import load_module

REF = REPO / "bench" / "reference"


def _host(name: str, seed: int = 11) -> HostGraph:
    cfg = TINY_CONFIGS[name]
    gen = load_module(REPO / "bench" / "generators" / f"{cfg['generator']}.py")
    lo, hi, n = gen.generate(cfg, np.random.default_rng(seed))
    return HostGraph(n, *symmetric_sorted(lo, hi, n))


def _graph(host: HostGraph):
    from repro.graphs import build_graph
    return build_graph(host.src, host.dst, n=host.n)


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_bfs_reference_matches_the_program(name):
    from repro import api
    host = _host(name)
    g = _graph(host)
    bfs = load_module(REF / "bfs.py")
    roots = [int(r) for r in np.flatnonzero(host.degree > 0)[:5]]
    sample = [({"root": r}, api.solve(g, "bfs", root=r).state)
              for r in roots]
    per = bfs.compare(host, sample, {})
    assert per == [{"level_mismatches": 0, "parent_errors": 0}] * len(roots)
    # and the reference's own least-id parents are valid
    lev = bfs.levels(host, roots[:1])[0]
    assert bfs.parent_errors(host, roots[0], bfs.min_parents(host, lev),
                             lev) == 0


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_bfs_control_fails(name):
    host = _host(name)
    bfs = load_module(REF / "bfs.py")
    roots = [int(r) for r in np.flatnonzero(host.degree > 0)[:3]]
    per = bfs.control(host, [({"root": r}, None) for r in roots], {})
    assert all(p["level_mismatches"] > 0 and p["parent_errors"] > 0
               for p in per)


def test_pagerank_reference_matches_the_program_and_control_fails():
    from repro import api
    host = _host("tiny-urand")
    g = _graph(host)
    pr = load_module(REF / "pagerank.py")
    params = {"iters": 20, "damp": 0.85}
    got = api.solve(g, "pagerank", **params).state
    [prog] = pr.compare(host, [({}, got)], params)
    [ctrl] = pr.control(host, [({}, None)], params)
    assert prog["max_rel_err"] < 1e-5
    assert ctrl["max_rel_err"] > 1e-3


def test_references_import_nothing_of_the_program():
    for path in REF.glob("*.py"):
        assert "repro" not in path.read_text(), path


def test_pr_roofline_counts_the_algorithms_bytes():
    mod = load_module(REPO / "bench" / "metrics" / "pr_roofline.py")
    n, m = 2**21, 67_106_816
    assert mod.iteration_bytes(n, m) == 4 * m + 8 * n == 285_204_480
    least_ms = 1e3 * mod.iteration_bytes(n, m) / 819e9
    assert 0.34 < least_ms < 0.36
