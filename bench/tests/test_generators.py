"""The benchmark's generator copies: simple, symmetric, exact counts."""

from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import REPO, TINY_CONFIGS
from bench.edges import distinct_pairs, symmetric_sorted
from bench.spec import load_module


def _generate(cfg: dict, seed: int):
    """``(src, dst, n)`` of the generator's graph, stored both ways."""
    gen = load_module(REPO / "bench" / "generators" / f"{cfg['generator']}.py")
    lo, hi, n = gen.generate(cfg, np.random.default_rng(seed))
    assert np.all(lo < hi)
    return (*symmetric_sorted(lo, hi, n), n)


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_simple_symmetric_sorted_and_exact(name):
    cfg = TINY_CONFIGS[name]
    src, dst, n = _generate(cfg, 2**31 + 99)
    assert n == 1 << cfg["scale"]
    assert src.dtype == dst.dtype == np.int32
    if cfg["generator"] == "urand":       # the same draws, counted apart
        rng = np.random.default_rng(2**31 + 99)
        a, b = (rng.integers(0, n, cfg["degree"] * n) for _ in range(2))
        assert len(src) == 2 * len({(min(x, y), max(x, y))
                                    for x, y in zip(a, b) if x != y})
    else:
        assert 0.5 * cfg["edge_factor"] * n < len(src) / 2 \
            <= cfg["edge_factor"] * n
    assert not np.any(src == dst)
    key = dst.astype(np.int64) * n + src
    assert np.all(np.diff(key) > 0)          # sorted, so no duplicates
    back = np.sort(src.astype(np.int64) * n + dst)
    np.testing.assert_array_equal(back, key)  # every edge both ways


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_same_seed_same_graph(name):
    cfg = TINY_CONFIGS[name]
    a, b = _generate(cfg, 5), _generate(cfg, 5)
    c = _generate(cfg, 6)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_distinct_pairs_drops_loops_and_repeats():
    a = np.array([3, 1, 2, 1, 5, 4, 0, 2])
    b = np.array([1, 3, 2, 4, 5, 1, 6, 0])
    lo, hi = distinct_pairs(a, b, 8)
    # (1,3) and (1,4) come twice, (2,2) and (5,5) are self loops
    np.testing.assert_array_equal(lo, [0, 0, 1, 1])
    np.testing.assert_array_equal(hi, [2, 6, 3, 4])


def test_kronecker_16_at_cell_size():
    """The configuration's own instance, as the program is handed it:
    its edge count and largest degree (the program pads its ELL view to
    9,816), and on another seed the same shape of graph."""
    cfg = json.loads(
        (REPO / "bench" / "configs" / "graph500-kron-16.json").read_text())
    for seed in (cfg["graph_seed"], 2**32 + 3):
        src, dst, n = _generate(cfg, seed)
        deg = np.bincount(dst, minlength=n)
        if seed == cfg["graph_seed"]:
            assert (len(src), deg.max()) == (1_818_572, 9_809)
        assert 9_000 < deg.max() < 10_500
        assert 0.2 < (deg == 0).mean() < 0.35   # many isolated vertices


def test_urand_degree_is_near_poisson():
    cfg = dict(TINY_CONFIGS["tiny-urand"], scale=14, degree=16)
    src, dst, n = _generate(cfg, 3)
    deg = np.bincount(dst, minlength=n)
    assert abs(deg.mean() - 32) < 0.1
    assert abs(deg.var() - 32) < 2.0
