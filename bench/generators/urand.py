"""GAP Benchmark Suite ``urand``: uniform random undirected graph.

As GAP's ``-u <scale> -k <degree>``: ``degree * 2**scale`` pairs, both
endpoints uniform over the ``2**scale`` vertices; self loops and
duplicates are removed, leaving a count a few hundred short of the
draws.
"""

from __future__ import annotations

import numpy as np

from bench.edges import distinct_pairs


def generate(cfg: dict, rng: np.random.Generator):
    """``(lo, hi, n)``: the graph's undirected edges, ``lo < hi``."""
    n = 1 << cfg["scale"]
    draws = cfg["degree"] * n
    a = rng.integers(0, n, size=draws, dtype=np.int64)
    b = rng.integers(0, n, size=draws, dtype=np.int64)
    return (*distinct_pairs(a, b, n), n)
