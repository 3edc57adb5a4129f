"""Graph500 Kronecker generator (graph500.org, BFS specification).

Each of the specification's ``edge_factor * 2**scale`` edges picks one
quadrant per bit of the vertex id with the initiator probabilities
``a``, ``b``, ``c`` (and ``1 - a - b - c``), exactly as the
specification's reference code does; vertex labels are then permuted at
random. Duplicate edges and self loops are dropped.
"""

from __future__ import annotations

import numpy as np

from bench.edges import distinct_pairs


def generate(cfg: dict, rng: np.random.Generator):
    """``(lo, hi, n)``: the graph's undirected edges, ``lo < hi``."""
    scale = cfg["scale"]
    n = 1 << scale
    draws = cfg["edge_factor"] * n
    a, b, c = cfg["a"], cfg["b"], cfg["c"]
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(draws, np.int64)
    dst = np.zeros(draws, np.int64)
    for bit in range(scale):
        src_bit = rng.random(draws) > ab
        dst_bit = rng.random(draws) > np.where(src_bit, c_norm, a_norm)
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return (*distinct_pairs(perm[src], perm[dst], n), n)
