"""Edge-list steps that every graph of the benchmark goes through.

A generator draws the source's number of endpoint pairs and keeps the
distinct undirected ones, self loops dropped (:func:`distinct_pairs`),
as GAP and Graph500 do. The run then relabels the vertices by a
permutation drawn from its seed and stores each edge in both
directions (:func:`symmetric_sorted`).
"""

from __future__ import annotations

import numpy as np

__all__ = ["distinct_pairs", "symmetric_sorted"]


def distinct_pairs(a: np.ndarray, b: np.ndarray,
                   n: int) -> tuple[np.ndarray, np.ndarray]:
    """``(lo, hi)``, ``lo < hi``, of the distinct undirected pairs of
    the stream ``(a[i], b[i])``, self loops left out, sorted."""
    lo = np.minimum(a, b).astype(np.int64)
    hi = np.maximum(a, b).astype(np.int64)
    key = np.unique(lo[lo != hi] * n + hi[lo != hi])
    lo = key // n
    return lo, key - lo * n


def symmetric_sorted(lo: np.ndarray, hi: np.ndarray,
                     n: int) -> tuple[np.ndarray, np.ndarray]:
    """Both directions of each pair as int32 ``(src, dst)``, sorted by
    ``(dst, src)``."""
    key = np.concatenate([hi * n + lo, lo * n + hi])
    key.sort()
    dst = key // n
    return (key - dst * n).astype(np.int32), dst.astype(np.int32)
