"""Plain BFS over the benchmark's own edge list, in numpy/scipy.

It decides ``correct`` for BFS traffic and imports nothing of the
program. Two numbers are compared per search, each with the limit 0:
``level_mismatches``, the vertices whose hop count differs from the
reference's (unreached vertices carry ``2**31 - 1``), and
``parent_errors``, the reached vertices whose parent is not a
neighbour one level closer to the root (the root must be its own
parent), as Graph500's validation asks.

The control breaks the guarantee that every reachable vertex gets its
hop count: it stops one level short, as a search that quits once the
frontier is small would.
"""

from __future__ import annotations

import numpy as np

UNREACHED = np.iinfo(np.int32).max
_CHUNK = 8      # roots expanded together: [n, 8] frontier blocks


def levels(host, roots) -> np.ndarray:
    """``[len(roots), n]`` hop counts, ``UNREACHED`` where none."""
    out = []
    for i in range(0, len(roots), _CHUNK):
        chunk = np.asarray(roots[i:i + _CHUNK], np.int64)
        k = len(chunk)
        dist = np.full((host.n, k), UNREACHED, np.int64)
        front = np.zeros((host.n, k), np.float32)
        dist[chunk, np.arange(k)] = 0
        front[chunk, np.arange(k)] = 1.0
        level = 0
        while front.any():
            level += 1
            new = (host.adjacency @ front > 0) & (dist == UNREACHED)
            dist[new] = level
            front = new.astype(np.float32)
        out.append(dist.T)
    return np.concatenate(out)


def parent_errors(host, root: int, parent: np.ndarray,
                  lev: np.ndarray) -> int:
    reached = np.flatnonzero((lev != UNREACHED) & (lev > 0))
    p = parent[reached].astype(np.int64)
    known = (p >= 0) & (p < host.n)
    p = np.where(known, p, 0)
    good = (known & host.has_edge(p, reached)
            & (lev[p] == lev[reached] - 1))
    return int(len(reached) - good.sum()) + int(parent[root] != root)


def _check(host, sample, want) -> list[dict]:
    out = []
    for (kw, got), lev in zip(sample, want):
        dist = np.asarray(got["dist"]).astype(np.int64)
        parent = np.asarray(got["parent"])
        if dist.shape != lev.shape or parent.shape != lev.shape:
            out.append({"level_mismatches": host.n, "parent_errors": host.n})
            continue
        out.append({
            "level_mismatches": int(np.sum(dist != lev)),
            "parent_errors": parent_errors(host, int(kw["root"]), parent,
                                           lev)})
    return out


def _levels_of(host, sample) -> list:
    """The reference's levels for each search of ``sample``, each root
    expanded once."""
    roots = [int(kw["root"]) for kw, _ in sample]
    uniq = sorted(set(roots))
    lev = levels(host, uniq)
    return [lev[uniq.index(r)] for r in roots]


def compare(host, sample, params) -> list[dict]:
    """Per search of ``sample`` (``[(kwargs, state)]``), the numbers."""
    return _check(host, sample, _levels_of(host, sample))


def min_parents(host, lev: np.ndarray) -> np.ndarray:
    """The least-id neighbour one level closer to the root, ``n`` for
    unreached vertices, the root for itself."""
    src, dst = host.src, host.dst
    ok = (lev[dst] != UNREACHED) & (lev[src] == lev[dst] - 1)
    parent = np.full(host.n, host.n, np.int64)
    # edges are sorted by (dst, src): the first match of a row is its least
    rows, first = np.unique(dst[ok], return_index=True)
    parent[rows] = src[ok][first]
    parent[lev == 0] = np.flatnonzero(lev == 0)
    return parent


def control(host, sample, params) -> list[dict]:
    """The numbers of the reference stopped one level short."""
    want = _levels_of(host, sample)
    states = []
    for (kw, _), lev in zip(sample, want):
        short = lev.copy()
        reached = lev != UNREACHED
        short[reached & (lev == lev[reached].max())] = UNREACHED
        states.append((kw, {"dist": short,
                            "parent": min_parents(host, short)}))
    return _check(host, states, want)
