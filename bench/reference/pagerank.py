"""Plain PageRank over the benchmark's own edge list, in numpy/scipy.

It decides ``correct`` for PageRank traffic and imports nothing of the
program. ``r = (1 - d) / n + d * A @ (r / outdeg)`` for a fixed number
of iterations from ``r = 1 / n``, in float64. The number compared is
``max_rel_err``: the largest ``|got - want| / want`` over the vertices
of each solve.

The control is the same iteration in the next precision below the
configuration's float32: ranks and contributions stored as bfloat16,
sums taken in float32, as a later change that halves the bytes moved
per edge would compute them.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def ranks(host, iters: int, damp: float, bf16: bool = False) -> np.ndarray:
    inv = 1.0 / np.maximum(host.degree, 1)
    a = host.adjacency if bf16 else host.adjacency.astype(np.float64)
    r = np.full(host.n, 1.0 / host.n)
    for _ in range(iters):
        contrib = r * inv
        if bf16:
            contrib = contrib.astype(ml_dtypes.bfloat16).astype(np.float32)
        r = (1.0 - damp) / host.n + damp * (a @ contrib)
        if bf16:
            r = r.astype(ml_dtypes.bfloat16).astype(np.float64)
    return r


def _check(sample, want) -> list[dict]:
    out = []
    for _, got in sample:
        got = np.asarray(got, np.float64)
        err = (np.max(np.abs(got - want) / want)
               if got.shape == want.shape else np.inf)
        out.append({"max_rel_err": float(err)})
    return out


def compare(host, sample, params) -> list[dict]:
    """Per solve of ``sample`` (``[(kwargs, ranks)]``), the numbers."""
    return _check(sample, ranks(host, params["iters"], params["damp"]))


def control(host, sample, params) -> list[dict]:
    """The numbers of the bfloat16 iteration in the program's place."""
    want = ranks(host, params["iters"], params["damp"])
    low = ranks(host, params["iters"], params["damp"], bf16=True)
    return _check([(kw, low) for kw, _ in sample], want)
