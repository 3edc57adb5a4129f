"""Scaling sweep: the sharded engine at 1/2/4/8 shards (paper §6).

One fixed graph, one subprocess faking 8 host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), sweeping the
shard count over device subsets. Per (algorithm × policy × shards) cell:
wall clock of a full ``api.solve(backend=ShardedBackend)`` run, total
inter-device wire bytes (the adaptive accounting the backend charges to
``Cost.collective_bytes``), and a correctness cross-check against the
single-device dense run. A compressed cell (error-feedback top-k on the
push accumulator) rides the same sweep.

The paper's DM claim shows up directly in the rows: BFS's frontier-
sparse push moves fewer bytes than its all_gather pull, while dense-
frontier PageRank pushes move more — the asymmetry ``AutoSwitch`` now
prices via ``StepStats.push/pull_wire_bytes``.

Rows are named ``scaling_*`` and carry a ``scaling_cell`` derived
payload (benchmarks/schema.json); ``benchmarks.validate`` enforces it.
"""

from __future__ import annotations

import subprocess
import sys

from . import common
from .common import emit

_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro import api
from repro.dist.compression import CompressionConfig
from repro.graphs import standin
from repro.shard import ShardedBackend

SCALE = %(scale)r
ITERS = %(iters)d
g = standin("orc", scale=SCALE, weighted=True)

CASES = [
    ("pagerank", dict(iters=20), "push", None),
    ("pagerank", dict(iters=20), "pull", None),
    ("pagerank", dict(iters=20), "push",
     CompressionConfig(kind="topk", topk_frac=0.05)),
    ("bfs", dict(root=0), "push", None),
    ("bfs", dict(root=0), "pull", None),
    ("bfs", dict(root=0), "auto", None),
]

refs = {}
for algo, kw, pol, _ in CASES:
    if (algo, pol) not in refs:
        refs[(algo, pol)] = api.solve(g, algo, policy=pol, **kw)

def states_match(algo, ref, got, compressed):
    if algo == "bfs":
        return bool(jnp.all(ref.state["dist"] == got.state["dist"]))
    tol = 5e-2 if compressed else 1e-5
    return bool(jnp.allclose(ref.state, got.state, rtol=tol, atol=tol))

for P in (1, 2, 4, 8):
    mesh = Mesh(np.array(jax.devices()[:P]).reshape(P, 1),
                ("data", "model"))
    plain = ShardedBackend.prepare(g, mesh=mesh)
    for algo, kw, pol, cfg in CASES:
        backend = (plain if cfg is None else
                   ShardedBackend.prepare(g, mesh=mesh, compression=cfg))
        run = lambda: api.solve(g, algo, policy=pol, backend=backend, **kw)
        r = run()
        jax.block_until_ready(r.state)
        ts = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            jax.block_until_ready(run().state)
            ts.append(time.perf_counter() - t0)
        us = sorted(ts)[len(ts) // 2] * 1e6
        comp = "none" if cfg is None else cfg.kind
        cell = {
            "algorithm": algo, "graph": "orc", "n": g.n, "m": g.m,
            "policy": pol, "backend": "shard", "shards": P,
            "compression": comp, "wall_us": round(us, 1),
            "collective_bytes": int(r.cost.collective_bytes),
            "steps": int(r.steps), "push_steps": int(r.push_steps),
            "converged": bool(r.converged),
            "weighted_total": float(r.cost.weighted_total()),
            "cut_edges": backend.cut_edges,
            "match": states_match(algo, refs[(algo, pol)], r,
                                  cfg is not None),
        }
        suffix = "" if cfg is None else "_" + comp
        print("ROW\t" + "scaling_" + algo + "_" + pol + suffix
              + "_P" + str(P) + "\t" + ("%%.1f" %% us) + "\t"
              + json.dumps(cell), flush=True)
"""


def run():
    scale = 1.0 / 1024 if common.SMOKE else 1.0 / 256
    iters = 1 if common.SMOKE else 3
    r = subprocess.run(
        [sys.executable, "-c", _SUB % {"scale": scale, "iters": iters}],
        capture_output=True, text=True, timeout=1200,
        env=common.fake_device_env(), cwd=common.ROOT)
    for line in r.stdout.splitlines():
        if line.startswith("ROW\t"):
            _, name, us, derived = line.split("\t", 3)
            emit(name, float(us), derived)
    if r.returncode != 0:
        print(r.stderr[-2000:])
        raise RuntimeError(f"scaling subprocess failed "
                           f"(exit {r.returncode})")


if __name__ == "__main__":
    run()
