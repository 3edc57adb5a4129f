"""Fig 3: distributed-memory exchange schedules.

Reproduces the paper's DM finding *structurally*: the combined-alltoall
("MP") push moves O(n) bytes/device; RMA-pull all_gathers O(n); RMA-push
(per-edge accumulate) moves O(cut·8) unaggregated bytes — the paper
measured it >10x slower for PR. We report analytic bytes/device for a P
sweep (from the PA split) + measured wall-clock on 8 fake host devices
(subprocess — the main bench process keeps 1 device)."""

from __future__ import annotations

import subprocess
import sys

from repro.graphs import partition_1d, pa_split

from .common import ROOT, emit, fake_device_env, graph

_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time, numpy as np
import jax, jax.numpy as jnp
from repro.graphs import standin, partition_1d, pa_split
from repro.dist.collectives import push_exchange, pull_exchange
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh()
g = standin("orc", scale=1.0/256)
part = partition_1d(g.n, 8)
local, remote, stats = pa_split(g, part)
vals = jnp.ones((part.n_padded,), jnp.float32)
for name, fn in (("push", push_exchange), ("pull", pull_exchange)):
    out, nbytes = fn(mesh, part, remote, vals)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(3):
        out, _ = fn(mesh, part, remote, vals)
        jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / 3 * 1e6
    print(f"{name},{dt:.1f},{nbytes}")
"""


def run():
    g = graph("orc")
    for P in (4, 16, 64, 256):
        part = partition_1d(g.n, P)
        _, remote, stats = pa_split(g, part)
        mp_bytes = part.n_padded * 4
        pull_bytes = part.n_padded * 4 * (P - 1) // P
        rma_push_bytes = stats["cut_edges"] * 8 // P
        emit(f"dm_bytes_P{P}", 0.0,
             f"cut={stats['cut_edges']};mp_push={mp_bytes};"
             f"rma_pull={pull_bytes};rma_push={rma_push_bytes}")
    r = subprocess.run([sys.executable, "-c", _SUB], capture_output=True,
                       text=True, timeout=600, env=fake_device_env(),
                       cwd=ROOT)
    for line in r.stdout.splitlines():
        if "," in line:
            name, dt, nbytes = line.split(",")
            emit(f"dm_exchange_{name}_8dev", float(dt), f"bytes={nbytes}")
    if r.returncode != 0:
        print(r.stderr[-1500:])


if __name__ == "__main__":
    run()
