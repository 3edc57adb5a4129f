"""repro.shard — the sharded engine subsystem.

Covers, single-device (in-process): partition/mesh validation (explicit
padding, never truncation; num_parts bounds), the adaptive wire-byte
accounting and its predictor exactness, and the distributed-only
AutoSwitch direction flip. Multi-device behavior (1/2/4/8 shards) runs
in fresh interpreters with XLA faking 8 host devices: solve parity
against the single-device dense backend for BFS / PageRank / SSSP ×
{push, pull, auto}, the ELL/Pallas inner pull executors, batched
multi-query solves, and the over-partition rejection.
"""

from __future__ import annotations

import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core.backend import DenseBackend
from repro.core.cost_model import (Cost, CostPredictor, CostWeights,
                                   StepStats)
from repro.core.direction import AutoSwitch
from repro.graphs.generators import erdos_renyi
from repro.graphs.partition import partition_1d
from repro.shard import ShardedBackend, build_topology, make_shard_mesh


def _run_sub(script: str) -> subprocess.CompletedProcess:
    import os
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900,
                          env=env, cwd=str(root))


# ---------------------------------------------------------------------
# partition / mesh validation (satellite S1)

def test_partition_rejects_nonpositive_parts():
    with pytest.raises(ValueError, match="at least one part"):
        partition_1d(10, 0)
    with pytest.raises(ValueError, match="at least one part"):
        partition_1d(10, -3)


def test_partition_rejects_more_parts_than_vertices():
    with pytest.raises(ValueError, match="exceeds the vertex count"):
        partition_1d(10, 11)
    partition_1d(10, 10)      # boundary: one vertex per part is fine


def test_partition_pads_explicitly_never_truncates():
    part = partition_1d(10, 4)
    assert part.shard_size == 3
    assert part.n_padded == 12 and part.n_padded >= part.n
    # every real vertex keeps an owner in range
    import numpy as np
    owners = part.owner_np(np.arange(10))
    assert owners.min() == 0 and owners.max() == 3


def test_mesh_validation():
    with pytest.raises(ValueError, match="at least one shard"):
        make_shard_mesh(0)
    ndev = len(jax.devices())
    with pytest.raises(ValueError, match="exceeds the"):
        make_shard_mesh(ndev + 1)


def test_prepare_validates_num_shards(small_graph):
    with pytest.raises(ValueError):
        ShardedBackend.prepare(small_graph, num_shards=0)
    with pytest.raises(ValueError, match="unknown inner"):
        ShardedBackend.prepare(small_graph, inner="csr")
    mesh = make_shard_mesh(1)
    with pytest.raises(ValueError, match="must equal the mesh"):
        ShardedBackend.prepare(small_graph, mesh=mesh, num_shards=2)


# ---------------------------------------------------------------------
# single-shard semantics + accounting (runs on the 1-device test process)

def test_single_shard_matches_dense(small_graph):
    g = small_graph
    sb = ShardedBackend.prepare(g, num_shards=1)
    for algo, kw, key in (("bfs", {"root": 0}, "dist"),
                          ("pagerank", {"iters": 15}, None)):
        ref = api.solve(g, algo, **kw)
        got = api.solve(g, algo, backend=sb, **kw)
        a = ref.state if key is None else ref.state[key]
        b = got.state if key is None else got.state[key]
        assert bool(jnp.all(a == b)), algo


def test_predictor_matches_charged_bytes(small_graph):
    """predict_comm_bytes must equal what push/pull then charge —
    the exactness AutoSwitch's §6 comm pricing rests on."""
    g = small_graph
    sb = ShardedBackend.prepare(g, num_shards=1)
    vals = jnp.ones((g.n,), jnp.float32)
    frontier = jnp.arange(g.n) % 3 == 0
    pb, lb = sb.predict_comm_bytes(g, vals, frontier)
    _, cp = sb.push(g, vals, frontier, "sum", lambda x, w: x * w, Cost())
    _, cl = sb.pull(g, vals, None, "sum", lambda x, w: x * w, Cost())
    assert int(cp.collective_bytes) == int(pb)
    assert int(cl.collective_bytes) == int(lb)


def test_topology_pull_groups_preserve_coo_order(small_graph):
    """Each shard's pull row must hold its destinations' in-edges in
    global coo order — the invariant that makes sharded pull-sum
    bit-identical to the single-device segment ops."""
    import numpy as np
    g = small_graph
    part = partition_1d(g.n, 4)
    topo = build_topology(g, part)
    dst = np.asarray(g.coo_dst)
    src = np.asarray(g.coo_src)
    own = part.owner_np(dst)
    for p in range(4):
        ok = np.asarray(topo.pull_edges.valid[p])
        np.testing.assert_array_equal(
            np.asarray(topo.pull_edges.src[p])[ok], src[own == p])
        np.testing.assert_array_equal(
            np.asarray(topo.pull_edges.dst[p])[ok], dst[own == p])


def test_autoswitch_flips_for_comm_asymmetry_alone():
    """Two steps identical in every §4 counter, differing only in wire
    bytes: the predictor must order them by the collective term, so a
    distributed backend can flip direction for comm reasons alone."""
    base = dict(frontier_vertices=jnp.int64(8),
                frontier_edges=jnp.int64(100),
                pull_edges=jnp.int64(100), pull_vertices=jnp.int64(50),
                unvisited_edges=jnp.int64(100), step=jnp.int64(1),
                prev_push=jnp.bool_(True))
    predictor = CostPredictor(weights=CostWeights(collective_byte=0.5))
    even = StepStats(**base, push_wire_bytes=jnp.int64(0),
                     pull_wire_bytes=jnp.int64(0))
    push_heavy = StepStats(**base, push_wire_bytes=jnp.int64(10_000),
                           pull_wire_bytes=jnp.int64(0))
    pull_heavy = StepStats(**base, push_wire_bytes=jnp.int64(0),
                           pull_wire_bytes=jnp.int64(10_000))
    auto = AutoSwitch(predictor=predictor)
    # wire bytes shift exactly the collective term
    assert float(predictor.predict_push(push_heavy)) == pytest.approx(
        float(predictor.predict_push(even)) + 10_000 * 0.5)
    assert float(predictor.predict_pull(pull_heavy)) == pytest.approx(
        float(predictor.predict_pull(even)) + 10_000 * 0.5)
    # with a comm-dominant push the decision flips to pull, and back
    g = None
    assert not bool(auto.decide(g, None, push_heavy))
    assert bool(auto.decide(g, None, pull_heavy))


def test_sparse_push_prices_below_pull_on_sparse_frontier():
    """The adaptive push accounting: a near-empty frontier sends a few
    (index, value) pairs — fewer bytes than the all_gather pull — while
    a full frontier falls back to the dense alltoall bound."""
    g = erdos_renyi(120, 4.0, seed=3, weighted=True)
    sb = ShardedBackend.prepare(g, num_shards=1)
    # num_shards=1 has no cut; emulate a 4-part split host-side
    part = partition_1d(g.n, 4)
    topo = build_topology(g, part)
    sb4 = ShardedBackend(mesh=sb.mesh, topo=topo, axis=sb.axis)
    vals = jnp.ones((g.n,), jnp.float32)
    sparse = jnp.zeros((g.n,), bool).at[0].set(True)
    dense = jnp.ones((g.n,), bool)
    pb_sparse, lb = sb4.predict_comm_bytes(g, vals, sparse)
    pb_dense, _ = sb4.predict_comm_bytes(g, vals, dense)
    assert int(pb_sparse) < int(lb)
    assert int(pb_dense) >= int(lb)


def test_shard_shorthand_requires_graph_context():
    with pytest.raises(ValueError, match="graph-specific"):
        api._resolve_backend("shard")


def test_shard_backend_identity_semantics(small_graph):
    """Same-config instances must NOT compare equal: the engine cache
    keys on the backend, and value equality across distinct prepared
    topologies would alias engines across graphs of one shape."""
    a = ShardedBackend.prepare(small_graph, num_shards=1)
    b = ShardedBackend.prepare(small_graph, num_shards=1)
    assert a == a
    assert a != b
    assert len({a, b}) == 2


# ---------------------------------------------------------------------
# multi-device parity (fresh interpreter, 8 fake host devices)

SHARD_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import api
from repro.core.backend import EllBackend
from repro.core.cost_model import Cost
from repro.graphs.generators import erdos_renyi
from repro.shard import ShardedBackend

g = erdos_renyi(130, 4.0, seed=5, weighted=True)   # 130 % 4 != 0: pads
CASES = [
    ("bfs", dict(root=0), ("dist", "parent"), True),
    ("pagerank", dict(iters=20), None, False),
    ("sssp_delta", dict(source=0, delta=2.0), ("dist",), True),
]
for algo, kw, keys, exact in CASES:
    for pol in ("push", "pull", "auto"):
        ref = api.solve(g, algo, policy=pol, **kw)
        for P in (1, 2, 4, 8):
            sb = ShardedBackend.prepare(g, num_shards=P)
            got = api.solve(g, algo, policy=pol, backend=sb, **kw)
            ra = [ref.state] if keys is None else [ref.state[k] for k in keys]
            ga = [got.state] if keys is None else [got.state[k] for k in keys]
            if exact or pol == "pull":
                ok = all(bool(jnp.all(a == b)) for a, b in zip(ra, ga))
            else:
                ok = all(bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-6))
                         for a, b in zip(ra, ga))
            print(f"{algo} {pol} P={P} ok: {ok}")

# inner executors: ELL / Pallas pull matches the EllBackend semantics
vals = jax.random.uniform(jax.random.PRNGKey(0), (g.n,), jnp.float32)
refe, _ = EllBackend().pull(g, vals, None, "sum", lambda x, w: x * w,
                            Cost())
for inner in ("ell", "pallas"):
    for P in (2, 8):
        si = ShardedBackend.prepare(g, num_shards=P, inner=inner)
        gote, _ = si.pull(g, vals, None, "sum", lambda x, w: x * w,
                          Cost())
        print(f"inner={inner} P={P} ok: {bool(jnp.all(refe == gote))}")

# predictor exactness with a real cut (P=4)
sb = ShardedBackend.prepare(g, num_shards=4)
frontier = jnp.arange(g.n) % 7 == 0
pb, lb = sb.predict_comm_bytes(g, vals, frontier)
_, cp = sb.push(g, vals, frontier, "sum", lambda x, w: x * w, Cost())
_, cl = sb.pull(g, vals, None, "sum", lambda x, w: x * w, Cost())
print("predict push ok:", int(pb) == int(cp.collective_bytes))
print("predict pull ok:", int(lb) == int(cl.collective_bytes))

# batched multi-query through the sharded backend
br = api.solve_batch(g, "bfs", sources=[0, 5, 9], backend="shard")
ok = all(bool(jnp.all(br.states[i]["dist"]
                      == api.solve(g, "bfs", root=s).state["dist"]))
         for i, s in enumerate([0, 5, 9]))
print("batch bfs ok:", ok)
br = api.solve_batch(g, "sssp_delta", sources=[0, 5], delta=2.0,
                     backend="shard")
ok = all(bool(jnp.all(br.states[i]["dist"]
                      == api.solve(g, "sssp_delta", source=s,
                                   delta=2.0).state["dist"]))
         for i, s in enumerate([0, 5]))
print("batch sssp ok:", ok)

# more shards than vertices is a hard error, not a silent alias
tiny = erdos_renyi(6, 1.5, seed=1)
try:
    ShardedBackend.prepare(tiny, num_shards=8)
    print("overpartition ok: False")
except ValueError:
    print("overpartition ok: True")
"""


@pytest.mark.dist
@pytest.mark.subprocess
def test_sharded_solve_parity_across_shard_counts():
    """solve(backend=ShardedBackend) at 1/2/4/8 shards reproduces the
    single-device dense states for BFS, PageRank, and Δ-stepping SSSP
    under push, pull, and auto — exact for the min-combines and the
    order-preserving pull, allclose(1e-5) for the reassociated
    psum_scatter push-sum — plus inner-executor parity, wire-byte
    predictor exactness with a real cut, batched solves, and the
    over-partition rejection."""
    r = _run_sub(SHARD_PARITY)
    assert r.returncode == 0, r.stdout + r.stderr
    for algo in ("bfs", "pagerank", "sssp_delta"):
        for pol in ("push", "pull", "auto"):
            for P in (1, 2, 4, 8):
                line = f"{algo} {pol} P={P} ok: True"
                assert line in r.stdout, (line, r.stdout + r.stderr)
    for inner in ("ell", "pallas"):
        for P in (2, 8):
            assert f"inner={inner} P={P} ok: True" in r.stdout, r.stdout
    for line in ("predict push ok: True", "predict pull ok: True",
                 "batch bfs ok: True", "batch sssp ok: True",
                 "overpartition ok: True"):
        assert line in r.stdout, (line, r.stdout + r.stderr)


def test_scaling_benches_fork_fake_devices_on_cpu_hosts_only(monkeypatch):
    """The scaling suites fake devices in a child process: pinned to the
    CPU, and refused on a TPU host, where this process holds the chip
    and a child reaching for it would fail or hang."""
    from benchmarks import common
    env = common.fake_device_env()
    assert env["JAX_PLATFORMS"] == "cpu" and "XLA_FLAGS" not in env
    monkeypatch.setattr(common.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU host"):
        common.fake_device_env()
