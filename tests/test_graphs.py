"""Graph substrate: structure invariants, generators, partitioner, sampler."""

import numpy as np
import jax.numpy as jnp
import jax
import pytest
from hypothesis import given, strategies as st

from repro.graphs import (build_graph, erdos_renyi, kronecker, ring,
                          road_grid, star, standin, partition_1d, pa_split,
                          sample_blocks)
from repro.graphs.partition import PartitionedEdges, pa_regroup_by_dst


def test_build_graph_layout_consistency(small_graph):
    g = small_graph
    # pull-major sorted by dst; push-major sorted by src
    assert np.all(np.diff(np.asarray(g.coo_dst)) >= 0)
    assert np.all(np.diff(np.asarray(g.push_src)) >= 0)
    # same multiset of edges in both orders
    a = set(zip(np.asarray(g.coo_src).tolist(),
                np.asarray(g.coo_dst).tolist()))
    b = set(zip(np.asarray(g.push_src).tolist(),
                np.asarray(g.push_dst).tolist()))
    assert a == b
    # CSR pointers match degree counts
    assert np.all(np.diff(np.asarray(g.in_ptr)) == np.asarray(g.in_deg))
    assert np.all(np.diff(np.asarray(g.out_ptr)) == np.asarray(g.out_deg))


def test_build_graph_rejects_out_of_range_endpoints():
    with pytest.raises(ValueError, match=r"dst\[1\] = 3 is outside"):
        build_graph(np.array([0, 1]), np.array([1, 3]), 3)
    with pytest.raises(ValueError, match=r"src\[0\] = -1"):
        build_graph(np.array([-1]), np.array([0]), 2)
    with pytest.raises(ValueError, match="must be aligned"):
        build_graph(np.array([0, 1]), np.array([1]), 2)


def test_build_graph_rejects_nonfinite_weights():
    src, dst = np.array([0, 1]), np.array([1, 0])
    with pytest.raises(ValueError, match=r"weights\[1\].*not finite"):
        build_graph(src, dst, 2, weights=np.array([1.0, np.nan]))
    with pytest.raises(ValueError, match="not finite"):
        build_graph(src, dst, 2, weights=np.array([np.inf, 1.0]))
    with pytest.raises(ValueError, match="does not match"):
        build_graph(src, dst, 2, weights=np.array([1.0]))


def test_build_graph_boundary_vertex_still_accepted():
    # an edge into the last vertex (id n-1) sits exactly on the valid
    # boundary — the range check must not off-by-one it away
    g = build_graph(np.array([0, 4]), np.array([4, 0]), 5,
                    weights=np.array([2.0, 2.0]))
    assert g.n == 5 and g.m == 2
    assert int(np.asarray(g.in_deg)[4]) == 1
    # empty graphs skip the scan entirely
    assert build_graph(np.array([]), np.array([]), 3).m == 0


@pytest.mark.parametrize("weighted", [False, True])
def test_unit_weights_are_one_array(weighted):
    """An unweighted graph keeps its unit weights once for both edge
    orders; a weighted one keeps each order's own."""
    rng = np.random.default_rng(3)
    src, dst = rng.integers(0, 50, (2, 300))
    w = rng.random(300) + 0.5 if weighted else None
    g = build_graph(src, dst, 50, weights=w)
    assert (g.push_w is g.coo_w) != weighted
    want = np.ones(300) if w is None else w[np.argsort(src, kind="stable")]
    np.testing.assert_array_equal(np.asarray(g.push_w),
                                  want.astype(np.float32))


def test_ell_covers_all_in_edges(small_graph):
    g = small_graph
    idx = np.asarray(g.ell_idx)
    valid = idx < g.n
    assert valid.sum() == g.m
    # per-row valid count == in-degree
    assert np.all(valid.sum(1) == np.asarray(g.in_deg))


def test_weights_symmetric_per_pair(small_graph):
    g = small_graph
    src, dst, w = (np.asarray(g.coo_src), np.asarray(g.coo_dst),
                   np.asarray(g.coo_w))
    lookup = {(s, d): ww for s, d, ww in zip(src, dst, w)}
    for (s, d), ww in lookup.items():
        assert lookup[(d, s)] == ww


@pytest.mark.parametrize("gen", [
    lambda: erdos_renyi(200, 3.0, seed=1, weighted=True),
    lambda: kronecker(7, 4, seed=2, weighted=True),
    lambda: road_grid(12, weighted=True),
    lambda: ring(50, weighted=True),
    lambda: star(33),
])
def test_generators_simple_symmetric(gen):
    g = gen()
    src, dst = np.asarray(g.coo_src), np.asarray(g.coo_dst)
    assert np.all(src != dst), "no self loops"
    pairs = set(zip(src.tolist(), dst.tolist()))
    assert len(pairs) == g.m, "no duplicate directed edges"
    assert all((d, s) in pairs for s, d in pairs), "symmetric"


def test_standins_exist():
    for name in ("orc", "pok", "ljn", "am", "rca"):
        g = standin(name, scale=1.0 / 512)
        assert g.n >= 256 and g.m > 0


def test_partition_awareness_split(power_graph):
    g = power_graph
    part = partition_1d(g.n, 4)
    local, remote, stats = pa_split(g, part)
    # every edge lands in exactly one bucket
    assert int(local.count.sum() + remote.count.sum()) == g.m
    assert stats["cut_edges"] == int(remote.count.sum())
    # local edges: same owner; remote: different
    for p in range(4):
        ls = np.asarray(local.src[p])[: int(local.count[p])]
        ld = np.asarray(local.dst[p])[: int(local.count[p])]
        assert np.all(part.owner_np(ls) == part.owner_np(ld))
        assert np.all(part.owner_np(ls) == p)
        rs = np.asarray(remote.src[p])[: int(remote.count[p])]
        rd = np.asarray(remote.dst[p])[: int(remote.count[p])]
        assert np.all(part.owner_np(rs) != part.owner_np(rd))


def test_sampler_shapes_and_validity(small_graph):
    g = small_graph
    seeds = jnp.arange(16, dtype=jnp.int32)
    blocks = sample_blocks(g, seeds, (4, 3), jax.random.PRNGKey(0))
    assert blocks.node_ids[0].shape == (16,)
    assert blocks.node_ids[1].shape == (64,)
    assert blocks.node_ids[2].shape == (192,)
    # sampled children are real in-neighbors of their parents
    ids1 = np.asarray(blocks.node_ids[1]).reshape(16, 4)
    ok1 = np.asarray(blocks.valid[1]).reshape(16, 4)
    in_ptr = np.asarray(g.in_ptr)
    nbr = np.asarray(g.coo_src)
    for i in range(16):
        neigh = set(nbr[in_ptr[i]: in_ptr[i + 1]].tolist())
        for j in range(4):
            if ok1[i, j]:
                assert int(ids1[i, j]) in neigh


@given(n=st.integers(17, 200), p=st.integers(1, 9))
def test_partition_covers(n, p):
    part = partition_1d(n, p)
    owners = part.owner_np(np.arange(n))
    assert owners.min() >= 0 and owners.max() <= p - 1
    # contiguous blocks
    assert np.all(np.diff(owners) >= 0)


# -- pa_regroup_by_dst properties ---------------------------------------
# The destination-owner regroup is both the distributed pull layout and
# the push kernel's phase-1 binning pass (kernels/coo_push.py), so its
# invariants are load-bearing in two subsystems.
def _flat_edges(src, dst, w, n):
    """Wrap a flat edge list as a single-row PartitionedEdges."""
    m = len(src)
    if m == 0:
        return PartitionedEdges(
            src=jnp.full((1, 1), n, jnp.int32),
            dst=jnp.full((1, 1), n, jnp.int32),
            w=jnp.zeros((1, 1), jnp.float32),
            valid=jnp.zeros((1, 1), bool),
            count=jnp.zeros((1,), jnp.int32), cap=1, num_parts=1)
    return PartitionedEdges(
        src=jnp.asarray(src, jnp.int32).reshape(1, -1),
        dst=jnp.asarray(dst, jnp.int32).reshape(1, -1),
        w=jnp.asarray(w, jnp.float32).reshape(1, -1),
        valid=jnp.ones((1, m), bool),
        count=jnp.asarray([m], jnp.int32), cap=m, num_parts=1)


def _bin_rows(part, binned):
    """Per-bin edge tuples, in packed order, padding stripped."""
    out = []
    for p in range(part.num_parts):
        k = int(binned.count[p])
        out.append(list(zip(np.asarray(binned.src[p])[:k].tolist(),
                            np.asarray(binned.dst[p])[:k].tolist(),
                            np.asarray(binned.w[p])[:k].tolist())))
    return out


@given(n=st.integers(9, 120), m=st.integers(0, 240),
       parts=st.integers(1, 6), seed=st.integers(0, 2))
def test_regroup_by_dst_bins_every_edge_in_its_dst_bin(n, m, parts, seed):
    """Totality + ownership: every input edge appears exactly once, in
    the row owned by its destination; padding slots carry the sentinel."""
    rng = np.random.RandomState(seed)
    src = rng.randint(0, n, m)
    dst = rng.randint(0, n, m)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32)
    part = partition_1d(n, parts)
    binned = pa_regroup_by_dst(part, _flat_edges(src, dst, w, n), n,
                               align=8)
    rows = _bin_rows(part, binned)
    assert sum(len(r) for r in rows) == m
    got = sorted(e for r in rows for e in r)
    want = sorted(zip(src.tolist(), dst.tolist(),
                      np.asarray(w, np.float32).tolist()))
    assert got == want
    for p, row in enumerate(rows):
        assert all(int(part.owner_np(np.int64(d))) == p
                   for _, d, _ in row)
        pad_dst = np.asarray(binned.dst[p])[len(row):]
        assert np.all(pad_dst == n)
        assert not np.asarray(binned.valid[p])[len(row):].any()


@given(n=st.integers(9, 120), m=st.integers(1, 240),
       parts=st.integers(1, 6), seed=st.integers(0, 2))
def test_regroup_by_dst_is_stable_and_permutation_invariant(n, m, parts,
                                                            seed):
    """Stability: within a bin, edges keep their input order (the push
    kernel's run pointers require it). Permutation invariance: shuffling
    the input permutes within-bin order but never the per-bin edge
    multiset."""
    rng = np.random.RandomState(seed + 100)
    src = rng.randint(0, n, m)
    dst = rng.randint(0, n, m)
    w = rng.uniform(0.5, 2.0, m).astype(np.float32)
    part = partition_1d(n, parts)
    rows = _bin_rows(part, pa_regroup_by_dst(
        part, _flat_edges(src, dst, w, n), n, align=8))
    own = part.owner_np(dst)
    triples = list(zip(src.tolist(), dst.tolist(),
                       np.asarray(w, np.float32).tolist()))
    for p, row in enumerate(rows):
        assert row == [t for t, o in zip(triples, own) if o == p]
    perm = rng.permutation(m)
    shuf = _bin_rows(part, pa_regroup_by_dst(
        part, _flat_edges(src[perm], dst[perm], w[perm], n), n, align=8))
    for row, srow in zip(rows, shuf):
        assert sorted(row) == sorted(srow)
