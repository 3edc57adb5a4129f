"""Push/pull primitive properties — the paper's core equivalences.

Central property (paper §3.8): with every vertex active, push and pull
k-relaxations compute the SAME combined updates; they differ only in Cost
structure (push: combining writes; pull: reads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import (Cost, spmv_pull, spmspv_push, PLUS_TIMES, MIN_PLUS,
                        OR_AND, push_relax, pull_relax, pull_relax_ell,
                        combine_identity)
from repro.core.primitives import COMBINE_FNS, _masked_push, push_caps
from repro.graphs import build_graph, erdos_renyi, kronecker


def _rand_graph(seed, n=64, deg=3.0):
    return erdos_renyi(n, deg, seed=seed, weighted=True)


@given(seed=st.integers(0, 50), combine=st.sampled_from(["sum", "min", "max"]))
def test_push_equals_pull_full_frontier(seed, combine):
    g = _rand_graph(seed)
    x = jax.random.normal(jax.random.PRNGKey(seed), (g.n,))
    allv = jnp.ones((g.n,), bool)
    out_push, c_push = push_relax(g, x, allv, combine=combine)
    out_pull, c_pull = pull_relax(g, x, combine=combine)
    ident = combine_identity(combine, out_pull.dtype)
    np.testing.assert_allclose(np.asarray(out_push), np.asarray(out_pull),
                               rtol=1e-5, atol=1e-5)
    # Cost structure: pull never combines concurrently; push always does
    assert int(c_pull.atomics) == 0 and int(c_pull.locks) == 0
    assert int(c_push.locks) == g.m  # float payload -> lock-equivalents
    assert int(c_pull.reads) == g.m


@given(seed=st.integers(0, 30))
def test_pull_ell_equals_pull_coo(seed):
    g = _rand_graph(seed)
    x = jax.random.normal(jax.random.PRNGKey(seed), (g.n,))
    a, _ = pull_relax(g, x, combine="sum")
    b, _ = pull_relax_ell(g, x, combine="sum")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@given(seed=st.integers(0, 30))
def test_push_frontier_masks_sources(seed):
    g = _rand_graph(seed)
    x = jnp.ones((g.n,))
    frontier = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.4, (g.n,))
    out, cost = push_relax(g, x, frontier, combine="sum")
    # reference: dense masked segment count (default msg = x[src], no w)
    src = np.asarray(g.push_src)
    dst = np.asarray(g.push_dst)
    f = np.asarray(frontier)
    want = np.zeros(g.n, np.float32)
    np.add.at(want, dst, np.where(f[src], 1.0, 0.0))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    # cost charged proportional to frontier out-edges only
    assert int(cost.reads) == int(f[src].sum())


@given(seed=st.integers(0, 30),
       sr_name=st.sampled_from(["plus_times", "min_plus", "or_and"]))
def test_semiring_push_pull_equivalence(seed, sr_name):
    sr = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
          "or_and": OR_AND}[sr_name]
    g = _rand_graph(seed)
    key = jax.random.PRNGKey(seed + 99)
    x = jax.random.uniform(key, (g.n,), minval=0.1, maxval=2.0)
    nz = jnp.ones((g.n,), bool)
    y_pull, _ = spmv_pull(g, x, sr)
    y_push, _ = spmspv_push(g, x, nz, sr)
    np.testing.assert_allclose(np.asarray(y_pull), np.asarray(y_push),
                               rtol=1e-4, atol=1e-4)


def test_spmv_matches_dense_matmul():
    g = _rand_graph(3, n=40)
    x = jax.random.normal(jax.random.PRNGKey(0), (g.n,))
    A = np.zeros((g.n, g.n), np.float32)
    A[np.asarray(g.coo_dst), np.asarray(g.coo_src)] = np.asarray(g.coo_w)
    y, _ = spmv_pull(g, x, PLUS_TIMES)
    np.testing.assert_allclose(np.asarray(y), A @ np.asarray(x), rtol=1e-4,
                               atol=1e-4)


def test_spmspv_exploits_sparsity_in_cost():
    g = _rand_graph(5, n=80)
    x = jnp.ones((g.n,))
    nz = jnp.zeros((g.n,), bool).at[:8].set(True)
    _, c_sparse = spmspv_push(g, x, nz)
    _, c_dense = spmspv_push(g, x, jnp.ones((g.n,), bool))
    assert int(c_sparse.reads) < int(c_dense.reads)
    assert int(c_dense.reads) == g.m


def test_cost_pytree_arithmetic():
    c = Cost().charge(reads=5).charge(writes=3)
    c2 = c + c
    assert int(c2.reads) == 10 and int(c2.writes) == 6
    c3 = c.charge_combining_writes(7, float_data=True)
    assert int(c3.locks) == 7 and int(c3.atomics) == 0
    c4 = c.charge_combining_writes(7, float_data=False)
    assert int(c4.atomics) == 7 and int(c4.locks) == 0


# -- push's masked wire table ------------------------------------------
# Vertices 9-11 have no edges; 0 has out-edges to 1, 2, 3, 5 and 8.
_SRC = [0, 0, 0, 0, 0, 1, 1, 2, 3, 4, 4, 5, 6, 7, 8, 8, 2, 3]
_DST = [1, 2, 3, 5, 8, 0, 4, 3, 2, 6, 7, 4, 7, 8, 1, 6, 6, 6]
_N = 12


def _old_push_relax(g, values, frontier, combine, msg_fn):
    """The per-edge formulation: gather the frontier bit of every edge's
    source and mask the edge's message with it."""
    active_e = jnp.take(frontier, g.push_src, axis=0, mode="fill",
                        fill_value=False)
    msgs = jnp.take(values, g.push_src, axis=0, mode="fill", fill_value=0)
    if msg_fn is not None:
        msgs = msg_fn(msgs, g.push_w)
    active_b = active_e.reshape((-1,) + (1,) * (msgs.ndim - 1))
    msgs = jnp.where(active_b, msgs, combine_identity(combine, msgs.dtype))
    return COMBINE_FNS[combine](msgs, g.push_dst, g.n)


def _add_weight(x, w):
    return x + w.reshape(w.shape + (1,) * (x.ndim - 1)).astype(x.dtype)


def _values(dtype, width):
    """Payloads with NaN, -0.0 and +-inf (float) or the int32 extremes."""
    if dtype == np.float32:
        col = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 1.5, -2.25,
                        3.0, -0.0, 7.0, np.nan, 2.0], np.float32)
    else:
        col = np.array([2**31 - 1, -2**31, 0, 5, -7, 11, 3, -1, 2**31 - 1,
                        9, 4, -2**31], np.int32)
    if width == 1:
        return col
    return np.stack([col, np.roll(col, 1), np.roll(col, 5)], axis=1)


_FRONTIERS = {
    "empty": np.zeros(_N, bool),
    "single": np.eye(_N, dtype=bool)[0],
    "full": np.ones(_N, bool),
}


@pytest.mark.parametrize("msg_fn", [None, _add_weight],
                         ids=["no_msg_fn", "msg_fn"])
@pytest.mark.parametrize("frontier", sorted(_FRONTIERS))
@pytest.mark.parametrize("width", [1, 3])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_push_masked_table_is_bit_exact(combine, dtype, width, frontier,
                                        msg_fn):
    """Masking the wire table once per step gives, bit for bit, what
    masking every edge's message gives: NaN, -0.0 and the extremes
    included, on a graph with isolated vertices."""
    g = build_graph(np.array(_SRC), np.array(_DST), _N,
                    weights=np.linspace(0.5, 9.0, len(_SRC)))
    x = jnp.asarray(_values(dtype, width))
    f = jnp.asarray(_FRONTIERS[frontier])
    got, cost = push_relax(g, x, f, combine=combine, msg_fn=msg_fn)
    want = _old_push_relax(g, x, f, combine, msg_fn)
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    k = int(np.asarray(g.out_deg)[_FRONTIERS[frontier]].sum())
    assert int(cost.reads) == k * width
    if dtype == np.float32 and frontier == "full" and width == 1:
        assert np.isnan(got).any()   # NaN sources reach the combine


def _gathers(msg_fn) -> int:
    g = build_graph(np.array(_SRC), np.array(_DST), _N)
    f = jax.jit(lambda g_, x, fr: push_relax(g_, x, fr, combine="min",
                                             msg_fn=msg_fn))
    text = f.lower(g, jnp.zeros(_N, jnp.int32),
                   jnp.zeros(_N, bool)).as_text()
    return text.count('"stablehlo.gather"(')


@pytest.mark.parametrize("msg_fn,want", [(None, 1), (_add_weight, 2)],
                         ids=["no_msg_fn", "msg_fn"])
def test_push_gathers_once_per_edge_without_msg_fn(msg_fn, want):
    """Without ``msg_fn`` the push gathers only the masked wire table; the
    per-edge frontier gather stays on the ``msg_fn`` path alone."""
    assert _gathers(msg_fn) == want


# -- push over the frontier's edges alone --------------------------------
# A directed graph with m = 32,780 edges, so push_caps gives the two
# capacities 1,024 and 4,096: vertices 0-4095 send 8 edges each, 4096-4107
# one each, and 4108-4147 none.
_FN = 4148
_CAPS = (1024, 4096)


@pytest.fixture(scope="module")
def cap_graph():
    rng = np.random.default_rng(17)
    deg = np.r_[np.full(4096, 8), np.ones(12, int), np.zeros(40, int)]
    src = np.repeat(np.arange(_FN), deg)
    dst = rng.integers(0, _FN, len(src))
    g = build_graph(src, dst, _FN, weights=rng.random(len(src)) + 0.5)
    assert push_caps(g.m) == _CAPS
    return g


def _frontier(n8: int, n1: int = 0, n0: int = 0, seed: int = 0):
    """A frontier of ``n8`` vertices of out-degree 8, ``n1`` of degree 1
    and ``n0`` without edges: ``8 * n8 + n1`` frontier edges."""
    rng = np.random.default_rng(seed)
    f = np.zeros(_FN, bool)
    f[rng.choice(4096, n8, replace=False)] = True
    f[4096 + rng.choice(12, n1, replace=False)] = True
    f[4108 + rng.choice(40, n0, replace=False)] = True
    return f


def _payload(dtype, width: int, seed: int):
    """Random payloads salted with NaN, -0.0, +-inf or the int32 extremes."""
    rng = np.random.default_rng(seed)
    shape = (_FN,) if width == 1 else (_FN, width)
    if dtype == np.float32:
        x = rng.standard_normal(shape).astype(np.float32)
        special = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf], np.float32)
    else:
        x = rng.integers(-1000, 1000, shape).astype(np.int32)
        special = np.array([2**31 - 1, -2**31, 0], np.int32)
    pick = rng.random(shape) < 0.1
    x[pick] = rng.choice(special, int(pick.sum()))
    return jnp.asarray(x)


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("edges", [(1, 0), (40, 3), (127, 8), (400, 5)],
                         ids=lambda e: f"{8 * e[0] + e[1]}edges")
@pytest.mark.parametrize("width", [1, 3])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("combine", ["min", "max"])
def test_frontier_push_is_bit_exact(cap_graph, combine, dtype, width, edges):
    """Inside a capacity the push reads only the frontier's edges and
    still equals the all-edge masked push (``push.premask``) bit for
    bit, for both payload widths, NaN, -0.0 and the extremes included."""
    f = jnp.asarray(_frontier(*edges, n0=4, seed=sum(edges)))
    x = _payload(dtype, width, seed=sum(edges) + width)
    got, cost = push_relax(cap_graph, x, f, combine=combine)
    want = _masked_push(cap_graph, x, f, combine)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(_bits(got), _bits(want))
    assert int(cost.reads) == (8 * edges[0] + edges[1]) * width


@pytest.fixture(scope="module")
def wide_graph():
    """65,536 vertices and 40,000 random edges: capacities 1,024, where
    the frontier's vertices are found by binary search, and 4,096, where
    a scatter compacts them; most vertices have no out-edge."""
    rng = np.random.default_rng(23)
    src, dst = rng.integers(0, 2**16, (2, 40_000))
    g = build_graph(src, dst, 2**16)
    assert push_caps(g.m) == (1024, 4096)
    return g


@pytest.mark.parametrize("frontier", [1_000, 4_000, 20_000],
                         ids=["searched", "scattered", "dense"])
@pytest.mark.parametrize("width", [1, 3])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("combine", ["min", "max"])
def test_frontier_push_is_bit_exact_on_a_wide_graph(wide_graph, combine,
                                                    dtype, width, frontier):
    """Both ways of compacting the frontier give the all-edge answer bit
    for bit."""
    rng = np.random.default_rng(frontier + width)
    f = np.zeros(2**16, bool)
    f[rng.choice(2**16, frontier, replace=False)] = True
    x = np.asarray(_payload(dtype, width, frontier))
    x = jnp.asarray(np.resize(x, (2**16,) + x.shape[1:]))
    f = jnp.asarray(f)
    got, _ = push_relax(wide_graph, x, f, combine=combine)
    want = _masked_push(wide_graph, x, f, combine)
    assert np.array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n0", [0, 40], ids=["empty", "edgeless"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("combine", ["min", "max"])
def test_frontier_push_without_frontier_edges(cap_graph, combine, dtype, n0):
    """An empty frontier, or one of vertices without out-edges, sends
    nothing: every destination holds the combine's identity."""
    f = jnp.asarray(_frontier(0, 0, n0))
    got, cost = push_relax(cap_graph, _payload(dtype, 1, 3), f,
                           combine=combine)
    ident = np.full(_FN, combine_identity(combine, dtype))
    assert np.array_equal(_bits(got), _bits(ident))
    assert int(cost.reads) == 0


def _which_path(monkeypatch):
    """Make the frontier push answer with its capacity in every slot, so
    the output tells which path ran: a capacity, or the dense push."""
    from repro.core import primitives

    def marked(g, values, active, rank, combine, cap):
        return jnp.full(values.shape, cap, values.dtype)
    monkeypatch.setattr(primitives, "_frontier_push", marked)


@pytest.mark.parametrize("edges,path", [
    ((0, 0), 1024), ((128, 0), 1024), ((128, 1), 4096), ((512, 0), 4096),
    ((512, 1), "dense"), ((4096, 12), "dense"),
], ids=["0", "1024", "1025", "4096", "4097", "all"])
def test_push_takes_the_smallest_capacity_that_holds_the_frontier(
        cap_graph, monkeypatch, edges, path):
    """Frontier edges exactly at a capacity run in it, one more edge in
    the next; past the largest capacity the dense push runs, and gives
    the all-edge answer."""
    f = jnp.asarray(_frontier(*edges, n0=2))
    x = _payload(np.int32, 1, 5)
    want = _old_push_relax(cap_graph, x, f, "min", None)
    got, _ = push_relax(cap_graph, x, f, combine="min")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    _which_path(monkeypatch)
    got, _ = push_relax(cap_graph, x, f, combine="min")
    if path == "dense":
        assert np.array_equal(np.asarray(got), np.asarray(want))
    else:
        assert (np.asarray(got) == path).all()


@pytest.mark.parametrize("combine,msg_fn", [
    ("sum", None), ("sum", _add_weight), ("min", _add_weight),
    ("max", _add_weight)], ids=["sum", "sum-msg_fn", "min-msg_fn",
                                "max-msg_fn"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_sum_and_msg_fn_pushes_keep_the_all_edge_path(
        cap_graph, monkeypatch, combine, msg_fn, dtype):
    """A sum push, or one with ``msg_fn``, never takes the frontier path,
    even for a frontier that fits a capacity, and is unchanged bit for
    bit."""
    _which_path(monkeypatch)
    f = jnp.asarray(_frontier(60, 4, n0=3, seed=9))
    x = _payload(dtype, 1, 11)
    got, _ = push_relax(cap_graph, x, f, combine=combine, msg_fn=msg_fn)
    want = _old_push_relax(cap_graph, x, f, combine, msg_fn)
    assert np.array_equal(_bits(got), _bits(want))


def _bfs_reference(g, root: int):
    """Hop counts and min-id parents of a BFS from ``root``, in numpy."""
    src, dst = np.asarray(g.push_src), np.asarray(g.push_dst)
    n = g.n
    dist = np.full(n, 2**31 - 1, np.int64)
    parent = np.full(n, n, np.int64)
    dist[root], parent[root] = 0, root
    frontier, level = np.array([root]), 0
    while frontier.size:
        on = np.zeros(n, bool)
        on[frontier] = True
        e = on[src] & (dist[dst] > level + 1)
        cand = np.full(n, n, np.int64)
        np.minimum.at(cand, dst[e], src[e])
        new = np.flatnonzero(cand < n)
        dist[new], parent[new] = level + 1, cand[new]
        frontier, level = new, level + 1
    return dist, parent


@pytest.fixture(scope="module")
def bfs_graphs():
    return {"er": erdos_renyi(3000, 12.0, seed=2),
            "kron": kronecker(11, edge_factor=16, seed=4)}


@pytest.mark.parametrize("policy", ["push", "gs"])
@pytest.mark.parametrize("backend", ["dense", "ell"])
@pytest.mark.parametrize("graph", ["er", "kron"])
def test_bfs_through_the_frontier_push(bfs_graphs, graph, backend, policy):
    """BFS through ``api.solve`` on graphs with push capacities: the
    levels and the min-id parents match a numpy BFS, on the dense and
    the ELL backend, always pushing and under GenericSwitch."""
    from repro import api
    g = bfs_graphs[graph]
    assert push_caps(g.m)
    root = int(np.argmax(np.asarray(g.out_deg) > 0))
    r = api.solve(g, "bfs", root=root, policy=policy, backend=backend)
    dist, parent = _bfs_reference(g, root)
    assert np.array_equal(np.asarray(r.state["dist"]), dist)
    assert np.array_equal(np.asarray(r.state["parent"]), parent)
    assert int(r.push_steps) >= 1


@pytest.mark.parametrize("high", [2, 300, 2**14])
@pytest.mark.parametrize("size", [1, 127, 128, 129, 16_387, 70_000])
def test_prefix_sum_on_the_matrix_unit_is_exact(size, high):
    """The frontier push's prefix sum equals numpy's for 0/1 marks and
    for degrees, across row boundaries and several levels of rows."""
    from repro.core.primitives import _cumsum
    x = np.random.default_rng(size + high).integers(0, high, size)
    x = x.astype(np.int32)
    assert np.array_equal(np.asarray(_cumsum(jnp.asarray(x))), np.cumsum(x))


def test_prefix_sum_reaches_the_int32_limit():
    """Every byte of an int32 count takes part: a total just under 2**31
    comes through whole."""
    from repro.core.primitives import _cumsum
    x = np.zeros(1000, np.int32)
    x[[3, 500, 999]] = [2**31 - 2**24 - 5, 2**24, 4]
    assert np.array_equal(np.asarray(_cumsum(jnp.asarray(x))), np.cumsum(x))
