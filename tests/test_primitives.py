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
from repro.core.primitives import COMBINE_FNS
from repro.graphs import build_graph, erdos_renyi


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
