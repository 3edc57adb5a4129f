"""PallasBackend: the push/pull Pallas kernels on the engine hot path.

Three layers of evidence that the kernels are production-grade:

  * kernel vs primitive parity — ``ell_spmv_pallas`` ≡ ``pull_relax_ell``
    and ``coo_push_pallas`` ≡ ``push_relax`` across combine × dtype ×
    payload rank × ragged n, interpret mode (bit-exact wherever the
    reduction order matches, incl. the empty-row combine identity);
  * dispatch — msg_fn classification, the jnp fallback for unsupported
    cells, the traced bin-capacity guard, the shape-keyed autotuner
    cache and its on-disk tier, the backend's per-graph bin-plan cache;
  * end to end — ``solve(..., backend="pallas")`` reproduces the dense
    backend on BFS / PageRank / SSSP for push, pull, and auto policies,
    and ``solve_batch`` runs [n, B] payloads through the kernel path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import Cost, EllBackend, PallasBackend, classify_msg_fn
from repro.core.primitives import (combine_identity, pull_relax_ell,
                                   push_relax)
from repro.graphs import build_graph, erdos_renyi
from repro.kernels.coo_push import (PUSH_STRATEGIES, build_push_plan,
                                    coo_push_pallas)
from repro.kernels.ell_spmv import ell_spmv_pallas
from repro.kernels.tune import pull_candidates, push_candidates

COMBINES = ("sum", "max", "min")
DTYPES = (jnp.float32, jnp.int32, jnp.int64)


@pytest.fixture(scope="module")
def ragged_graph():
    # n = 130: not a multiple of any kernel block size -> exercises the
    # grid padding rows/edges on every call
    return erdos_renyi(130, 4.0, seed=1, weighted=True)


def _payload(g, dtype, batch):
    shape = (g.n,) if batch is None else (g.n, batch)
    key = jax.random.PRNGKey(7)
    if jnp.issubdtype(dtype, jnp.floating):
        return jax.random.normal(key, shape, dtype)
    return jax.random.randint(key, shape, -50, 50).astype(dtype)


def _assert_kernel_equal(got, want, order_matches: bool):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype
    if order_matches or got.dtype.kind != "f":
        np.testing.assert_array_equal(got, want)
    else:  # float sums over a different edge order: tight allclose
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- kernel vs primitive parity -----------------------------------------
@pytest.mark.parametrize("batch", [None, 3])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("combine", COMBINES)
def test_ell_kernel_matches_pull_primitive(ragged_graph, combine, dtype,
                                           batch):
    """msg="copy" (the primitives' msg_fn=None) — same row reduce, same
    empty-row identity, bit for bit, at a non-block-aligned n."""
    g = ragged_graph
    x = _payload(g, dtype, batch)
    want, _ = pull_relax_ell(g, x, combine=combine)
    xp = jnp.pad(x, [(0, 1)] + [(0, 0)] * (x.ndim - 1))
    got = ell_spmv_pallas(xp, g.ell_idx, g.ell_w, combine=combine,
                          msg="copy", block_n=64)
    _assert_kernel_equal(got, want, order_matches=True)


@pytest.mark.parametrize("strategy", PUSH_STRATEGIES)
@pytest.mark.parametrize("batch", [None, 3])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("combine", COMBINES)
def test_coo_kernel_matches_push_primitive(ragged_graph, combine, dtype,
                                           batch, strategy):
    """Partial frontier push: kernel combine over dst-sorted edges ≡
    push_relax's segment combine (float sums differ only in edge
    order), for both phase-2 reduce strategies."""
    g = ragged_graph
    x = _payload(g, dtype, batch)
    frontier = jax.random.uniform(jax.random.PRNGKey(3), (g.n,)) < 0.4
    want, _ = push_relax(g, x, frontier, combine=combine)
    got = coo_push_pallas(x, frontier, g.coo_src, g.coo_dst, g.coo_w,
                          g.n, combine=combine, msg="copy", block_e=64,
                          block_n=128, strategy=strategy)
    order_matches = not (combine == "sum"
                         and jnp.issubdtype(dtype, jnp.floating))
    _assert_kernel_equal(got, want, order_matches=order_matches)


@pytest.mark.parametrize("msg,msg_fn", [
    ("mul", lambda v, w: v * w), ("add", lambda v, w: v + w)])
def test_kernel_msg_modes_match_msg_fns(ragged_graph, msg, msg_fn):
    g = ragged_graph
    x = _payload(g, jnp.float32, None)
    want, _ = pull_relax_ell(g, x, combine="min", msg_fn=msg_fn)
    got = ell_spmv_pallas(jnp.pad(x, (0, 1)), g.ell_idx, g.ell_w,
                          combine="min", msg=msg, block_n=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    want, _ = push_relax(g, x, jnp.ones((g.n,), bool), combine="min",
                         msg_fn=msg_fn)
    got = coo_push_pallas(x, jnp.ones((g.n,), bool), g.coo_src,
                          g.coo_dst, g.coo_w, g.n, combine="min",
                          msg=msg, block_e=64, block_n=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ell_empty_rows_hold_combine_identity():
    """The old kernel rewrote empty-row ±inf to 0.0; it must return the
    combine identity so mask_untouched/convergence agree bit-for-bit."""
    # vertex 5 has no in-edges at all (edges only among 0..4)
    g = build_graph([0, 1, 2, 3], [1, 2, 3, 4], n=6)
    x = jnp.arange(1.0, 7.0, dtype=jnp.float32)
    for combine in COMBINES:
        got = ell_spmv_pallas(jnp.pad(x, (0, 1)), g.ell_idx, g.ell_w,
                              combine=combine, msg="copy", block_n=8)
        want, _ = pull_relax_ell(g, x, combine=combine)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        ident = combine_identity(combine, jnp.float32)
        assert np.asarray(got)[5] == np.asarray(ident)
        assert np.asarray(got)[0] == np.asarray(ident)  # 0 in-deg too


def test_coo_push_padding_never_aims_at_last_vertex():
    """Regression: padded edges used to carry dst = n-1 (a real
    vertex). A graph whose last vertex has nonzero in-degree must get
    exactly its own messages, for every block shape that forces
    padding."""
    n = 9
    g = build_graph(np.arange(8), np.full(8, 8), n=n)  # all into v8
    x = jnp.arange(1.0, 10.0, dtype=jnp.float32)
    act = jnp.ones((n,), bool)
    want, _ = push_relax(g, x, act)
    for block_e, block_n in ((16, 8), (8, 8), (32, 16)):
        got = coo_push_pallas(x, act, g.coo_src, g.coo_dst, g.coo_w, n,
                              block_e=block_e, block_n=block_n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
    # min-combine would surface a sentinel aimed at v8 as a wrong 0
    want, _ = push_relax(g, x, act, combine="min")
    got = coo_push_pallas(x, act, g.coo_src, g.coo_dst, g.coo_w, n,
                          combine="min", block_e=16, block_n=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_push_bin_cap_guard_falls_back_correctly():
    """Traced path (the engine jits the graph): when the static bin
    capacity cannot hold the skewest bin, the lax.cond fits guard must
    route to the jnp branch and still produce the primitive's answer;
    with enough capacity the same trace takes the kernel. Each run of
    the jnp branch is counted as ``fallback_push_overflow``."""
    # 16 edges all into dst 0: bin 0 holds 16 edges
    src = np.arange(16)
    dst = np.zeros(16, np.int64)
    g = build_graph(src, dst, n=24)
    x = jnp.arange(24, dtype=jnp.float32)
    act = jnp.ones((24,), bool)
    want, _ = push_relax(g, x, act)
    for cap, overflows in ((8, 1), (32, 0)):  # 8 < 16 edges -> jnp
        backend = PallasBackend(block_e=8, push_block_n=8,
                                push_strategy="scan", push_bin_cap=cap,
                                autotune=False)
        out = jax.jit(lambda g, v, f, b=backend: b.push(
            g, v, f, "sum", None, Cost())[0])(g, x, act)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6)
        jax.effects_barrier()
        assert backend.stats["fallback_push_overflow"] == overflows


def test_push_edgeless_bin_holds_combine_identity():
    """Regression (mirrors the PR 5 empty-ELL-row fix): a bin whose
    edge block is all padding must return the combine identity without
    reading the sentinel row — a min over negative payloads would
    surface any sentinel read as a wrong value."""
    n = 256
    # edges land only in bin 1 (dst >= 128): bin 0 is pure padding
    src = np.arange(12)
    dst = np.arange(130, 142)
    g = build_graph(src, dst, n=n)
    x = -jnp.arange(1.0, n + 1.0, dtype=jnp.float32)  # all negative
    act = jnp.ones((n,), bool)
    for strategy in PUSH_STRATEGIES:
        for combine in COMBINES:
            plan = build_push_plan(g.coo_src, g.coo_dst, g.coo_w, n,
                                   bin_n=128, align=64)
            got = coo_push_pallas(x, act, g.coo_src, g.coo_dst, g.coo_w,
                                  n, combine=combine, msg="copy",
                                  block_e=64, block_n=128, plan=plan,
                                  strategy=strategy)
            want, _ = push_relax(g, x, act, combine=combine)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            ident = combine_identity(combine, jnp.float32)
            assert (np.asarray(got)[:128] == np.asarray(ident)).all()


def test_edgeless_graph_runs_on_pallas():
    """m=0 regression: grid=(0,) pallas_call crashed; every destination
    must hold the combine identity, like the segment primitives."""
    g = build_graph(np.zeros((0,), np.int64), np.zeros((0,), np.int64),
                    n=5)
    x = jnp.arange(5, dtype=jnp.float32)
    act = jnp.ones((5,), bool)
    for combine in COMBINES:
        got = coo_push_pallas(x, act, g.coo_src, g.coo_dst, g.coo_w,
                              g.n, combine=combine, msg="copy")
        want, _ = push_relax(g, x, act, combine=combine)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    r = api.solve(g, "bfs", root=0, backend=PallasBackend())
    assert int(np.asarray(r.state["dist"])[0]) == 0
    assert bool(r.converged)


def test_backend_instances_hash_by_identity():
    """Engine caches key on the backend: differently-configured
    PallasBackends must not compare equal (eq=False alone inherited
    EllBackend's field-blind value equality)."""
    a = PallasBackend()
    b = PallasBackend(autotune=False, block_n=64, block_e=8,
                      push_block_n=8, interpret=True)
    assert a != b                 # value equality would collide caches
    assert a == a
    assert hash(a) == hash(a)     # usable as an engine-cache key
    from repro.core import DistributedBackend
    assert DistributedBackend.__eq__ is not EllBackend.__eq__


# -- dispatch -----------------------------------------------------------
def test_classify_msg_fn_modes():
    assert classify_msg_fn(None) == "copy"
    assert classify_msg_fn(lambda v, w: v) == "copy"
    assert classify_msg_fn(lambda v, w: v * w) == "mul"
    assert classify_msg_fn(lambda v, w: v + w) == "add"
    assert classify_msg_fn(lambda v, w: v * v) is None
    assert classify_msg_fn(lambda v, w: w) is None

    # classification must also work while an outer trace is live (the
    # engine classifies during while_loop tracing)
    seen = []

    def traced(v):
        seen.append(classify_msg_fn(lambda x, w: x + w))
        return v

    jax.jit(traced)(jnp.ones((3,)))
    assert seen == ["add"]


def test_unsupported_msg_fn_falls_back_to_ell_path(ragged_graph):
    g = ragged_graph
    x = _payload(g, jnp.float32, None)
    weird = lambda v, w: v * v  # noqa: E731
    backend = PallasBackend()
    before = dict(backend.stats)
    got, _ = backend.pull(g, x, None, "sum", weird, Cost())
    want, _ = EllBackend().pull(g, x, None, "sum", weird, Cost())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert backend.stats["fallback_pull"] == before["fallback_pull"] + 1
    got, _ = backend.push(g, x, jnp.ones((g.n,), bool), "sum", weird,
                          Cost())
    want, _ = push_relax(g, x, jnp.ones((g.n,), bool), msg_fn=weird)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)
    assert backend.stats["fallback_push"] == before["fallback_push"] + 1


def test_compiled_backend_sends_64bit_payloads_to_counted_fallback(
        ragged_graph):
    """Compiled kernels take 32-bit payloads: a 64-bit cell is routed to
    the jnp path up front and counted, never handed to Mosaic."""
    g = ragged_graph
    x = _payload(g, jnp.int64, None)
    backend = PallasBackend(interpret=False)
    got, _ = backend.pull(g, x, None, "min", None, Cost())
    want, _ = EllBackend().pull(g, x, None, "min", None, Cost())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got, _ = backend.push(g, x, jnp.ones((g.n,), bool), "min", None,
                          Cost())
    want, _ = push_relax(g, x, jnp.ones((g.n,), bool), combine="min")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert backend.stats["fallback_pull"] == 1
    assert backend.stats["fallback_push"] == 1
    assert backend.stats["kernel_pull"] == backend.stats["kernel_push"] == 0


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_kernel_failure_is_raised_not_served_from_jnp(ragged_graph,
                                                      monkeypatch,
                                                      direction):
    """Only injected faults take the degradation ladder: a kernel that
    fails for real propagates, so a chip run cannot time jnp and report
    it as the kernel."""
    g = ragged_graph
    backend = PallasBackend()

    def broken(*a, **k):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(PallasBackend, f"_{direction}_kernel", broken)
    x = _payload(g, jnp.float32, None)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        if direction == "pull":
            backend.pull(g, x, None, "sum", None, Cost())
        else:
            backend.push(g, x, jnp.ones((g.n,), bool), "sum", None, Cost())
    assert backend.stats[f"fault_fallback_{direction}"] == 0
    assert backend.stats[f"fallback_{direction}"] == 0


def test_compiled_tuner_candidates_are_tile_legal():
    """Compiled ladders hold lane-aligned blocks whose working set fits
    the per-kernel VMEM cap, even where the interpreted ladder offers
    the whole vertex range."""
    from repro.kernels.coo_push import push_vmem_bytes
    from repro.kernels.ell_spmv import VMEM_CAP, ell_vmem_bytes
    from repro.kernels.tune import pull_frontier_candidates
    n, m, d_ell = 1 << 21, 1 << 26, 64
    for width in (1, 8):
        pulls = pull_candidates(n, width, d_ell=d_ell, compiled=True)
        pulls += pull_frontier_candidates(n, 1 << 19, width=width,
                                          d_ell=d_ell, compiled=True)
        assert pulls and all(c % 128 == 0 for c in pulls)
        assert all(ell_vmem_bytes(c, d_ell, width) <= VMEM_CAP
                   for c in pulls)
        pushes = push_candidates(n, m, width=width, compiled=True)
        assert pushes
        for block_e, bin_n, _ in pushes:
            assert block_e % 128 == 0 and bin_n % 128 == 0
            assert push_vmem_bytes(block_e, bin_n, width) <= VMEM_CAP
    assert any(b == n for _, b, _ in push_candidates(n, m))


def test_pallas_pull_charges_ell_cost_and_scans_all(ragged_graph):
    """A touched=None kernel pull scans every edge and charges exactly
    the ELL primitive's counters. pull_scans_all is now False — the
    frontier kernel restricts touched pulls (test_pull_frontier.py) —
    but the dense-destination case must keep the full-scan price."""
    g = ragged_graph
    assert not PallasBackend.pull_scans_all
    x = _payload(g, jnp.float32, None)
    backend = PallasBackend()
    _, c_kernel = backend.pull(g, x, None, "sum", None, Cost())
    _, c_ell = EllBackend().pull(g, x, None, "sum", None, Cost())
    assert c_kernel.as_dict() == c_ell.as_dict()
    _, p_kernel = backend.push(g, x, jnp.ones((g.n,), bool), "sum",
                               None, Cost())
    _, p_dense = push_relax(g, x, jnp.ones((g.n,), bool))
    # kernel push = primitive's charge + the phase-1 binning pass
    # (reads and rewrites every edge once)
    pk, pd = p_kernel.as_dict(), p_dense.as_dict()
    assert pk.pop("reads") == pd.pop("reads") + g.m
    assert pk.pop("writes") == pd.pop("writes") + g.m
    assert pk == pd


def test_autotuner_caches_per_shape(ragged_graph):
    g = ragged_graph
    backend = PallasBackend()
    x = _payload(g, jnp.float32, None)
    backend.pull(g, x, None, "sum", None, Cost())
    keys = set(backend._tuned)
    assert len(keys) == 1
    bn = next(iter(backend._tuned.values()))
    assert bn in pull_candidates(g.n)
    backend.pull(g, x, None, "sum", None, Cost())   # cache hit
    assert set(backend._tuned) == keys
    backend.push(g, x, jnp.ones((g.n,), bool), "sum", None, Cost())
    (pk,) = [k for k in backend._tuned if k[0] == "push"]
    assert backend._tuned[pk] in push_candidates(g.n, g.m)
    be, bn, strat = backend._tuned[pk]
    assert strat in PUSH_STRATEGIES
    # a partial pin overrides only its own component
    half = PallasBackend(push_block_n=512, autotune=False)
    pe, pn, ps = half._push_blocks(g, x, "sum", "copy")
    first = push_candidates(g.n, g.m)[0]
    assert (pe, pn, ps) == (first[0], 512, first[2])


def test_push_plan_cached_per_graph(ragged_graph):
    """Concrete-graph pushes build the phase-1 bin layout once and
    reuse it; a different (bin width, edge block) gets its own entry."""
    g = ragged_graph
    backend = PallasBackend(block_e=64, push_block_n=64,
                            push_strategy="scan", autotune=False)
    x = _payload(g, jnp.float32, None)
    act = jnp.ones((g.n,), bool)
    out, _ = backend.push(g, x, act, "sum", None, Cost())
    want, _ = push_relax(g, x, act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert len(backend._plans) == 1
    plan = next(iter(backend._plans.values()))[1]
    backend.push(g, x, act, "sum", None, Cost())
    assert next(iter(backend._plans.values()))[1] is plan  # cache hit
    assert len(backend._plans) == 1
    wide = PallasBackend(block_e=32, push_block_n=128,
                         push_strategy="scan", autotune=False)
    wide.push(g, x, act, "sum", None, Cost())
    assert next(iter(wide._plans))[1:] == (128, 32)


def test_tuner_disk_cache_round_trip(tmp_path, monkeypatch, ragged_graph):
    """Tuned winners persist under $REPRO_CACHE_DIR and are served from
    disk after the in-memory tier is dropped — without re-probing."""
    import repro.kernels.tune as tune
    g = ragged_graph
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    tune.clear_memory_cache()
    try:
        got = tune.tune_push(g.n, g.m, 1, jnp.float32, "sum", "copy",
                             interpret=True)
        assert got in push_candidates(g.n, g.m)
        import json
        disk = json.loads((tmp_path / "tune.json").read_text())
        assert list(got) in [v for v in disk.values()]
        # second process simulation: cold memory, warm disk
        tune.clear_memory_cache()
        monkeypatch.setattr(tune, "_escaped",
                            lambda fn: pytest.fail("re-probed a cached "
                                                   "configuration"))
        assert tune.tune_push(g.n, g.m, 1, jnp.float32, "sum", "copy",
                              interpret=True) == got
    finally:
        tune.clear_memory_cache()  # drop state pointing at tmp_path


@pytest.mark.parametrize("garbage", [
    "", "{", "not json at all", '[1, 2, 3]', '"a string"', "null",
])
def test_tuner_survives_corrupt_disk_cache(tmp_path, monkeypatch,
                                           garbage):
    """A crashed or racing writer can leave anything in tune.json —
    truncated JSON, garbage bytes, or valid JSON that is not a dict.
    The tuner must fall back to the in-memory tier, still return a
    valid configuration, and atomically rewrite a healthy file."""
    import json

    import repro.kernels.tune as tune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    tune.clear_memory_cache()
    try:
        (tmp_path / "tune.json").write_text(garbage)
        assert tune.tune_pull(64, 8, 1, jnp.float32, "sum", "copy",
                              interpret=True) in pull_candidates(
                                  64, width=1)
        got = tune.tune_push(64, 256, 1, jnp.float32, "sum", "copy",
                             interpret=True)
        assert got in push_candidates(64, 256)
        # the corpse was replaced by a valid cache holding the winner
        disk = json.loads((tmp_path / "tune.json").read_text())
        assert isinstance(disk, dict) and list(got) in disk.values()
    finally:
        tune.clear_memory_cache()


def test_tuner_survives_poisoned_cache_entry(tmp_path, monkeypatch):
    """A key that parses but holds a garbage value (wrong type/arity)
    must not crash the tuner — it re-probes and overwrites the entry."""
    import json

    import repro.kernels.tune as tune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    tune.clear_memory_cache()
    try:
        good = tune.tune_push(64, 256, 1, jnp.float32, "sum", "copy",
                              interpret=True)
        cands = tune.push_candidates(64, 256)
        assert good in cands
        disk = json.loads((tmp_path / "tune.json").read_text())
        poisoned = {k: {"nested": "junk"} for k in disk}
        (tmp_path / "tune.json").write_text(json.dumps(poisoned))
        tune.clear_memory_cache()
        # the re-probe may crown a different near-tie winner under
        # machine load — the contract is a *valid* candidate and a
        # healed disk entry, not winner stability
        assert tune.tune_push(64, 256, 1, jnp.float32, "sum", "copy",
                              interpret=True) in cands
        healed = json.loads((tmp_path / "tune.json").read_text())
        assert all(isinstance(v, list) for v in healed.values())
        assert not any(v == {"nested": "junk"} for v in healed.values())
        assert tune.tune_pull(64, 8, 1, jnp.float32, "sum", "copy",
                              interpret=True) in pull_candidates(
                                  64, width=1)
    finally:
        tune.clear_memory_cache()


def test_tuner_concurrent_writers_keep_cache_valid(tmp_path,
                                                   monkeypatch):
    """Many threads writing winners concurrently: every put lands, the
    final file is valid JSON, and a cold reader sees every entry (the
    tmp-file + os.replace protocol never exposes a half-written
    file)."""
    import json
    import threading

    import repro.kernels.tune as tune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    tune.clear_memory_cache()
    try:
        keys = [f"cpu|race|{i}" for i in range(16)]

        def writer(i):
            tune._cache_put(keys[i], (i, i * 2, "scan"))
            # interleave reads of other threads' keys mid-race
            tune._cache_get(keys[(i + 7) % len(keys)])

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(len(keys))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        disk = json.loads((tmp_path / "tune.json").read_text())
        assert isinstance(disk, dict)
        assert set(keys) <= set(disk)
        tune.clear_memory_cache()  # cold reader
        for i, k in enumerate(keys):
            assert tune._cache_get(k) == (i, i * 2, "scan")
    finally:
        tune.clear_memory_cache()


def test_pull_frontier_candidates_ladder():
    """Frontier-block candidates stay within the padded row capacity
    and always offer at least one rung."""
    from repro.kernels.tune import pull_frontier_candidates
    for rows in (8, 24, 256, 4096):
        cands = pull_frontier_candidates(16384, rows)
        r_pad = -(-max(rows, 8) // 8) * 8
        assert cands
        assert all(8 <= c <= r_pad for c in cands)
        assert r_pad in cands


def test_pull_b1_candidates_prefer_sub_n_blocks():
    """The kernel_pull_*_b1 regression: single-column payloads must be
    tuned over sub-n blocks (the full-row rung loses to jnp there), so
    the rmat-sized candidate list drops the full-row rung entirely."""
    n = 16384  # the benchmark rmat scale
    cands = pull_candidates(n, width=1)
    assert cands and all(c < n for c in cands)
    # batched payloads keep the full-row rung as an option
    assert any(c >= n for c in pull_candidates(n, width=8))
    # tiny graphs where no ladder rung fits still get a block
    assert pull_candidates(64, width=1) == (64,)


def test_backend_shorthand_is_shared_singleton(ragged_graph):
    assert api._resolve_backend("pallas") is api.BACKEND_SHORTHANDS[
        "pallas"]
    with pytest.raises(ValueError, match="pallas"):
        api._resolve_backend("nope")
    r = api.solve(ragged_graph, "bfs", root=0, backend="pallas")
    assert int(r.steps) >= 1


# -- end to end ---------------------------------------------------------
def _assert_states_match(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        if la.dtype.kind == "f":
            np.testing.assert_allclose(la, lb, atol=1e-6)
        else:
            np.testing.assert_array_equal(la, lb)


@pytest.mark.parametrize("policy", ["push", "pull", "auto"])
@pytest.mark.parametrize("alg,kw", [
    ("bfs", {"root": 3}),
    ("pagerank", {"iters": 25}),
    ("sssp_delta", {"source": 3, "delta": 2.5}),
])
def test_solve_pallas_matches_dense(small_graph, alg, kw, policy):
    """The acceptance matrix: BFS / PageRank / SSSP × push / pull / auto
    through the kernel path reproduce the dense backend (int states bit
    for bit, float fixpoints at the suite's standard tolerance)."""
    backend = PallasBackend()
    dense = api.solve(small_graph, alg, policy=policy, **kw)
    pallas = api.solve(small_graph, alg, policy=policy, backend=backend,
                       **kw)
    _assert_states_match(dense.state, pallas.state)
    # the run dispatched kernels, not fallbacks (touched pulls now
    # trace through the frontier dispatch)
    assert (backend.stats["kernel_pull"]
            + backend.stats["kernel_pull_frontier"]
            + backend.stats["kernel_push"]) > 0
    assert backend.stats["fallback_pull"] == 0
    assert backend.stats["fallback_push"] == 0


def test_solve_batch_pallas_runs_kernel_path(small_graph):
    """Batched [n, B] payload columns ride the kernels: per-query states
    equal the dense batched run, and the dispatch counters prove the
    kernel path executed for both directions."""
    g = small_graph
    backend = PallasBackend()
    for alg, kw in (("bfs", {}), ("ppr", {"tol": 1e-6}),
                    ("sssp_delta", {"delta": 2.5})):
        dense = api.solve_batch(g, alg, sources=[0, 5, 9], **kw)
        pallas = api.solve_batch(g, alg, sources=[0, 5, 9],
                                 backend=backend, **kw)
        assert pallas.batch == 3
        for i in range(3):
            _assert_states_match(dense.states[i], pallas.states[i])
    assert (backend.stats["kernel_pull"]
            + backend.stats["kernel_pull_frontier"]) > 0
    assert backend.stats["kernel_push"] > 0
    assert backend.stats["fallback_pull"] == 0
    assert backend.stats["fallback_push"] == 0
    # batched shapes were tuned separately from scalar ones
    assert any(k[3] == 3 for k in backend._tuned)


def test_every_algorithm_runs_under_pallas(small_graph):
    """Coverage: all nine registered algorithms (and all policies they
    declare) execute under backend="pallas" — via kernels where the
    cell qualifies, via the transparent fallback elsewhere."""
    KW = {"bfs": {"root": 3}, "pagerank": {"iters": 5},
          "ppr": {"source": 3, "tol": 1e-5}, "wcc": {},
          "pr_delta": {"tol": 1e-5},
          "sssp_delta": {"source": 3, "delta": 2.5},
          "betweenness": {"num_sources": 2},
          "coloring": {"num_parts": 8}, "mst_boruvka": {},
          "triangle_count": {"edge_block": 512}}
    for name in api.algorithms():
        spec = api.get_spec(name)
        assert "pallas" in spec.backends
        r = api.solve(small_graph, name, backend="pallas", **KW[name])
        assert int(r.steps) >= 1
