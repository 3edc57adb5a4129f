"""Kernel-level push/pull wall-clock suite — the ``kernel_*`` rows.

The first *wall-clock* (not counter-only) trajectory in BENCH: for every
(direction × combine × graph family × batch width) cell, time the jnp
primitive (``pull_relax_ell`` / ``push_relax``) against the Pallas
kernel (``ell_spmv_pallas`` / ``coo_push_pallas``) at the autotuned
configuration (block sizes + push reduce strategy; push runs on a
prebuilt phase-1 bin plan, matching the backend's per-graph cache),
check they agree, and emit one schema-validated ``kernel_cell`` row
(``benchmarks/schema.json``). Every row also reports its analytic
roofline anchors — ``bytes_moved``, ``flops``, ``pct_roofline`` (via
``repro.roofline.analysis.kernel_roofline``) — so the trajectory tracks
distance-to-hardware, not just distance-to-jnp. The bound is always the
v5e target's: on a CPU these rows time the Pallas interpreter, and
their ``pct_roofline`` says nothing about a chip.

    PYTHONPATH=src python -m benchmarks.run --only kernels \
        --json BENCH_kernels.json

``kernel_pullf_*`` rows cover the frontier-restricted pull
(``ell_pull_frontier_pallas``) on BFS-shaped touched sets at ≤10%
density, against both the jnp masked pull (``us_jnp``) and the
full-scan kernel + mask (``us_full_kernel``) — the committed run must
show the frontier kernel beating the full scan on at least one sparse
cell, which is the wall-clock grounding for ``PallasBackend`` pricing
restricted pulls cheaper than ``(m, n)``.

``--smoke`` shrinks to the RMAT family × sum × both directions (CI
asserts the rows exist and validate — interpreter wall-clock is only
meaningful relatively, and only the committed full run claims the
pull-side win). The model kernels (flash attention, CIN) keep a small
sanity row each under the ``aux_`` prefix.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .common import emit, timeit


def _graphs(smoke: bool):
    from repro.graphs import erdos_renyi, kronecker
    if smoke:
        return {"rmat": kronecker(7, edge_factor=6, seed=7,
                                  weighted=True)}
    return {
        "rmat": kronecker(10, edge_factor=8, seed=7, weighted=True),
        "uniform": erdos_renyi(1024, 8.0, seed=5, weighted=True),
    }


def _payload(g, batch: int, dtype):
    shape = (g.n,) if batch == 1 else (g.n, batch)
    key = jax.random.PRNGKey(3)
    if jnp.issubdtype(dtype, jnp.floating):
        return jax.random.normal(key, shape, dtype)
    return jax.random.randint(key, shape, -100, 100).astype(dtype)


@functools.partial(jax.jit, static_argnames=("combine",))
def _jnp_pull(g, x, combine):
    from repro.core.primitives import pull_relax_ell
    return pull_relax_ell(g, x, combine=combine)[0]


@functools.partial(jax.jit, static_argnames=("combine",))
def _jnp_push(g, x, active, combine):
    from repro.core.primitives import push_relax
    return push_relax(g, x, active, combine=combine)[0]


@functools.partial(jax.jit, static_argnames=("combine",))
def _jnp_pull_masked(g, x, touched, combine):
    from repro.core.primitives import mask_untouched, pull_relax_ell
    out = pull_relax_ell(g, x, combine=combine)[0]
    return mask_untouched(out, touched, combine)


@functools.partial(jax.jit,
                   static_argnames=("combine", "rows_n", "block_r"))
def _pallas_pullf(xp, ell_idx, ell_w, touched, combine, rows_n, block_r):
    # compaction + frontier kernel + identity scatter under one jit —
    # how the engine's traced pull path runs it (eager nonzero dispatch
    # would otherwise dominate the measurement)
    from repro.kernels.ell_pull_frontier import (ell_pull_frontier_full,
                                                 frontier_rows)
    rows = frontier_rows(touched, rows_n)
    return ell_pull_frontier_full(xp, ell_idx, ell_w, rows,
                                  combine=combine, msg="copy",
                                  block_r=block_r)


def _bfs_touched_sets(g, layout, max_density=0.10, max_levels=4, keep=2):
    """BFS-shaped touched sets: each BFS level's frontier, expanded to
    the destinations its pull step would touch (N_out of the frontier —
    what the engine's ``touched_fn`` hands the backend). Keeps the
    first ``keep`` levels at ≤ ``max_density`` — the sparse-frontier
    regime where restricting the scan is supposed to pay."""
    from repro import api
    from repro.kernels.layout import touched_out_mask
    dist = np.asarray(api.solve(g, "bfs", root=0).state["dist"])
    out = []
    for lv in range(max_levels):
        frontier = jnp.asarray(dist == lv)
        if not bool(frontier.any()):
            break
        touched = touched_out_mask(layout, frontier)
        cnt = int(jnp.sum(touched))
        if cnt and cnt / g.n <= max_density:
            out.append((lv, touched, cnt))
        if len(out) == keep:
            break
    return out


def _agree(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind == "f":
        return bool(np.allclose(a, b, rtol=1e-5, atol=1e-5,
                                equal_nan=True))
    return bool(np.array_equal(a, b))


def _cell(direction, combine, gname, g, batch, extra):
    return dict({
        "direction": direction, "combine": combine, "graph": gname,
        "n": int(g.n), "m": int(g.m), "d_ell": int(g.d_ell),
        "batch": int(batch), "dtype": "float32", "msg": "copy",
    }, **extra)


def run():
    from repro.graphs.structure import pad_values
    from repro.kernels.coo_push import build_push_plan, coo_push_pallas
    from repro.kernels.ell_spmv import ell_spmv_pallas
    from repro.kernels.tune import tune_pull, tune_push
    from repro.roofline.analysis import V5E, kernel_roofline

    combines = ("sum",) if common.SMOKE else ("sum", "min")
    batches = (1, 8)
    # interpret-mode medians at 2-3 iters are noisy enough to flip the
    # CI regression gate; 7 stabilizes them at negligible suite cost
    iters = 7

    for gname, g in _graphs(common.SMOKE).items():
        for combine in combines:
            for batch in batches:
                x = _payload(g, batch, jnp.float32)
                # ---- pull: jnp ELL gather vs Pallas ell_spmv --------
                us_jnp = timeit(lambda: _jnp_pull(g, x, combine),
                                iters=iters)
                block_n = tune_pull(g.n, g.d_ell, batch, x.dtype,
                                    combine, "copy")
                xp = pad_values(x)
                pallas_pull = lambda: ell_spmv_pallas(  # noqa: E731
                    xp, g.ell_idx, g.ell_w, combine=combine, msg="copy",
                    block_n=block_n)
                us_pal = timeit(pallas_pull, iters=iters)
                roof = kernel_roofline(
                    "pull", device_kind=V5E, n=g.n, d_ell=g.d_ell,
                    batch=batch,
                    itemsize=x.dtype.itemsize, measured_us=us_pal)
                cell = _cell("pull", combine, gname, g, batch, {
                    "block_n": int(block_n),
                    "us_jnp": round(us_jnp, 1),
                    "us_pallas": round(us_pal, 1),
                    "speedup": round(us_jnp / max(us_pal, 1e-9), 3),
                    "match": _agree(_jnp_pull(g, x, combine),
                                    pallas_pull()),
                    "bytes_moved": roof["bytes_moved"],
                    "flops": roof["flops"],
                    "pct_roofline": roof["pct_roofline"],
                })
                emit(f"kernel_pull_{combine}_{gname}_b{batch}", us_pal,
                     json.dumps(cell))

                # ---- push: jnp segment scatter vs Pallas coo_push ---
                active = jnp.ones((g.n,), bool)
                us_jnp = timeit(lambda: _jnp_push(g, x, active, combine),
                                iters=iters)
                block_e, pbn, strategy = tune_push(
                    g.n, g.m, batch, x.dtype, combine, "copy")
                # phase-1 bin layout: built once per graph and cached on
                # the backend in production, so timed separately here
                plan = build_push_plan(g.coo_src, g.coo_dst, g.coo_w,
                                       g.n, pbn, align=block_e)
                pallas_push = lambda: coo_push_pallas(  # noqa: E731
                    x, active, g.coo_src, g.coo_dst, g.coo_w, g.n,
                    combine=combine, msg="copy", block_e=block_e,
                    block_n=pbn, plan=plan, strategy=strategy)
                us_pal = timeit(pallas_push, iters=iters)
                roof = kernel_roofline(
                    "push", device_kind=V5E, n=g.n, batch=batch,
                    itemsize=x.dtype.itemsize, nb=plan.nb, cap=plan.cap,
                    bin_n=plan.bin_n, measured_us=us_pal)
                cell = _cell("push", combine, gname, g, batch, {
                    "block_e": int(block_e), "block_n": int(pbn),
                    "strategy": strategy, "bins": int(plan.nb),
                    "us_jnp": round(us_jnp, 1),
                    "us_pallas": round(us_pal, 1),
                    "speedup": round(us_jnp / max(us_pal, 1e-9), 3),
                    "match": _agree(_jnp_push(g, x, active, combine),
                                    pallas_push()),
                    "bytes_moved": roof["bytes_moved"],
                    "flops": roof["flops"],
                    "pct_roofline": roof["pct_roofline"],
                })
                emit(f"kernel_push_{combine}_{gname}_b{batch}", us_pal,
                     json.dumps(cell))

    # ---- frontier pull: touched-row gather vs full scan + mask ------
    # kernel_pullf_* rows time the PR 8 dispatch against both honest
    # baselines on the SAME touched set: the jnp full pull + mask
    # (us_jnp) and the full-scan Pallas kernel + mask (us_full_kernel,
    # the pre-frontier kernel path). us_pallas includes the frontier
    # compaction and identity scatter, so the speedup is end to end.
    from repro.core.primitives import mask_untouched
    from repro.kernels.layout import build_dual_ell
    from repro.kernels.tune import tune_pull_frontier

    for gname, g in _graphs(common.SMOKE).items():
        layout = build_dual_ell(g)
        fronts = _bfs_touched_sets(g, layout)
        xp_cache = {}
        for combine in combines:
            for batch in batches:
                x = xp_cache.setdefault(batch, _payload(g, batch,
                                                        jnp.float32))
                xp = pad_values(x)
                block_n = tune_pull(g.n, g.d_ell, batch, x.dtype,
                                    combine, "copy")
                for lv, touched, cnt in fronts:
                    # same pow-of-two row-capacity bucketing as the
                    # backend's concrete dispatch
                    rows_n = max(8, 1 << (cnt - 1).bit_length())
                    us_jnp = timeit(
                        lambda: _jnp_pull_masked(g, x, touched, combine),
                        iters=iters)
                    full_kernel = lambda: mask_untouched(  # noqa: E731
                        ell_spmv_pallas(xp, g.ell_idx, g.ell_w,
                                        combine=combine, msg="copy",
                                        block_n=block_n),
                        touched, combine)
                    us_full = timeit(full_kernel, iters=iters)
                    block_r = tune_pull_frontier(
                        g.n, g.d_ell, rows_n, batch, x.dtype, combine,
                        "copy")
                    pallas_f = lambda: _pallas_pullf(  # noqa: E731
                        xp, layout.in_idx, layout.in_w, touched,
                        combine, rows_n, block_r)
                    us_pal = timeit(pallas_f, iters=iters)
                    roof = kernel_roofline(
                        "pullf", device_kind=V5E, n=rows_n,
                        d_ell=g.d_ell, batch=batch,
                        itemsize=x.dtype.itemsize, measured_us=us_pal)
                    cell = _cell("pullf", combine, gname, g, batch, {
                        "block_n": int(block_r),
                        "rows": int(rows_n),
                        "density": round(cnt / g.n, 4),
                        "us_jnp": round(us_jnp, 1),
                        "us_full_kernel": round(us_full, 1),
                        "us_pallas": round(us_pal, 1),
                        "speedup": round(us_full / max(us_pal, 1e-9), 3),
                        "match": _agree(
                            _jnp_pull_masked(g, x, touched, combine),
                            pallas_f()),
                        "bytes_moved": roof["bytes_moved"],
                        "flops": roof["flops"],
                        "pct_roofline": roof["pct_roofline"],
                    })
                    emit(f"kernel_pullf_{combine}_{gname}_b{batch}_L{lv}",
                         us_pal, json.dumps(cell))

    # ---- model-kernel sanity rows (aux_: not kernel_cell shaped) ----
    from repro.kernels import cin_layer, flash_attention
    from repro.kernels import ref as R
    key = jax.random.PRNGKey(1)
    B, T, H, d = 1, 128 if common.SMOKE else 256, 4, 64
    q = jax.random.normal(key, (B, T, H, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, d))
    want = R.flash_attention_ref(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3)
                                 ).transpose(0, 2, 1, 3)
    ok = bool(jnp.allclose(flash_attention(q, k, v), want, atol=1e-3))
    t = timeit(lambda: flash_attention(q, k, v), iters=2)
    emit("aux_flash_attention", t, f"allclose={ok};T={T}")

    xk = jax.random.normal(key, (64, 50, 10), jnp.float32)
    x0 = jax.random.normal(jax.random.fold_in(key, 3), (64, 20, 10))
    w = jax.random.normal(jax.random.fold_in(key, 4), (50, 50, 20)) * 0.01
    ok = bool(jnp.allclose(cin_layer(xk, x0, w), R.cin_layer_ref(xk, x0, w),
                           rtol=1e-3, atol=1e-3))
    t = timeit(lambda: cin_layer(xk, x0, w), iters=2)
    emit("aux_cin", t, f"allclose={ok};B=64;H=50")


if __name__ == "__main__":
    run()
