#!/usr/bin/env python3
"""Run repro's main path once on a TPU and check it against a host reference.

    python chip_smoke.py             # one chip: dense, Pallas, QueryService
    python chip_smoke.py --chips 4   # the sharded backend on a 2x2 mesh

The graph is the GAP Benchmark Suite's ``urand`` kind (uniform random,
average degree 16), made from a seed at 2**21 vertices instead of GAP's
2**27: one chip's 16 GB must hold the Graph's four layouts (about
2.7 GB) plus the working sets of the Pallas backend and of an 8-wide
query batch. Every result is compared with a plain numpy/scipy
reference computed from the graph's edge list: BFS levels exactly,
PageRank to 1e-4 relative, personalized PageRank to 1e-5 absolute.

Earlier lines report, per phase, the time of each solve's second call
and the first call's excess (compile), the Pallas backend's dispatch
counters, the tuner's counters and the device's peak memory. The last
line is one JSON object naming the device. The script exits non-zero,
without that line, when JAX finds no TPU or when any check fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCALE = 21
DEGREE = 16.0
SEED = 0
BFS_ROOTS = (0, 12_345, 1 << 20, (1 << 21) - 1)
SERVICE_BFS = BFS_ROOTS + (7, 99_999, 1_500_000, 2_000_001)
SERVICE_PPR = (3, 77, 4_242, 65_536, 300_000, 1_048_575, 1_700_000,
               2_097_000)
PR_ITERS = 20
DAMP = 0.85
PPR_TOL = 1e-6
UNREACHED = np.iinfo(np.int32).max


def log(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"chip_smoke: FAILED: {what}", file=sys.stderr, flush=True)
        sys.exit(1)


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


class HostReference:
    """BFS levels, PageRank and personalized PageRank over one edge list,
    in numpy/scipy — the semantics repro implements, nothing of its
    code. Row ``v`` of ``a`` holds ``v``'s in-neighbors."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        import scipy.sparse as sp
        self.n = n
        self.a = sp.csr_matrix(
            (np.ones(len(src), np.float32), (dst, src)), shape=(n, n))
        self.inv_deg = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)

    def bfs_levels(self, roots) -> np.ndarray:
        """[len(roots), n] hop counts, ``UNREACHED`` where none."""
        k = len(roots)
        dist = np.full((self.n, k), UNREACHED, np.int64)
        front = np.zeros((self.n, k), np.float32)
        dist[list(roots), np.arange(k)] = 0
        front[list(roots), np.arange(k)] = 1.0
        level = 0
        while front.any():
            level += 1
            new = (self.a @ front > 0) & (dist == UNREACHED)
            dist[new] = level
            front = new.astype(np.float32)
        return dist.T

    def pagerank(self, iters: int, damp: float) -> np.ndarray:
        r = np.full(self.n, 1.0 / self.n)
        for _ in range(iters):
            r = (1 - damp) / self.n + damp * (self.a @ (r * self.inv_deg))
        return r

    def ppr(self, sources, damp: float, tol: float,
            iters: int = 100) -> np.ndarray:
        """[len(sources), n]: each column iterates until its max change
        drops below ``tol``, then freezes."""
        k = len(sources)
        base = np.zeros((self.n, k), np.float32)
        base[list(sources), np.arange(k)] = 1 - damp
        inv = self.inv_deg.astype(np.float32)[:, None]
        r, active = base.copy(), np.ones(k, bool)
        for _ in range(iters):
            if not active.any():
                break
            new = base + np.float32(damp) * (self.a @ (r * inv))
            resid = np.abs(new - r).max(axis=0)
            r = np.where(active[None, :], new, r)
            active &= resid >= tol
        return r.T


def peak_bytes() -> list[int]:
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()]


def check_pagerank(got, want: np.ndarray, what: str) -> float:
    got = np.asarray(got, np.float64)
    err = float(np.max(np.abs(got - want) / want))
    check(bool(np.isfinite(got).all()) and got.shape == want.shape,
          f"{what}: ranks not finite or of shape {got.shape}")
    check(err <= 1e-4, f"{what}: max relative error {err:.3g} > 1e-4")
    return err


def phase_solves(g, ref: HostReference, levels, pr_ref, backend,
                 label: str, pr_policies=("pull",)) -> None:
    """BFS/auto from every fixed root and PageRank, each solved twice;
    the second call is timed, the first call's excess is compile."""
    from repro import api
    for root, want in zip(BFS_ROOTS, levels):
        run = lambda: api.solve(g, "bfs", root=root,   # noqa: E731
                                policy="auto", backend=backend)
        _, first = timed(run)
        r, second = timed(run)
        dist = np.asarray(r.state["dist"])
        check(np.array_equal(dist, want),
              f"{label} bfs root={root}: levels differ from the host "
              f"reference at {int(np.sum(dist != want))} vertices")
        log(f"{label} bfs root={root} steps={int(r.steps)} "
            f"push_steps={int(r.push_steps)} time_s={second:.4f} "
            f"compile_s={max(first - second, 0.0):.2f}")
    for pol in pr_policies:
        run = lambda: api.solve(g, "pagerank", iters=PR_ITERS,  # noqa
                                policy=pol, backend=backend).state
        _, first = timed(run)
        ranks, second = timed(run)
        err = check_pagerank(ranks, pr_ref, f"{label} pagerank/{pol}")
        log(f"{label} pagerank/{pol} iters={PR_ITERS} time_s={second:.4f}"
            f" compile_s={max(first - second, 0.0):.2f} "
            f"max_rel_err={err:.3g}")
    log(f"{label} peak_bytes_in_use {peak_bytes()}")


def phase_pallas(g, ref, levels, pr_ref) -> None:
    from repro.core import PallasBackend
    from repro.kernels.tune import tune_stats
    backend = PallasBackend()
    phase_solves(g, ref, levels, pr_ref, backend, "pallas")
    counters = backend.telemetry_counters()
    tuner = tune_stats()
    log("pallas counters", json.dumps(counters, sort_keys=True))
    log("pallas tuner", json.dumps(tuner, sort_keys=True))
    for name in ("kernel_pull", "kernel_pull_frontier", "kernel_push"):
        check(counters[name] > 0, f"pallas: {name} is 0, the kernel "
                                  "never ran on this path")
    for name, value in counters.items():
        if name.startswith(("fallback_", "fault_fallback_", "breaker_")):
            check(value == 0, f"pallas: {name} = {value}, a fallback "
                              "served this path")
    check(tuner["probe_degraded"] == 0,
          f"pallas: {tuner['probe_degraded']} tuner probes degraded")


def phase_service(g, ref: HostReference) -> None:
    """16 mixed BFS/PPR queries through QueryService, twice: the second
    round (a fresh service, so no cached results) is timed."""
    from repro.service import QueryService
    bfs_want = ref.bfs_levels(SERVICE_BFS)
    ppr_want = ref.ppr(SERVICE_PPR, DAMP, PPR_TOL)
    for rnd in (1, 2):
        svc = QueryService(g, slots=8)
        rids = []
        for b, p in zip(SERVICE_BFS, SERVICE_PPR):
            rids.append(("bfs", b, svc.submit("bfs", b)))
            rids.append(("ppr", p, svc.submit("ppr", p)))
        _, dt = timed(lambda: (svc.run_until_complete(),
                               [svc.poll(rid) for *_, rid in rids])[1])
        stats = svc.stats()
        check(not stats["failures"] and stats["force_retired"] == 0,
              f"service: failures {stats['failures']}, force-retired "
              f"{stats['force_retired']}")
        worst = 0.0
        for kind, src, rid in rids:
            got = svc.poll(rid)
            if kind == "bfs":
                want = bfs_want[SERVICE_BFS.index(src)]
                dist = np.asarray(got["dist"])
                check(np.array_equal(dist, want),
                      f"service bfs source={src}: levels differ at "
                      f"{int(np.sum(dist != want))} vertices")
            else:
                err = float(np.max(np.abs(
                    np.asarray(got["ranks"], np.float64)
                    - ppr_want[SERVICE_PPR.index(src)])))
                check(err <= 1e-5, f"service ppr source={src}: max abs "
                                   f"error {err:.3g} > 1e-5")
                worst = max(worst, err)
        log(f"service round={rnd} queries={len(rids)} time_s={dt:.4f} "
            f"batches={stats['batches_started']} "
            f"chunks={stats['chunks_run']} ppr_max_abs_err={worst:.3g}")
    log(f"service peak_bytes_in_use {peak_bytes()}")


def phase_sharded(g, ref: HostReference, levels, pr_ref) -> None:
    from repro.shard import ShardedBackend
    t0 = time.perf_counter()
    backend = ShardedBackend.prepare(g, num_shards=4)
    log(f"shard prepare_s {time.perf_counter() - t0:.1f} "
        f"cut_edges={backend.cut_edges}")
    phase_solves(g, ref, levels, pr_ref, backend, "shard",
                 pr_policies=("push", "pull"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded backend on a 2x2 mesh only")
    args = ap.parse_args()

    import jax
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips}, but JAX sees {len(devices)} devices")
    log(f"device {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache}")

    from repro.graphs import erdos_renyi
    t0 = time.perf_counter()
    g = erdos_renyi(1 << SCALE, DEGREE, seed=SEED, weighted=True)
    log(f"graph_build_s {time.perf_counter() - t0:.1f}")
    log(f"graph n={g.n} m={g.m} d_ell={g.d_ell}")

    t0 = time.perf_counter()
    ref = HostReference(np.asarray(g.coo_src), np.asarray(g.coo_dst), g.n)
    levels = ref.bfs_levels(BFS_ROOTS)
    pr_ref = ref.pagerank(PR_ITERS, DAMP)
    log(f"reference_s {time.perf_counter() - t0:.1f}")

    if args.chips == 4:
        phase_sharded(g, ref, levels, pr_ref)
    else:
        phase_solves(g, ref, levels, pr_ref, "dense", "dense")
        phase_pallas(g, ref, levels, pr_ref)
        phase_service(g, ref)
    log(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
