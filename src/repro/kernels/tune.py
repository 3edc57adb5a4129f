"""Autotuner for the push/pull Pallas kernels: real search, disk cache.

The right configuration depends on the execution mode and the shape:
compiled TPU kernels want VMEM-sized tiles and the MXU reduce; the
interpreter (CPU CI) wants the largest block that amortizes per-step
overhead and the bandwidth-bound scan reduce. A static choice is wrong
for one of the two, so the ``PallasBackend`` probes a candidate grid
once per (graph shape, payload shape, platform) and caches the winner
twice over:

  * **on disk** under ``~/.cache/repro/tune.json`` (override with
    ``$REPRO_CACHE_DIR``), keyed by platform × kernel × shape × dtype ×
    combine × msg, so repeated runs (benchmarks, CI, services) skip the
    probe entirely;
  * **in memory** (module-level dict), which also serves as the
    fallback when the cache directory is unwritable.

Search space — pull: ``block_n`` rungs; push: the (block_e, block_n
= bin width, strategy) grid over both phase-2 reduce strategies
(``"scan"`` | ``"mxu"``). Probes time each candidate on synthetic data
of the shape being solved (one warmup + one timed call) with **early
pruning**: candidates are grouped by (strategy, bin width), and a
group whose first rung lands ≥ ``_PRUNE``× behind the incumbent is
abandoned — the rest of its rungs only move block_e, which never
recovers that much.

Probing is eager and runs in a single worker thread so it escapes any
ambient jit trace (the backend discovers new shapes mid-trace). Probes
run at the real shape up to ``_PROBE_N`` vertices and at a scaled-down
copy of it beyond (the winner is still cached under the real shape):
the kernels' per-block cost does not depend on the grid length, and a
full-size probe of every candidate would cost more than it saves.

Compiled (TPU) candidates are restricted to tile-legal blocks whose
working set fits ``VMEM_CAP``. A compiled probe that fails for a reason
other than an injected fault or a deadline raises: degrading to the
default would hide a kernel the chip refuses.
"""

from __future__ import annotations

import json
import os
import threading
import time

import jax
import jax.numpy as jnp

from ..resilience import FaultInjected, ProbeTimeout, fault_point, note
from .coo_push import build_push_plan, coo_push_pallas, push_vmem_bytes
from .ell_spmv import (VMEM_CAP, default_interpret, ell_spmv_pallas,
                       ell_vmem_bytes)

__all__ = ["pull_candidates", "pull_frontier_candidates",
           "push_candidates", "tune_pull", "tune_pull_frontier",
           "tune_push", "cache_dir", "clear_memory_cache"]

_PULL_LADDER = (128, 256, 512, 1024, 2048, 4096)
_EDGE_LADDER = (1024, 4096, 16384)
_BIN_LADDER = (128, 256, 1024)
_PRUNE = 2.0
_PROBE_N = 1 << 16


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def _row_rungs(rows: int, width: int, d_ell: int | None,
               compiled: bool) -> list[int]:
    """Pull ladder rungs below the padded row count; compiled, only the
    rungs whose working set fits ``VMEM_CAP`` (the ladder itself is
    lane-aligned)."""
    r_pad = _round_up(max(rows, 8), 8)
    cands = [c for c in _PULL_LADDER if c < r_pad]
    if compiled and d_ell is not None:
        cands = [c for c in cands
                 if ell_vmem_bytes(c, d_ell, width) <= VMEM_CAP]
    return cands


def _whole_rung_fits(rows: int, width: int, d_ell: int | None,
                     compiled: bool) -> bool:
    return not (compiled and d_ell is not None and ell_vmem_bytes(
        _round_up(max(rows, 8), 8), d_ell, width) > VMEM_CAP)


def pull_candidates(n: int, width: int | None = None, *,
                    d_ell: int | None = None,
                    compiled: bool = False) -> tuple[int, ...]:
    """``block_n`` rungs for the ELL pull kernel: the ladder below n
    plus the whole (padded) vertex range. Single-column payloads
    (``width == 1``) drop the full-row rung whenever sub-n rungs
    exist — the b1 gather is too thin to amortize a grid of one, and
    the full-row rung measurably loses to jnp there (the
    kernel_pull_*_b1 regression)."""
    cands = _row_rungs(n, width or 1, d_ell, compiled)
    if not (width == 1 and cands) and (
            not cands or _whole_rung_fits(n, width or 1, d_ell, compiled)):
        cands.append(_round_up(max(n, 8), 8))
    return tuple(cands)


def pull_frontier_candidates(n: int, rows: int, *, width: int = 1,
                             d_ell: int | None = None,
                             compiled: bool = False) -> tuple[int, ...]:
    """``block_r`` rungs for the frontier pull kernel, keyed by frontier
    density: the grid tiles the compacted ``rows`` touched-row list (not
    the vertex range), so the useful rungs shrink with ``rows / n``. A
    sparse frontier (few hundred rows) wants one or two tiles; only
    near-full frontiers see the deep ladder. Rungs are the pull ladder
    clipped below the padded row count, plus the whole-range rung."""
    cands = _row_rungs(rows, width, d_ell, compiled)
    if not cands or _whole_rung_fits(rows, width, d_ell, compiled):
        cands.append(_round_up(max(rows, 8), 8))
    return tuple(cands)


def push_candidates(n: int, m: int, *, width: int = 1,
                    compiled: bool = False
                    ) -> tuple[tuple[int, int, str], ...]:
    """(block_e, block_n, strategy) grid for the two-phase push kernel.

    ``block_n`` is the destination-bin width (phase 1), ``block_e`` the
    streamed edge-chunk size (phase 2), ``strategy`` the reduce. Scan
    rungs cover the full bin ladder; MXU rungs are limited to bins the
    window/one-hot expansion can afford (its work is bin_n × cap).
    Ordered scan-first so pruning meets the incumbent early. Compiled,
    bins and edge blocks are lane multiples whose working set fits
    ``VMEM_CAP`` (the whole-range bin never does at real sizes).
    """
    q = 128 if compiled else 8
    n_pad = _round_up(max(n, 8), q)
    m_pad = _round_up(max(m, 8), q)
    bins = sorted({min(b, n_pad) for b in _BIN_LADDER} | {n_pad})
    edges = sorted({min(e, m_pad) for e in _EDGE_LADDER} | {m_pad})
    cands = [(e, b, "scan") for b in bins for e in edges]
    cands += [(e, b, "mxu") for b in bins if b <= 256 for e in edges]
    if compiled:
        cands = [c for c in cands
                 if push_vmem_bytes(c[0], c[1], width) <= VMEM_CAP]
    return tuple(cands)


# -- persistent cache ---------------------------------------------------
_MEM_CACHE: dict[str, tuple] = {}
_DISK: dict | None = None
_LOCK = threading.Lock()

# probe/cache outcome counters, exported to repro.obs as `tuner.*` —
# the cheap answer to "did this run pay autotuning, or ride the cache?"
# plus the resilience tail: probe retries/timeouts/failures and how
# often the tuner degraded to the default candidate
_STATS = {"mem_hits": 0, "disk_hits": 0, "misses": 0, "probes": 0,
          "writes": 0, "write_errors": 0, "probe_retries": 0,
          "probe_timeouts": 0, "probe_failures": 0, "probe_degraded": 0}


def tune_stats() -> dict[str, int]:
    """Snapshot of the process-wide tuner counters (copies, safe to
    mutate)."""
    with _LOCK:
        return dict(_STATS)


def clear_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0


def cache_dir() -> str:
    return os.environ.get(
        "REPRO_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "repro"))


def _cache_path() -> str:
    return os.path.join(cache_dir(), "tune.json")


def clear_memory_cache() -> None:
    """Drop the in-memory tier (tests re-point $REPRO_CACHE_DIR)."""
    global _DISK
    with _LOCK:
        _MEM_CACHE.clear()
        _DISK = None


def _platform(interpret: bool) -> str:
    return "interpret" if interpret else jax.default_backend()


def _cache_key(kernel: str, interpret: bool, shape: tuple, width: int,
               dtype, combine: str, msg: str) -> str:
    dims = "x".join(str(s) for s in shape)
    return (f"{_platform(interpret)}|{kernel}|{dims}|w{width}|"
            f"{jnp.dtype(dtype).name}|{combine}|{msg}")


def _load_disk() -> dict:
    """Load the on-disk tier, surviving anything a crashed or racing
    writer can leave behind: a missing/unreadable file, truncated or
    garbage JSON, or a file that parses to a non-dict value. Every
    failure mode degrades to an empty dict — the in-memory tier keeps
    serving, and the next ``_cache_put`` atomically rewrites a valid
    file over the corpse."""
    try:
        fault_point("tune.cache.load")
        with open(_cache_path()) as f:
            data = json.load(f)
    except (OSError, ValueError, FaultInjected):
        return {}
    return data if isinstance(data, dict) else {}


def _cache_get(key: str):
    global _DISK
    with _LOCK:
        if key in _MEM_CACHE:
            _STATS["mem_hits"] += 1
            return _MEM_CACHE[key]
        if _DISK is None:
            _DISK = _load_disk()
        hit = _DISK.get(key)
        if hit is not None:
            _STATS["disk_hits"] += 1
            hit = tuple(hit) if isinstance(hit, list) else hit
            _MEM_CACHE[key] = hit
        else:
            _STATS["misses"] += 1
        return hit


def _cache_put(key: str, value) -> None:
    global _DISK
    with _LOCK:
        _STATS["writes"] += 1
        _MEM_CACHE[key] = value
        if _DISK is None:
            _DISK = {}
        _DISK[key] = list(value) if isinstance(value, tuple) else value
        path = _cache_path()
        try:
            fault_point("tune.cache.write")
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(_DISK, f, indent=0, sort_keys=True)
            os.replace(tmp, path)
        except (OSError, FaultInjected):
            # unwritable home (or an injected disk fault): the
            # in-memory tier still serves
            _STATS["write_errors"] += 1


def _time(fn, *args) -> float:
    jax.block_until_ready(fn(*args))              # warmup = compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


# Probes run while the backend is being traced into an engine loop, and
# JAX's trace context is ambient (thread-local): any op issued here —
# even on concrete arrays — would be spliced into the engine's jaxpr
# instead of executing. A fresh thread has no ambient trace, so
# candidates execute (and are timed) for real. One daemon thread per
# probe (probes are once-per-shape rare) so a *hung* probe can be
# abandoned at the deadline without wedging later probes or process
# exit — a shared worker would stay stuck behind the corpse.


def _escaped(fn, deadline=None, kernel: str = "?"):
    box: dict = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=run, name="kernel-tune", daemon=True)
    t.start()
    t.join(deadline)
    if t.is_alive():
        raise ProbeTimeout(kernel, deadline)
    if "error" in box:
        raise box["error"]
    return box["value"]


def _probe_deadline_s() -> float:
    return float(os.environ.get("REPRO_TUNE_DEADLINE_S", "120"))


def _probe_retries() -> int:
    return int(os.environ.get("REPRO_TUNE_RETRIES", "2"))


def _probe_guarded(kernel: str, probe, default, strict: bool = False):
    """Run ``probe`` off-thread under the wall deadline with bounded
    retry-with-backoff; returns ``(winner, probed)``. Exhausted
    attempts degrade to ``default`` (``probed=False`` — the caller must
    NOT persist it, so a healthy later run re-probes). ``strict``
    (compiled kernels) re-raises any failure that is neither an
    injected fault nor a deadline."""
    deadline, retries = _probe_deadline_s(), _probe_retries()

    def attempt_fn():
        fault_point("tune.probe")
        return probe()

    for attempt in range(retries + 1):
        try:
            return _escaped(attempt_fn, deadline=deadline,
                            kernel=kernel), True
        except Exception as e:   # noqa: BLE001 — chaos/flake seam
            if strict and not isinstance(e, (FaultInjected, ProbeTimeout)):
                raise
            timed_out = isinstance(e, ProbeTimeout)
            with _LOCK:
                _STATS["probe_timeouts" if timed_out
                       else "probe_failures"] += 1
            if attempt < retries:
                with _LOCK:
                    _STATS["probe_retries"] += 1
                note("retry.tune.probe", kernel=kernel,
                     attempt=attempt + 1, error=type(e).__name__)
                time.sleep(min(0.02 * (2 ** attempt), 0.5))
    with _LOCK:
        _STATS["probe_degraded"] += 1
    note("degraded.tune.probe", kernel=kernel, default=str(default))
    return default, False


def tune_pull(n: int, d_ell: int, width: int, dtype, combine: str,
              msg: str, interpret: bool | None = None) -> int:
    """Best ``block_n`` for an ELL pull of this shape (synthetic probe,
    shape-and-platform-keyed, persisted)."""
    if interpret is None:
        interpret = default_interpret()
    cands = pull_candidates(n, width, d_ell=d_ell,
                            compiled=not interpret)
    if len(cands) == 1:                   # nothing to probe
        return cands[0]
    key = _cache_key("pull", interpret, (n, d_ell), width, dtype,
                     combine, msg)
    hit = _cache_get(key)
    if hit is not None:
        try:
            return int(hit)
        except (TypeError, ValueError):
            pass   # poisoned cache entry: fall through and re-probe

    def probe():
        n_p = min(n, _PROBE_N)
        key_ = jax.random.PRNGKey(0)
        idx = jax.random.randint(key_, (n_p, d_ell), 0, n_p + 1,
                                 jnp.int32)
        w = jnp.ones((n_p, d_ell), jnp.float32)
        shape = (n_p + 1,) if width == 1 else (n_p + 1, width)
        x = jnp.ones(shape, dtype)
        best, best_t = None, None
        for block_n in cands:
            t = _time(lambda b=block_n: ell_spmv_pallas(
                x, idx, w, combine=combine, msg=msg, block_n=b,
                interpret=interpret))
            if best_t is None or t < best_t:
                best, best_t = block_n, t
        return best

    with _LOCK:
        _STATS["probes"] += 1
    best, probed = _probe_guarded("pull", probe, cands[0],
                                  strict=not interpret)
    if probed:
        _cache_put(key, best)
    return best


def tune_pull_frontier(n: int, d_ell: int, rows: int, width: int, dtype,
                       combine: str, msg: str,
                       interpret: bool | None = None) -> int:
    """Best ``block_r`` for a frontier pull of this shape. Keyed by the
    compacted row capacity (= frontier density × n) on top of the usual
    shape key: a 64-row BFS tail and a half-full frontier over the same
    graph want different tiles, so they tune — and cache — separately."""
    if interpret is None:
        interpret = default_interpret()
    cands = pull_frontier_candidates(n, rows, width=width, d_ell=d_ell,
                                     compiled=not interpret)
    if len(cands) == 1:
        return cands[0]
    key = _cache_key("pullf", interpret, (n, d_ell, rows), width, dtype,
                     combine, msg)
    hit = _cache_get(key)
    if hit is not None:
        try:
            return int(hit)
        except (TypeError, ValueError):
            pass   # poisoned cache entry: fall through and re-probe

    def probe():
        from .ell_pull_frontier import ell_pull_frontier_pallas
        n_p = min(n, _PROBE_N)
        rows_p = rows if n_p == n else max(8, rows * n_p // n)
        key_ = jax.random.PRNGKey(2)
        idx = jax.random.randint(key_, (n_p, d_ell), 0, n_p + 1,
                                 jnp.int32)
        w = jnp.ones((n_p, d_ell), jnp.float32)
        shape = (n_p + 1,) if width == 1 else (n_p + 1, width)
        x = jnp.ones(shape, dtype)
        rids = jax.random.permutation(
            jax.random.fold_in(key_, 1), n_p)[:rows_p].astype(jnp.int32)
        rids = jnp.pad(rids, (0, max(0, rows_p - n_p)),
                       constant_values=n_p)
        best, best_t = None, None
        for block_r in cands:
            t = _time(lambda b=block_r: ell_pull_frontier_pallas(
                x, idx, w, rids, combine=combine, msg=msg, block_r=b,
                interpret=interpret))
            if best_t is None or t < best_t:
                best, best_t = block_r, t
        return best

    with _LOCK:
        _STATS["probes"] += 1
    best, probed = _probe_guarded("pullf", probe, cands[0],
                                  strict=not interpret)
    if probed:
        _cache_put(key, best)
    return best


def tune_push(n: int, m: int, width: int, dtype, combine: str,
              msg: str, interpret: bool | None = None
              ) -> tuple[int, int, str]:
    """Best ``(block_e, block_n, strategy)`` for a two-phase push of
    this shape: grid search with early pruning, shape-and-platform-
    keyed, persisted to the on-disk cache."""
    if interpret is None:
        interpret = default_interpret()
    cands = push_candidates(n, m, width=width, compiled=not interpret)
    if len(cands) == 1:
        return cands[0]
    key = _cache_key("push", interpret, (n, m), width, dtype, combine,
                     msg)
    hit = _cache_get(key)
    if hit is not None:
        try:
            be, bn, strat = hit
            return int(be), int(bn), str(strat)
        except (TypeError, ValueError):
            pass   # poisoned cache entry: fall through and re-probe

    def probe():
        n_p = min(n, _PROBE_N)
        m_p = max(1, m * n_p // n)
        key_ = jax.random.PRNGKey(1)
        dst = jnp.sort(jax.random.randint(key_, (m_p,), 0, n_p,
                                          jnp.int32))
        src = jax.random.randint(jax.random.fold_in(key_, 1), (m_p,), 0,
                                 n_p, jnp.int32)
        w = jnp.ones((m_p,), jnp.float32)
        shape = (n_p,) if width == 1 else (n_p, width)
        x = jnp.ones(shape, dtype)
        active = jnp.ones((n_p,), bool)
        plans: dict[tuple[int, int], object] = {}
        best, best_t = None, None
        pruned: set[tuple[str, int]] = set()
        group_seen: set[tuple[str, int]] = set()
        for block_e, block_n, strategy in cands:
            group = (strategy, block_n)
            if group in pruned:
                continue
            pkey = (block_n, block_e)
            if pkey not in plans:
                plans[pkey] = build_push_plan(src, dst, w, n_p, block_n,
                                              align=block_e)
            t = _time(lambda be=block_e, bn=block_n, st=strategy,
                      p=plans[pkey]: coo_push_pallas(
                x, active, src, dst, w, n_p, combine=combine, msg=msg,
                block_e=be, block_n=bn, interpret=interpret, plan=p,
                strategy=st))
            first = group not in group_seen
            group_seen.add(group)
            if best_t is None or t < best_t:
                best, best_t = (block_e, block_n, strategy), t
            elif first and t > _PRUNE * best_t:
                pruned.add(group)    # the rest of the group only moves
                continue             # block_e; it won't close a 2x gap
        return best

    with _LOCK:
        _STATS["probes"] += 1
    best, probed = _probe_guarded("push", probe, cands[0],
                                  strict=not interpret)
    if probed:
        _cache_put(key, best)
    return best
