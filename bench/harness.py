"""One run of one cell: set-up, the measured window, the check, the line.

``bench/run.py`` is the command. Tests drive :func:`run_cell` with
``require_tpu=False`` to exercise everything but the look for a chip.

Set-up runs from process start to the first timed call: imports, the
configuration's graph drawn by its generator from its ``graph_seed``
and relabelled by a permutation drawn from the run's seed, the
program's ``Graph`` made by ``repro.graphs.build_graph`` and placed on
the device, and one warm-up solve (compiled, or loaded from the
persistent compilation cache after a checkout's first run). The window
is driven by the traffic mix's driver (``bench/traffic/<driver>.py``),
which starts ``api.solve`` calls; each is ended by
``block_until_ready`` on its state, and the window counts every solve
started, up to the end of the last.
After the window the device's peak memory is read, a sample of the
solves' answers drawn from the seed is fetched, the program's state is
freed, and the cell's plain reference judges the sample.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import tracing
from bench.edges import symmetric_sorted
from bench.peaks import peaks
from bench.spec import Cell

__all__ = ["HostGraph", "Solve", "Run", "NoAccelerator",
           "instance", "run_cell", "print_line"]

TRACE_DIR = Path("bench") / ".trace"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class HostGraph:
    """The benchmark's own copy of the graph: int32 directed edges,
    every edge in both directions, sorted by ``(dst, src)``."""
    n: int
    src: np.ndarray
    dst: np.ndarray

    @property
    def m(self) -> int:
        return len(self.src)

    @functools.cached_property
    def degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)

    @functools.cached_property
    def adjacency(self):
        """CSR matrix whose row ``v`` holds ``v``'s in-neighbours."""
        import scipy.sparse as sp
        ptr = np.zeros(self.n + 1, np.int64)
        ptr[1:] = np.cumsum(np.bincount(self.dst, minlength=self.n))
        return sp.csr_matrix((np.ones(self.m, np.float32), self.src, ptr),
                             shape=(self.n, self.n))

    @functools.cached_property
    def _keys(self) -> np.ndarray:
        return self.dst.astype(np.int64) * self.n + self.src

    def has_edge(self, u, v) -> np.ndarray:
        """Whether each edge ``u[i] -> v[i]`` exists."""
        key = np.asarray(v, np.int64) * self.n + np.asarray(u, np.int64)
        pos = np.minimum(np.searchsorted(self._keys, key), self.m - 1)
        return self._keys[pos] == key

    @functools.cached_property
    def component_edges(self) -> np.ndarray:
        """Per vertex, the undirected edges of its connected component."""
        from scipy.sparse.csgraph import connected_components
        _, label = connected_components(self.adjacency, directed=True,
                                        connection="weak")
        return (np.bincount(label, weights=self.degree) / 2)[label]


@dataclasses.dataclass(frozen=True)
class Solve:
    kwargs: dict
    t_call: float          # host clock at the call
    t_end: float           # host clock once its state was ready
    steps: int
    push_steps: int


@dataclasses.dataclass
class Run:
    """What ``bench/metrics/<name>.py``'s ``read(run)`` reads."""
    cell: Cell
    host: HostGraph
    solves: list
    window_s: float        # host clock, first call to last block
    setup_s: float
    peak_bytes: int
    device_kind: str
    trace: tracing.Summary | None

    @property
    def algorithm(self) -> str:
        return self.cell.traffic["algorithm"]

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)


class Reservoir:
    """A uniform sample of at most ``k`` of the items offered."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def instance(cell: Cell, seed: int):
    """``(HostGraph, driver, rng for the check's sample)`` of a run.

    The graph and its search keys are one fixed instance, drawn from the
    configuration's ``graph_seed``; the run's seed relabels the vertices
    by a permutation and orders the keys, so every seed does the same
    work in another order. The driver is the traffic mix's
    ``bench/traffic/<driver>.py``."""
    cfg, t = cell.config, cell.traffic
    label_rng, order_rng, sample_rng = (
        np.random.default_rng(s)
        for s in np.random.SeedSequence(seed).spawn(3))
    base = np.random.default_rng(cfg["graph_seed"])
    lo, hi, n = cell.module("generators", cfg["generator"]).generate(
        cfg, base)
    label = label_rng.permutation(n)
    driver = cell.module("traffic", t["driver"]).make(
        t, np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n),
        label, base, order_rng)
    host = HostGraph(n, *symmetric_sorted(label[lo], label[hi], n))
    return host, driver, sample_rng


def _log(*parts) -> None:
    print(*parts, flush=True)


def _use_compile_cache(jax, root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of an entry's key), every program kept."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _worst(values) -> float:
    return float(np.max(np.asarray(values, np.float64)))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, require_tpu: bool = True, control: bool = False,
             log=_log) -> dict:
    """Run ``cell`` once; returns the result line as a dict. With
    ``control``, the line also holds the control's numbers under
    ``"control"``."""
    mark = [t0]

    def part(name: str) -> None:
        now = time.perf_counter()
        log(f"setup {name}_s {now - mark[0]:.3f}")
        mark[0] = now

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found "
                            f"{devices[0].platform!r}")
    if require_tpu and len(devices) < cell.chips:
        raise NoAccelerator(f"{cell.name} needs {cell.chips} chips, JAX "
                            f"sees {len(devices)}")
    used = devices[:cell.chips]
    _use_compile_cache(jax, cell.root)
    from repro import api
    from repro.graphs import build_graph
    part("import")

    t = cell.traffic
    host, driver, sample_rng = instance(cell, seed)
    n = host.n
    part("generate")
    g = build_graph(host.src, host.dst, n=n)
    jax.block_until_ready(g)
    part("build")
    solve = functools.partial(api.solve, g, t["algorithm"],
                              policy=t.get("policy"),
                              backend=t.get("backend"))
    warm = solve(**driver.warmup())
    jax.block_until_ready(warm.state)
    int(warm.steps), int(warm.push_steps)
    del warm
    part("warm")
    setup_s = time.perf_counter() - t0
    log(f"setup_s {setup_s:.3f} graph n={n} m={host.m} d_ell={g.d_ell}")

    keep = Reservoir(t["check"]["sample"], sample_rng)
    solves = []
    if trace:
        trace_dir = cell.root / TRACE_DIR / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    ann = jax.profiler.TraceAnnotation

    def start(kw):
        t_call = time.perf_counter()
        with ann("bench.solve"):
            return kw, t_call, solve(**kw)

    def finish(started) -> Solve:
        kw, t_call, r = started
        with ann("bench.block"):
            jax.block_until_ready(r.state)
        t_end = time.perf_counter()
        with ann("bench.fetch"):
            done = Solve(kw, t_call, t_end, int(r.steps), int(r.push_steps))
        solves.append(done)
        keep.offer((kw, r.state))
        return done

    with ann("bench.window"):
        driver.run(start, finish, seconds)
    if trace:
        jax.profiler.stop_trace()
    window_s = (max(s.t_end for s in solves)
                - min(s.t_call for s in solves))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    log(f"window solves={len(solves)} window_s={window_s:.4f} "
        f"steps={sum(s.steps for s in solves)} "
        f"push_steps={sum(s.push_steps for s in solves)} "
        f"peak_bytes_in_use={peak}")
    sample = [(kw, jax.device_get(state)) for kw, state in keep.items]
    del g, keep, solve

    summary = None
    if trace:
        ops, spans = tracing.read_xspace(
            tracing.find_xspace(trace_dir), {d.id for d in used})
        summary = tracing.summarize(ops, spans)
        log(f"trace busy_s={summary.busy_s:.6f} "
            f"window_s={summary.window_s:.6f}")

    ref = cell.module("reference", t["algorithm"])
    limits = t["check"]["limits"]
    t_check = time.perf_counter()
    per_solve = ref.compare(host, sample, t.get("params", {}))
    log(f"check of {len(sample)} solves took "
        f"{time.perf_counter() - t_check:.3f} s")
    checks = {name: {"value": _worst([p[name] for p in per_solve]),
                     "limit": limit} for name, limit in limits.items()}
    failed = sum(any(not p[name] <= limit for name, limit in limits.items())
                 for p in per_solve)

    run = Run(cell=cell, host=host, solves=solves, window_s=window_s,
              setup_s=setup_s, peak_bytes=peak,
              device_kind=used[0].device_kind, trace=summary)
    metrics = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        value = cell.module("metrics", entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    line = {"correct": failed == 0, "attempted": len(solves),
            "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["breakdown"] = {"device_ops": summary.top_ops,
                             "idle_gaps": summary.idle_gaps}
    if control:
        per_control = ref.control(host, sample, t.get("params", {}))
        line["control"] = {name: _worst([p[name] for p in per_control])
                           for name in limits}
    line["checks"] = checks
    return line


def print_line(line: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard
    output."""
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
