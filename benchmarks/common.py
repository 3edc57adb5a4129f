"""Shared benchmark scaffolding.

Wall-clock numbers come from the CPU container, so they validate the
paper's *relative* push/pull claims; the analytic PRAM counters validate
the *structural* claims (Table 1). Real-world graphs are offline, so the
paper's graphs are structurally matched synthetic stand-ins
(graphs.generators.standin; DESIGN.md §10).
"""

from __future__ import annotations

import os
import time
from functools import lru_cache

import jax
import numpy as np

SCALE = 1.0 / 256    # stand-in scale vs paper sizes (CPU container)

# set by `benchmarks.run --smoke`: suites that honor it shrink their
# graphs to CI-sized instances (seconds, not minutes, per suite)
SMOKE = False

# set by `benchmarks.run --trace-out PATH`: a repro.obs.Telemetry handle
# suites emit into (auto-policy cells run one extra observed solve so
# the trace carries their decision audit; timed runs stay
# telemetry-free so the numbers are untouched)
TELEMETRY = None

ROWS: list[str] = []
# structured mirror of ROWS, consumed by `benchmarks.run --json PATH`
RESULTS: list[dict] = []


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fake_device_env() -> dict:
    """Environment for a child process that fakes host CPU devices
    (its script sets ``XLA_FLAGS`` itself), pinned to the CPU. Refuses
    on a TPU host: this process already holds the chip, and a child
    that reaches for it would fail or hang."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "this suite fakes devices in a child process and cannot run "
            "on a TPU host; run the sharded path in-process there "
            "(python chip_smoke.py --chips 4)")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


def emit(name: str, us_per_call: float, derived: str = ""):
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    RESULTS.append({"name": name, "us_per_call": round(us_per_call, 1),
                    "derived": derived})
    print(line, flush=True)


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-clock microseconds of fn(*args) (blocks on result)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e6


@lru_cache(maxsize=None)
def graph(name: str, weighted: bool = False, scale: float = SCALE):
    from repro.graphs import standin
    return standin(name, scale=scale, weighted=weighted)


def fmt_count(x: int) -> str:
    if x >= 1_000_000_000:
        return f"{x/1e9:.2f}B"
    if x >= 1_000_000:
        return f"{x/1e6:.2f}M"
    if x >= 1_000:
        return f"{x/1e3:.1f}k"
    return str(x)
