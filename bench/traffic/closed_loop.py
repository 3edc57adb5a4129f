"""Closed loop: one solve at a time, each started once the last has
ended, until the window's seconds have passed and the last pass over
the mix's search keys is whole.

A traffic mix's JSON file names its driver (``"driver":
"closed_loop"``), the module ``bench/traffic/<driver>.py`` that makes
the run's requests and drives the window. Each driver has
``make(traffic, degree, label, graph_rng, run_rng)``, whose object has
``warmup()``, the request of the warm-up solve, and ``run(start,
finish, seconds)``, the window: ``start(kwargs)`` calls the program and
returns at once, ``finish(started)`` waits for that solve, records it
and returns its :class:`bench.harness.Solve`. Another kind of traffic,
such as open-loop arrivals, is a new driver file.
"""

from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["ClosedLoop", "draw_keys", "make"]


def draw_keys(traffic: dict, degree: np.ndarray,
              rng: np.random.Generator) -> np.ndarray:
    """The mix's search keys, drawn once per graph among the vertices
    of degree >= ``min_degree``, as Graph500 draws its 64; none where
    the mix names no ``source_key``."""
    if not traffic.get("source_key"):
        return np.zeros(0, np.int64)
    pool = np.flatnonzero(degree >= traffic["min_degree"])
    return rng.choice(pool, traffic["keys"], replace=False)


def make(traffic: dict, degree: np.ndarray, label: np.ndarray,
         graph_rng: np.random.Generator, run_rng: np.random.Generator):
    """The run's requests: keys drawn on the fixed graph (``degree`` is
    its, before the run relabels vertex ``v`` as ``label[v]``) from
    ``graph_rng``, ordered in passes by ``run_rng``."""
    return ClosedLoop(traffic, label[draw_keys(traffic, degree, graph_rng)],
                      run_rng)


class ClosedLoop:
    """The mix's fixed ``params`` and, where it names a ``source_key``,
    one of ``keys`` per request. The keys come in passes, each pass all
    of them in an order drawn from the run's seed."""

    def __init__(self, traffic: dict, keys: np.ndarray,
                 rng: np.random.Generator):
        self.params = dict(traffic.get("params", {}))
        self.key = traffic.get("source_key")
        self.keys, self.rng, self.order = keys, rng, []

    def warmup(self) -> dict:
        """A request for the warm-up solve that leaves the passes whole."""
        kw = dict(self.params)
        if self.key:
            kw[self.key] = int(self.keys[0])
        return kw

    @property
    def pass_done(self) -> bool:
        """Whether the last request ended a pass over the keys (always,
        where the mix has none): a window of whole passes does the same
        work on every seed."""
        return not self.order

    def next(self) -> dict:
        kw = dict(self.params)
        if self.key:
            if not self.order:
                self.order = self.rng.permutation(self.keys).tolist()
            kw[self.key] = int(self.order.pop())
        return kw

    def run(self, start, finish, seconds: float) -> None:
        first = None
        while True:
            with TraceAnnotation("bench.request"):
                kw = self.next()
            done = finish(start(kw))
            first = done.t_call if first is None else first
            if done.t_end - first >= seconds and self.pass_done:
                return
