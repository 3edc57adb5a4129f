"""Span/timer API and the bounded event ring (tentpole part 1).

Design constraints, in order:

1. **Zero-cost when absent.** Telemetry is an *opt-in handle*, not a
   global: ``api.solve(..., telemetry=None)`` never imports this module
   on the hot path and runs the one-``jax.jit``-call fast path
   untouched, so disabled telemetry is bit-identical by construction
   (tested in ``tests/test_obs.py``).
2. **jit-aware.** Host wall clocks cannot live inside a traced
   ``lax.while_loop`` — a jitted body runs asynchronously and a Python
   ``time.perf_counter()`` inside it would time tracing, not execution.
   Step-level timing therefore uses the engine's host-driven
   :meth:`~repro.core.engine.Engine.run_stepwise` loop, which jits the
   *body once* and calls ``jax.block_until_ready`` at every step
   boundary; :class:`Telemetry` only ever stamps timestamps on the
   host side of that boundary. In-loop *counters* (the exact §4 cost
   numbers) ride the jitted carry in
   :class:`~repro.core.cost_model.StepTrace` and are merged in
   afterwards by :func:`repro.obs.metrics.record_solve`.
3. **Bounded.** The event ring holds at most ``capacity`` events; once
   full, new events are dropped and counted in :attr:`Telemetry.dropped`
   (mirroring ``StepTrace.overflow`` on the device side) — telemetry
   must never turn a long run into an OOM.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator

from jax.profiler import TraceAnnotation

from .metrics import MetricRegistry

__all__ = ["Telemetry"]


class Telemetry:
    """A per-session telemetry handle: event ring + counter registry.

    Pass one instance to ``api.solve`` / ``api.solve_batch`` /
    ``QueryService`` (or set ``benchmarks.common.TELEMETRY``) and every
    layer appends structured events to it:

        >>> tel = Telemetry()
        >>> r = api.solve(g, "bfs", root=0, policy="auto",
        ...               telemetry=tel)              # doctest: +SKIP
        >>> [e["kind"] for e in tel.events][:3]       # doctest: +SKIP
        ['step', 'step', 'step']

    Events are plain dicts with at least ``ts_us`` (microseconds since
    this handle's ``t0``) and ``kind`` (one of ``meta | span | run |
    step | counter | event | audit`` — see ``benchmarks/obs_schema.json``
    for the full contract). ``counters`` is a
    :class:`~repro.obs.metrics.MetricRegistry` accumulating namespaced
    totals across runs; exporters append its snapshot as ``counter``
    events.
    """

    def __init__(self, *, capacity: int = 65536,
                 step_timing: bool = True) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        #: When True (default), ``api.solve`` routes eligible runs
        #: through the engine's host-driven stepwise loop so ``step``
        #: events carry measured ``us`` wall times (the decision
        #: audit's wall basis). Set False to keep the single-dispatch
        #: fast path and get predicted-basis audits only.
        self.step_timing = bool(step_timing)
        self.events: list[dict[str, Any]] = []
        self.dropped = 0
        self.counters = MetricRegistry()
        self._runs = 0
        self._t0 = time.perf_counter()

    # -- clock -----------------------------------------------------------
    def now_us(self) -> float:
        """Microseconds since this handle was created (host clock)."""
        return (time.perf_counter() - self._t0) * 1e6

    # -- event ring ------------------------------------------------------
    def emit(self, kind: str, name: str = "", *,
             ts_us: float | None = None, **fields: Any) -> None:
        """Append one event; drop (and count) once the ring is full."""
        if len(self.events) >= self.capacity:
            self.dropped += 1
            return
        ev: dict[str, Any] = {
            "ts_us": round(self.now_us() if ts_us is None else ts_us, 3),
            "kind": kind}
        if name:
            ev["name"] = name
        ev.update(fields)
        self.events.append(ev)

    def new_run(self) -> int:
        """Allocate the next run id (events from one solve share it)."""
        run = self._runs
        self._runs = run + 1
        return run

    @property
    def last_run(self) -> int | None:
        """Id of the most recently started run, or None before any."""
        return self._runs - 1 if self._runs else None

    def events_for(self, run: int, kind: str | None = None
                   ) -> list[dict[str, Any]]:
        """All events of one run (optionally one kind), in emit order."""
        return [e for e in self.events if e.get("run") == run
                and (kind is None or e["kind"] == kind)]

    # -- spans -----------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **fields: Any) -> Iterator[dict[str, Any]]:
        """Time a host-side region; emits one ``span`` event on exit.

        The yielded dict is live — mutate it to attach result fields::

            with tel.span("solve", algorithm="bfs") as sp:
                r = engine.run(...)
                sp["steps"] = int(r.steps)

        The event's ``ts_us`` is the span *start*, ``dur_us`` the
        elapsed host wall time — exactly the (ts, dur) pair the Chrome
        ``"X"`` (complete-event) exporter needs. Spans around jitted
        work should end after a ``jax.block_until_ready``, else they
        time dispatch, not execution.

        The span also opens a ``jax.profiler.TraceAnnotation`` of the
        same name, so under ``jax.profiler.trace`` it lands on the
        device trace's clock beside the program's ``repro.solve``
        spans.
        """
        t0 = self.now_us()
        sp = dict(fields)
        try:
            with TraceAnnotation(name):
                yield sp
        finally:
            sp.setdefault("dur_us", round(self.now_us() - t0, 3))
            self.emit("span", name, ts_us=t0, **sp)
