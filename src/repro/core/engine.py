"""PushPullEngine — the paper's contribution as a composable JAX runtime.

A *vertex program* is (msg_fn, combine, update_fn) plus optional hooks:

    msg_fn(src_value, edge_weight) -> message          (⊗ of §7.1)
    combine ∈ {sum, min, max}                          (⊕ / CRCW-CB)
    update_fn(old_state, combined_msgs, step) -> (new_state, frontier,
                                                  converged)
    values_fn(g, state, frontier) -> wire values       (default: state)
    touched_fn(g, state, frontier, visited) -> bool[n] pull destinations
    local_fn(g, state, frontier, step, do_push, cost)  (non-exchange step:
        -> (state, frontier, converged, cost)           sequential/greedy
                                                        sub-phases, edge
                                                        maps with private
                                                        accumulation)
    tail_fn(g, state, frontier, cost) -> (state, cost) (GreedySwitch
                                                        hand-off, §5-GrS)

Beyond the single flat fixed-point loop, the engine executes
*phase-structured* programs (:class:`PhaseProgram`): a sequence of
:class:`Phase`\\ s — each with its own ``VertexProgram``, step bound, and
carry-rewrite hooks — optionally wrapped in a nested *epoch* loop. This
covers every control shape in the paper:

  * flat fixed point            — BFS, PageRank, WCC, δ-PR (one phase);
  * nested epochs               — Δ-stepping's bucket loop around an
                                  inner relaxation loop (§3.4);
  * forward/backward pairs      — Brandes BC: the backward phase replays
                                  the forward trace (levels/σ) recorded
                                  in the carry (§3.5);
  * per-round contraction       — Borůvka's supervertex relabel as a
                                  second phase per round (§3.7);
  * one-shot edge maps          — triangle counting: a fixed number of
                                  steps, no fixed point (§3.2).

Each step runs as either a push k-relaxation (scatter from the frontier)
or a pull k-relaxation (gather into destinations) under a
DirectionPolicy, with only the chosen direction evaluated at runtime
(``lax.cond``) — and, orthogonally, through a pluggable
:class:`~repro.core.backend.ExchangeBackend` (dense / ELL / distributed).
Before each step of a *switching* policy the engine assembles
:class:`~repro.core.cost_model.StepStats` — frontier size, the frontier's
out-edge sum, the in-edge sum of the program's actual pull destination
set under the backend's actual layout, and the unvisited-edge count —
and hands it to ``policy.decide``; ``AutoSwitch`` prices both directions
with the §4 cost model from exactly these statistics. ``Fixed`` policies
keep their static fast path: only the chosen direction is traced and no
statistics are computed.

Every phase loop carries a real *visited* mask (the union of every
frontier so far), so ``GenericSwitch``'s growing-phase test sees the
actual unvisited edge count, and push steps pay the paper's k-filter
compaction. With ``trace_capacity > 0`` the loop also carries a
:class:`~repro.core.cost_model.StepTrace` recording, per executed step,
the chosen direction, the frontier statistics, and the step's counter
deltas — the raw material for ``BENCH_*.json`` trajectories. ``state``
may be any pytree; it is the only channel between phases and epochs, so
the carry structure must be stable across them.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from ..graphs.structure import Graph
from .backend import DenseBackend, ExchangeBackend
from .cost_model import Cost, StepStats, StepTrace, counter, counter_dtype
from .direction import Direction, DirectionPolicy, Fixed, GreedySwitch
from .primitives import frontier_in_edges, frontier_out_edges, k_filter

__all__ = ["VertexProgram", "Phase", "PhaseProgram", "PushPullEngine",
           "EngineResult", "Checkpoint"]


class Checkpoint(NamedTuple):
    """A stepwise solve's resumable snapshot: the full loop carry after
    ``step`` completed steps. The carry is a device pytree — holding it
    is a reference, not a copy — and resuming re-enters the identical
    jitted body, so a resumed run is bit-identical to an uninterrupted
    one."""
    step: int
    carry: Any


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    combine: str = "sum"
    msg_fn: Optional[Callable] = None
    # update_fn(state, msgs, step) -> (state, frontier, converged)
    update_fn: Callable = None  # type: ignore[assignment]
    # values_fn(g, state, frontier) -> values put on the wire (default:
    # state itself — the label-propagation case)
    values_fn: Optional[Callable] = None
    # what pull inspects: 'all' destinations, or only the 'unvisited' ones
    # (BFS-style programs where settled vertices never update again)
    pull_touched: str = "all"
    # touched_fn(g, state, frontier, visited) -> bool[n]: state-derived
    # pull destination set (Δ-stepping's unsettled set, BC's level masks);
    # overrides pull_touched when set
    touched_fn: Optional[Callable] = None
    # static per-iteration charges, e.g. (("reads", 2 * n),) for reading
    # own state + degree when forming contributions
    step_charges: tuple = ()
    # dynamic per-iteration charges: charge_fn(g, state, frontier) -> dict
    # of traced counter increments (state/frontier are pre-update)
    charge_fn: Optional[Callable] = None
    # charge the paper's k-filter (frontier compaction) after push steps —
    # only meaningful for sparse-frontier programs (BFS); dense programs
    # (PR) never filter, matching the paper's accounting
    k_filter_push: bool = False
    # k_filter_set_fn(old_state, new_state, frontier) -> bool[n]: the set
    # the push k-filter compacts, when it differs from the next frontier
    # (Δ-stepping filters the *updated* vertices; its frontier is the
    # whole re-activated bucket). Default: the frontier itself.
    k_filter_set_fn: Optional[Callable] = None
    # GreedySwitch terminal hand-off (paper §5-GrS): invoked once when the
    # active set drops below the policy's tail threshold
    tail_fn: Optional[Callable] = None
    # local_fn(g, state, frontier, step, do_push, cost)
    #   -> (state, frontier, converged, cost)
    # replaces the relax+update step entirely: the step never touches the
    # exchange backend (partition-sequential coloring, Borůvka's find-min
    # over contracted supervertices, blocked triangle edge maps). The
    # decided direction still arrives as `do_push` so the step can charge
    # the paper's direction-dependent cost.
    local_fn: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Phase:
    """One fixed-point (or bounded) loop inside a program.

    enter_fn(g, state, frontier, epoch) -> (state, frontier) rewrites the
        carry before the phase's first step (Δ-stepping's bucket frontier,
        BC's per-source reset / backward-level seeding).
    exit_fn(g, state, frontier, cost) -> (state, frontier, cost) runs
        after the phase's loop (contraction, trace post-processing).
    """
    program: VertexProgram
    max_steps: int = 100
    name: str = ""
    enter_fn: Optional[Callable] = None
    exit_fn: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class PhaseProgram:
    """A sequence of phases, optionally iterated as epochs.

    epoch_cond(g, state, epoch) -> bool: run another epoch? (checked
        before each epoch; None = run exactly ``max_epochs``).
    epoch_exit_fn(g, state, frontier, epoch) -> (state, frontier): carry
        rewrite after each epoch (BC's per-source accumulation, Borůvka's
        component relabel when not expressed as a phase).
    max_epochs: epoch bound; None defers to the engine's ``max_steps``.
    """
    phases: tuple
    max_epochs: Optional[int] = None
    epoch_cond: Optional[Callable] = None
    epoch_exit_fn: Optional[Callable] = None


class EngineResult(NamedTuple):
    state: Any
    cost: Cost
    steps: jax.Array
    push_steps: jax.Array
    converged: jax.Array = jnp.bool_(True)
    epochs: jax.Array = jnp.int32(1)
    trace: Any = None            # StepTrace when trace_capacity > 0
    # final backend exchange-carried state (e.g. the compression
    # error-feedback accumulator); () for stateless backends — surfaced
    # so telemetry can report compression residuals post-run
    xstate: Any = ()


class _Loop(NamedTuple):
    state: Any
    frontier: jax.Array
    visited: jax.Array
    converged: jax.Array
    handoff: jax.Array
    step: jax.Array
    cost: Cost
    pushes: jax.Array
    last_push: jax.Array
    trace: StepTrace
    # backend exchange-carried state (compression error feedback); an
    # empty pytree for stateless backends
    xstate: Any = ()


@dataclasses.dataclass(frozen=True)
class PushPullEngine:
    program: Union[VertexProgram, PhaseProgram]
    policy: DirectionPolicy = Fixed(Direction.PULL)
    max_steps: int = 100
    backend: ExchangeBackend = DenseBackend()
    # > 0 allocates a StepTrace of that many slots and records every
    # executed step into it (overflow steps are dropped); 0 = no tracing,
    # no overhead
    trace_capacity: int = 0

    def _step_stats(self, g: Graph, prog: VertexProgram, st: _Loop,
                    unvisited, touched, values) -> StepStats:
        """The decision inputs for this step — §4's quantities, computed
        from degree sums only (no edge traversal)."""
        # what THIS backend's pull would actually traverse — full scan
        # (m, n) for scan-all backends, the frontier restriction for
        # backends that can gather only touched rows
        pull_edges, pull_vertices = self.backend.predict_pull_scan(
            g, touched, values=values, combine=prog.combine,
            msg_fn=prog.msg_fn)
        # the layout-independent Σ in-degree over touched destinations
        # (what an ideal CSR pull would read), kept for analysis
        pull_touched = (counter(g.m) if touched is None
                        else frontier_in_edges(g, touched))
        float_data = bool(values is not None
                          and jnp.issubdtype(values.dtype, jnp.floating))
        # payload elements per vertex on the wire — B for batched
        # multi-query values [n, B] (repro.service), 1 for plain vectors
        width = (1 if values is None or values.ndim == 1
                 else int(values.shape[-1]))
        # inter-device bytes each direction would move (0 on one device)
        push_wb = pull_wb = counter(0)
        if values is not None:
            push_wb, pull_wb = self.backend.predict_comm_bytes(
                g, values, st.frontier)
        return StepStats(
            frontier_vertices=jnp.sum(
                st.frontier.astype(counter_dtype())),
            frontier_edges=frontier_out_edges(g, st.frontier),
            pull_edges=pull_edges, pull_vertices=pull_vertices,
            unvisited_edges=frontier_in_edges(g, unvisited),
            step=st.step, prev_push=st.last_push,
            float_data=float_data, k_filter_push=prog.k_filter_push,
            width=width, push_wire_bytes=push_wb, pull_wire_bytes=pull_wb,
            pull_touched_edges=pull_touched)

    # -- one phase: the classic fixed-point loop --------------------------
    def _phase_loop(self, g: Graph, phase: Phase, state0, frontier0,
                    epoch, cost0: Cost, steps0, pushes0,
                    trace0: StepTrace, xstate0=()):
        """Build one phase's ``(cond, body, init)`` loop pieces.

        ``_run_phase`` feeds them to ``lax.while_loop``;
        :meth:`run_stepwise` instead jits ``body`` once and drives it
        from the host, blocking between steps so telemetry can stamp
        per-step wall times — same closures, same arithmetic, same
        result."""
        prog = phase.program
        values_fn = prog.values_fn or (lambda g_, s, f: s)
        greedy = (isinstance(self.policy, GreedySwitch)
                  and prog.tail_fn is not None)
        # Fixed policies dispatch statically: only the chosen direction is
        # traced/compiled (switching policies pay the lax.cond).
        fixed_dir = (self.policy.direction
                     if isinstance(self.policy, Fixed) else None)
        tracing = self.trace_capacity > 0
        predictor = self.policy.trace_predictor() if tracing else None

        if phase.enter_fn is not None:
            state0, frontier0 = phase.enter_fn(g, state0, frontier0, epoch)

        def cond(st: _Loop):
            return ((~st.converged) & (~st.handoff)
                    & (st.step < phase.max_steps))

        def step(st: _Loop):
            unvisited = ~st.visited
            # the program's pull destination set and wire values are
            # direction-independent, so they can inform the decision
            if prog.local_fn is not None:
                values = touched = None
            else:
                values = values_fn(g, st.state, st.frontier)
                if prog.touched_fn is not None:
                    touched = prog.touched_fn(g, st.state, st.frontier,
                                              st.visited)
                elif prog.pull_touched == "unvisited":
                    touched = unvisited
                else:
                    touched = None
            with jax.named_scope("policy.decide"):
                stats = (self._step_stats(g, prog, st, unvisited, touched,
                                          values)
                         if (fixed_dir is None or tracing) else None)
                if fixed_dir is not None:
                    direction = fixed_dir
                    do_push = jnp.bool_(fixed_dir == Direction.PUSH)
                else:
                    direction = do_push = self.policy.decide(
                        g, st.frontier, stats)
            cost = st.cost
            xstate = st.xstate
            if prog.local_fn is not None:
                state, frontier, conv, cost = prog.local_fn(
                    g, st.state, st.frontier, st.step, do_push, cost)
            else:
                msgs, cost, xstate = self.backend.relax_ex(
                    g, values, st.frontier, direction=direction,
                    combine=prog.combine, msg_fn=prog.msg_fn,
                    touched=touched, cost=cost, xstate=xstate)
            with jax.named_scope("program.update"):
                if prog.local_fn is None:
                    state, frontier, conv = prog.update_fn(st.state, msgs,
                                                           st.step)
                    if prog.k_filter_push:
                        # push produced a sparse updated set -> k-filter
                        # compacts it (paper: pull inspects every vertex)
                        kf_set = (frontier if prog.k_filter_set_fn is None
                                  else prog.k_filter_set_fn(st.state, state,
                                                            frontier))
                        _, cost = jax.lax.cond(
                            do_push, k_filter, lambda f, c: (f, c), kf_set,
                            cost)
                cost = cost.charge(iterations=1, barriers=1,
                                   **dict(prog.step_charges))
                if prog.charge_fn is not None:
                    cost = cost.charge(**prog.charge_fn(g, st.state,
                                                        st.frontier))
                handoff = st.handoff
                if greedy:
                    active = jnp.sum(frontier.astype(counter_dtype()))
                    handoff = (~conv) & self.policy.should_handoff(g,
                                                                   active)
                trace = st.trace
                if tracing:
                    delta = jax.tree.map(lambda a, b: a - b, cost, st.cost)
                    trace = st.trace.record(
                        steps0 + st.step, do_push, stats, delta,
                        predicted_push=predictor.predict_push(stats),
                        predicted_pull=predictor.predict_pull(stats))
            return _Loop(state=state, frontier=frontier,
                         visited=st.visited | frontier, converged=conv,
                         handoff=handoff, step=st.step + 1, cost=cost,
                         pushes=st.pushes + do_push.astype(jnp.int32),
                         last_push=do_push, trace=trace, xstate=xstate)

        def body(st: _Loop):
            # profiler scopes: compile-time op metadata, no added ops
            with jax.named_scope("engine.step"):
                return step(st)

        # an empty entering frontier is already converged (matches the
        # seed loops, whose cond checked the frontier before any work)
        init = _Loop(state=state0, frontier=frontier0, visited=frontier0,
                     converged=~jnp.any(frontier0),
                     handoff=jnp.bool_(False), step=jnp.int32(0),
                     cost=cost0, pushes=jnp.int32(0),
                     last_push=jnp.bool_(False), trace=trace0,
                     xstate=xstate0)
        return cond, body, init

    def _finish_phase(self, g: Graph, phase: Phase, fin: _Loop, steps0,
                      pushes0):
        """Post-loop phase epilogue: greedy tail hand-off and exit_fn."""
        prog = phase.program
        greedy = (isinstance(self.policy, GreedySwitch)
                  and prog.tail_fn is not None)
        state, frontier, cost = fin.state, fin.frontier, fin.cost
        converged = fin.converged
        if greedy:
            state, cost = jax.lax.cond(
                fin.handoff,
                lambda s, f, c: prog.tail_fn(g, s, f, c),
                lambda s, f, c: (s, c),
                fin.state, fin.frontier, fin.cost)
            converged = converged | fin.handoff
        if phase.exit_fn is not None:
            state, frontier, cost = phase.exit_fn(g, state, frontier, cost)
        return (state, frontier, cost, steps0 + fin.step,
                pushes0 + fin.pushes, converged, fin.trace, fin.xstate)

    def _run_phase(self, g: Graph, phase: Phase, state0, frontier0, epoch,
                   cost0: Cost, steps0, pushes0, trace0: StepTrace,
                   xstate0=()):
        cond, body, init = self._phase_loop(
            g, phase, state0, frontier0, epoch, cost0, steps0, pushes0,
            trace0, xstate0)
        fin = jax.lax.while_loop(cond, body, init)
        return self._finish_phase(g, phase, fin, steps0, pushes0)

    # -- the full program: phases under an epoch loop ---------------------
    def run(self, g: Graph, init_state: Any,
            init_frontier: jax.Array) -> EngineResult:
        return self._run(g, init_state, init_frontier,
                         self.backend.operands())

    @partial(jax.jit, static_argnames=("self",))
    def _run(self, g: Graph, init_state: Any, init_frontier: jax.Array,
             operands: Any) -> EngineResult:
        # the backend's graph-sized views enter as arguments (closed
        # over, they would be baked into the program as constants)
        bound = dataclasses.replace(self,
                                    backend=self.backend.bind(operands))
        return bound._run_body(g, init_state, init_frontier)

    def _run_body(self, g: Graph, init_state: Any,
                  init_frontier: jax.Array) -> EngineResult:
        if isinstance(self.program, PhaseProgram):
            pp = self.program
            phases = tuple(pp.phases)
            max_epochs = (self.max_steps if pp.max_epochs is None
                          else pp.max_epochs)
            epoch_cond, epoch_exit = pp.epoch_cond, pp.epoch_exit_fn
        else:
            phases = (Phase(program=self.program,
                            max_steps=self.max_steps),)
            max_epochs, epoch_cond, epoch_exit = 1, None, None

        trace0 = StepTrace.empty(self.trace_capacity)
        xstate0 = self.backend.init_exchange_state(g)

        def run_epoch(state, frontier, epoch, cost, steps, pushes, trace,
                      xstate):
            conv = jnp.bool_(True)
            for ph in phases:         # statically unrolled: phases differ
                (state, frontier, cost, steps, pushes, conv, trace,
                 xstate) = self._run_phase(g, ph, state, frontier, epoch,
                                           cost, steps, pushes, trace,
                                           xstate)
            if epoch_exit is not None:
                state, frontier = epoch_exit(g, state, frontier, epoch)
            return state, frontier, cost, steps, pushes, conv, trace, \
                xstate

        def result(state, cost, steps, pushes, converged, epochs, trace,
                   xstate=()):
            return EngineResult(
                state=state, cost=cost, steps=steps, push_steps=pushes,
                converged=converged, epochs=epochs,
                trace=trace if self.trace_capacity > 0 else None,
                xstate=xstate)

        if max_epochs == 1 and epoch_cond is None:
            # single-epoch programs (the PR-1 algorithms) skip the outer
            # loop entirely — same trace as the old flat engine
            state, frontier, cost, steps, pushes, conv, trace, xs = \
                run_epoch(init_state, init_frontier, jnp.int32(0), Cost(),
                          jnp.int32(0), jnp.int32(0), trace0, xstate0)
            return result(state, cost, steps, pushes, conv, jnp.int32(1),
                          trace, xs)

        def cond(carry):
            (state, frontier, epoch, cost, steps, pushes, conv,
             trace, xstate) = carry
            go = epoch < max_epochs
            if epoch_cond is not None:
                go = go & epoch_cond(g, state, epoch)
            return go

        def body(carry):
            (state, frontier, epoch, cost, steps, pushes, _, trace,
             xstate) = carry
            state, frontier, cost, steps, pushes, conv, trace, xstate = \
                run_epoch(state, frontier, epoch, cost, steps, pushes,
                          trace, xstate)
            return (state, frontier, epoch + 1, cost, steps, pushes, conv,
                    trace, xstate)

        init = (init_state, init_frontier, jnp.int32(0), Cost(),
                jnp.int32(0), jnp.int32(0), jnp.bool_(True), trace0,
                xstate0)
        state, frontier, epochs, cost, steps, pushes, conv, trace, xs = \
            jax.lax.while_loop(cond, body, init)
        if epoch_cond is not None:
            # converged iff the work test (not the epoch bound) ended it
            converged = ~epoch_cond(g, state, epochs)
        else:
            converged = conv
        return result(state, cost, steps, pushes, converged, epochs,
                      trace, xs)

    # -- host-driven stepwise execution (telemetry timing path) -----------
    @property
    def supports_stepwise(self) -> bool:
        """True when :meth:`run_stepwise` can execute this program —
        flat (single-phase, single-epoch) programs only."""
        return not isinstance(self.program, PhaseProgram)

    @staticmethod
    def _check_finite(state: Any, mode, step: int) -> None:
        """Abort on non-finite float state: ``mode`` ``"nan"`` trips on
        NaN only (the default — BFS/SSSP legitimately carry ±Inf
        sentinels), ``"all"``/True on NaN or ±Inf. Raises the
        structured :class:`repro.resilience.DivergenceError` naming the
        step, instead of burning the remaining ``max_steps`` budget on
        poisoned values."""
        from ..resilience import DivergenceError
        strict = mode in ("all", True)
        for leaf in jax.tree_util.tree_leaves(state):
            if not (hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                continue
            bad = (not bool(jnp.isfinite(leaf).all()) if strict
                   else bool(jnp.isnan(leaf).any()))
            if bad:
                raise DivergenceError(
                    step=step, mode="all" if strict else "nan")

    def run_stepwise(self, g: Graph, init_state: Any,
                     init_frontier: jax.Array,
                     on_step: Optional[Callable] = None,
                     check_finite=None,
                     checkpoint_every: int = 0,
                     resume_from: Optional[Checkpoint] = None
                     ) -> EngineResult:
        """Run a flat program one step at a time from the host.

        Semantically identical to :meth:`run` — the loop body is the
        same closure ``_phase_loop`` hands to ``lax.while_loop``, jitted
        once and called repeatedly — but the host blocks on every step
        (``jax.block_until_ready``), so ``on_step(step_index,
        wall_us)`` observes a real per-step wall time. This is the
        telemetry timing path: the jitted-loop path cannot see host
        timestamps at step boundaries from inside ``lax.while_loop``.

        The host loop is also where the resilience guards live:

        * ``check_finite``: ``"nan"``/True/``"all"`` enables the
          divergence detector (:meth:`_check_finite`) after every step.
        * ``checkpoint_every=N``: snapshot the loop carry every N
          completed steps. A failure mid-loop (an injected
          ``engine.step`` fault, a poisoned device buffer) then raises
          :class:`~repro.resilience.SolveInterrupted` carrying the last
          :class:`Checkpoint` instead of losing the run.
        * ``resume_from``: re-enter the loop from a checkpoint; the
          remaining steps replay the identical jitted body, so the
          final result is bit-identical to an uninterrupted run.

        The same ops run in the same order, so results are bit-identical
        to :meth:`run` (deterministic backends). Each call re-traces the
        step body (one compile per call); use :meth:`run` when timing is
        not needed.

        Raises:
            ValueError: for :class:`PhaseProgram` programs — their
                epoch/phase structure runs under :meth:`run`.
            DivergenceError: ``check_finite`` tripped.
            SolveInterrupted: the loop died with ``checkpoint_every``
                set (or on an injected ``engine.step`` fault).
        """
        if not self.supports_stepwise:
            raise ValueError(
                "run_stepwise executes flat (single-VertexProgram) "
                "programs only; phase-structured programs run under "
                "run() — check supports_stepwise before dispatching")
        import time

        from ..resilience import (DivergenceError, SolveInterrupted,
                                  fault_point)
        phase = Phase(program=self.program, max_steps=self.max_steps)
        trace0 = StepTrace.empty(self.trace_capacity)
        xstate0 = self.backend.init_exchange_state(g)
        cond, body, init = self._phase_loop(
            g, phase, init_state, init_frontier, jnp.int32(0), Cost(),
            jnp.int32(0), jnp.int32(0), trace0, xstate0)
        body_j = jax.jit(body)
        st, i = init, 0
        last_ckpt = resume_from
        if resume_from is not None:
            st, i = resume_from.carry, resume_from.step
        if on_step is not None and bool(cond(st)):
            # pay tracing/compilation outside the timed loop (the body is
            # pure, so a discarded warmup execution is free of effects) —
            # otherwise step 0's wall time is dominated by the compile
            # and the decision audit flags it spuriously
            jax.block_until_ready(body_j(st))
        while True:
            try:
                fault_point("engine.step")
                if not bool(cond(st)):
                    break
                t0 = time.perf_counter()
                nxt = body_j(st)
                jax.block_until_ready(nxt)
                dt_us = (time.perf_counter() - t0) * 1e6
            except (DivergenceError, SolveInterrupted):
                raise
            except Exception as exc:  # noqa: BLE001 — resumable seam
                raise SolveInterrupted(step=i,
                                       checkpoint=last_ckpt) from exc
            st = nxt
            i += 1
            if check_finite:
                self._check_finite(st.state, check_finite, i - 1)
            if checkpoint_every and i % checkpoint_every == 0:
                last_ckpt = Checkpoint(step=i, carry=st)
            if on_step is not None:
                on_step(i - 1, dt_us)
        state, frontier, cost, steps, pushes, conv, trace, xs = \
            self._finish_phase(g, phase, st, jnp.int32(0), jnp.int32(0))
        return EngineResult(
            state=state, cost=cost, steps=steps, push_steps=pushes,
            converged=conv, epochs=jnp.int32(1),
            trace=trace if self.trace_capacity > 0 else None, xstate=xs)
