"""repro.api — one k-relaxation API for every graph workload.

The paper's claim is that every graph algorithm reduces to one abstract
primitive (k-relaxation) with push and pull as interchangeable
implementations. This module is that claim as an interface:

    from repro import api
    from repro.core import Fixed, Direction, GenericSwitch
    from repro.core.backend import EllBackend

    r = api.solve(g, "pagerank", iters=30)                  # GS policy
    r = api.solve(g, "bfs", root=0, policy=Fixed(Direction.PUSH))
    r = api.solve(g, "bfs", root=0, policy="auto")          # AutoSwitch
    r = api.solve(g, "sssp_delta", source=0, delta=2.0)     # Δ-stepping
    r = api.solve(g, "mst_boruvka", backend=EllBackend())   # ELL layout
    r = api.solve(g, "bfs", root=0, backend="pallas")       # Pallas kernels

Every algorithm is a :class:`~repro.core.engine.VertexProgram` — or a
multi-phase :class:`~repro.core.engine.PhaseProgram` (Δ-stepping's bucket
epochs, Brandes BC's forward/backward pair, Borůvka's find-min/contract
rounds, Boman coloring's color/fix iterations) — executed by the
:class:`~repro.core.engine.PushPullEngine`; ``policy`` chooses the
direction per step (Fixed / GenericSwitch / GreedySwitch) and ``backend``
chooses the memory system (Dense / ELL / Pallas / Distributed) — any
algorithm runs under any (policy × backend) cell it declares supported
and returns the same states. Unsupported combinations raise a
``ValueError`` naming the combination.

``solve`` returns a :class:`RunResult` with a unified surface:
``state`` (algorithm-specific pytree), ``cost`` (paper Table-1
counters), ``steps``, ``push_steps``, ``epochs``, ``converged``.

New algorithms register an :class:`AlgorithmSpec`; engines are cached per
(algorithm, policy, backend, static-kwargs, graph shape) so repeated
solves hit the jit cache like the hand-rolled loops they replaced.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

from .core.algorithms.betweenness import (betweenness_finalize,
                                          betweenness_init,
                                          betweenness_program)
from .core.algorithms.bfs import bfs_init, bfs_program
from .core.algorithms.coloring import (coloring_finalize, coloring_init,
                                       coloring_program)
from .core.algorithms.mst_boruvka import (mst_finalize, mst_init,
                                          mst_program)
from .core.algorithms.pagerank import pagerank_init, pagerank_program
from .core.algorithms.ppr import ppr_finalize, ppr_init, ppr_program
from .core.algorithms.pr_delta import (pr_delta_finalize, pr_delta_init,
                                       pr_delta_program)
from .core.algorithms.sssp_delta import (sssp_delta_finalize,
                                         sssp_delta_init,
                                         sssp_delta_program)
from .core.algorithms.triangle_count import (triangle_finalize,
                                             triangle_init,
                                             triangle_program)
from .core.algorithms.wcc import wcc_init, wcc_program
from .core.backend import (DenseBackend, DistributedBackend, EllBackend,
                           ExchangeBackend, PallasBackend)
from .core.cost_model import Cost, StepTrace
from .core.direction import (AutoSwitch, Direction, DirectionPolicy, Fixed,
                             GenericSwitch, GreedySwitch)
from .core.engine import PhaseProgram, PushPullEngine, VertexProgram
from .graphs.structure import Graph

__all__ = ["RunResult", "AlgorithmSpec", "register", "algorithms",
           "get_spec", "solve", "solve_batch", "POLICY_SHORTHANDS",
           "BACKEND_SHORTHANDS", "DenseBackend", "EllBackend",
           "PallasBackend", "DistributedBackend", "ExchangeBackend",
           "Fixed", "GenericSwitch", "GreedySwitch", "AutoSwitch",
           "Direction"]


class RunResult(NamedTuple):
    """Unified result of ``solve``.

    Attributes:
        state: the algorithm's public state pytree (e.g. BFS's
            ``{"dist", "parent", "visited"}`` dict, PageRank's rank
            vector).
        cost: accumulated paper-Table-1 counters
            (:class:`~repro.core.cost_model.Cost`); collapse with
            ``cost.weighted_total()`` for one comparable scalar.
        steps: relaxation/local steps across all phases.
        push_steps: how many of those ran in push direction.
        converged: whether the fixed point (not a step bound) ended the
            run.
        epochs: outer rounds — buckets, sources, Borůvka rounds,
            coloring iterations; 1 for flat programs.
        trace: per-step :class:`~repro.core.cost_model.StepTrace` when
            ``solve(..., trace=N)`` was given, else None.

    Example::

        r = api.solve(g, "bfs", root=0, policy="auto", trace=64)
        int(r.steps), bool(r.converged)
        float(r.cost.weighted_total())
        r.trace.as_dict(int(r.steps))["pushed"]   # per-step directions
    """
    state: Any
    cost: Cost
    steps: jax.Array
    push_steps: jax.Array
    converged: jax.Array
    epochs: jax.Array
    trace: Optional[StepTrace] = None


@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    """How an algorithm plugs into the engine.

    build(g, *, policy, backend, **static_kw) -> (program,
        default_max_steps) — ``program`` is a VertexProgram or a
        PhaseProgram (for phase programs the default bounds *epochs*).
        Must close over static graph attributes only (n, m), never
        arrays, so engines cache across graphs of one shape; must raise
        NotImplementedError/ValueError for (policy, backend) combinations
        it has no execution path for (``solve`` surfaces these as a
        ValueError naming the combination).
    init(g, **kw) -> (init_state, init_frontier).
    finalize(g, state) -> public state pytree.
    runtime_keys: kwargs consumed only by ``init`` (e.g. ``root``),
        excluded from the engine cache key.
    backends: declared-supported backend names (introspection only; the
        authoritative check lives in ``build``).
    policies: declared-supported policy shorthands (see
        ``POLICY_SHORTHANDS``) — the (policy × backend) support matrix
        that docs/algorithms.md and the benchmark sweep enumerate.
    paper: the paper section this algorithm reproduces.
    """
    name: str
    build: Callable
    init: Callable
    finalize: Callable = staticmethod(lambda g, state: state)
    default_policy: DirectionPolicy = GenericSwitch()
    runtime_keys: tuple = ()
    backends: tuple = ("dense", "ell", "pallas", "distributed", "shard")
    policies: tuple = ("push", "pull", "gs", "grs", "auto")
    paper: str = ""


_REGISTRY: dict[str, AlgorithmSpec] = {}


class EngineCache:
    """Bounded FIFO of built engines keyed by hashable tuples.

    A DistributedBackend key pins graph-sized edge arrays, so stale
    entries must be evictable in long-lived processes; unhashable keys
    (e.g. unhashable kwargs) skip caching and rebuild every call.
    Shared by ``solve`` and the service layer's batched path.
    """

    def __init__(self, max_size: int = 128):
        self.max_size = max_size
        self._data: dict = {}

    def get_or_build(self, key, build: Callable):
        try:
            hash(key)
        except TypeError:
            return build()
        engine = self._data.get(key)
        if engine is None:
            engine = build()
            while len(self._data) >= self.max_size:
                self._data.pop(next(iter(self._data)))
            self._data[key] = engine
        return engine


# Built engines keyed by (algorithm, policy, backend, static kwargs,
# graph shape).
_ENGINE_CACHE = EngineCache()


def register(spec: AlgorithmSpec) -> AlgorithmSpec:
    _REGISTRY[spec.name] = spec
    return spec


def algorithms() -> list[str]:
    """Names accepted by ``solve``."""
    return sorted(_REGISTRY)


def get_spec(name: str) -> AlgorithmSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown algorithm {name!r}; registered: {algorithms()}"
        ) from None


# String shorthands accepted wherever a DirectionPolicy is expected —
# zero-arg factories so each solve gets a fresh default-configured policy.
POLICY_SHORTHANDS: dict[str, Callable[[], DirectionPolicy]] = {
    "push": lambda: Fixed(Direction.PUSH),
    "pull": lambda: Fixed(Direction.PULL),
    "gs": GenericSwitch,
    "grs": GreedySwitch,
    "auto": AutoSwitch,
}

# String shorthands accepted wherever an ExchangeBackend is expected.
# One shared instance per name (not a factory): engines are cached per
# backend instance, and the Pallas backend additionally keeps its
# autotuner cache warm across solves. "distributed" is absent on
# purpose — it is graph-specific and must go through
# DistributedBackend.prepare(g).
BACKEND_SHORTHANDS: dict[str, ExchangeBackend] = {
    "dense": DenseBackend(),
    "ell": EllBackend(),
    "pallas": PallasBackend(),
}

# solve(trace=True) records up to this many steps
_DEFAULT_TRACE_CAPACITY = 256

# numbers each solve() call; its profiler spans carry the number
_SOLVE_SEQ = itertools.count()

# runtime kwargs that name vertices and must index into [0, n); JAX
# scatter semantics would otherwise clip/drop bad indices silently
_VERTEX_KEYS = ("root", "source")


def validate_vertex_indices(g: Graph, name: str, value) -> None:
    """Raise ``ValueError`` naming any vertex index outside ``[0, n)``.

    ``value`` may be a python int, a 0-d array, or a sequence/array of
    ints (``solve_batch`` sources). Traced (abstract) values pass
    through unchecked — inside jit the caller owns validity.
    """
    import numpy as np
    try:
        arr = np.asarray(value)
    except Exception:  # traced values have no concrete array view
        return
    if arr.size == 0:  # emptiness is the callee's error to report
        return
    if arr.dtype == object or not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(
            f"{name}={value!r} is not a vertex index (expected integer "
            f"in [0, {g.n}))")
    bad = (arr < 0) | (arr >= g.n)
    if bad.any():
        first = int(arr.reshape(-1)[np.flatnonzero(bad.reshape(-1))[0]])
        raise ValueError(
            f"{name} contains vertex index {first} out of range for a "
            f"graph with n={g.n} vertices (valid: 0..{g.n - 1})")


def _resolve_policy(policy) -> DirectionPolicy:
    if not isinstance(policy, str):
        return policy
    try:
        return POLICY_SHORTHANDS[policy]()
    except KeyError:
        raise ValueError(
            f"unknown policy shorthand {policy!r}; valid options: "
            f"{sorted(POLICY_SHORTHANDS)} (or pass a DirectionPolicy "
            "instance)") from None


# Graph-specific "shard" backends, cached per live graph object (keyed
# by id with a weakref guard against id reuse after collection).
_SHARD_BACKENDS: dict[int, tuple] = {}


def _shard_backend_for(g: Graph) -> ExchangeBackend:
    import weakref

    from .shard import ShardedBackend
    key = id(g)
    hit = _SHARD_BACKENDS.get(key)
    if hit is not None and hit[0]() is g:
        return hit[1]
    prepared = ShardedBackend.prepare(g)
    ref = weakref.ref(g, lambda _: _SHARD_BACKENDS.pop(key, None))
    _SHARD_BACKENDS[key] = (ref, prepared)
    return prepared


def _resolve_backend(backend, g: Optional[Graph] = None
                     ) -> ExchangeBackend:
    if backend is None:
        return BACKEND_SHORTHANDS["dense"]
    if not isinstance(backend, str):
        return backend
    if backend == "shard":
        # graph-specific: prepared per graph (mesh over all visible
        # devices), not a shared instance like the other shorthands
        if g is None:
            raise ValueError(
                "backend='shard' is graph-specific; pass it through "
                "solve()/solve_batch(), or prepare an instance with "
                "repro.shard.ShardedBackend.prepare(g)")
        return _shard_backend_for(g)
    try:
        return BACKEND_SHORTHANDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown backend shorthand {backend!r}; valid options: "
            f"{sorted(BACKEND_SHORTHANDS) + ['shard']} (or pass an "
            "ExchangeBackend instance, e.g. "
            "DistributedBackend.prepare(g))") from None


def solve(g: Graph, algorithm: str, *,
          policy: Optional[DirectionPolicy | str] = None,
          backend: Optional[ExchangeBackend | str] = None,
          max_steps: Optional[int] = None,
          trace: int | bool = 0, telemetry=None,
          check_finite=None, checkpoint_every: int = 0,
          **kw) -> RunResult:
    """Run ``algorithm`` on ``g`` under a direction policy and an
    exchange backend.

    Args:
        g: the :class:`~repro.graphs.structure.Graph` to process.
        algorithm: a registered name — see :func:`algorithms`.
        policy: a :class:`~repro.core.direction.DirectionPolicy` instance
            or one of the string shorthands ``"push"``, ``"pull"``,
            ``"gs"`` (GenericSwitch), ``"grs"`` (GreedySwitch), ``"auto"``
            (cost-model-driven AutoSwitch). Default: the algorithm's
            declared default policy.
        backend: an :class:`ExchangeBackend` instance or one of the
            string shorthands ``"dense"``, ``"ell"``, ``"pallas"``
            (kernel-dispatching :class:`PallasBackend`); default
            :class:`DenseBackend`.
        max_steps: per-phase step bound override (bounds *epochs* for
            phase programs).
        trace: record a per-step
            :class:`~repro.core.cost_model.StepTrace` on the result —
            an int capacity, or True for a default of 256 slots.
        telemetry: a :class:`repro.obs.Telemetry` handle, or None
            (default). With a handle, the run emits structured events
            into it — per-step counter/prediction rows, a run summary,
            and a direction-decision audit — and single-phase solves
            route through the engine's host-driven stepwise loop so
            each step also carries measured wall time (set
            ``telemetry.step_timing = False`` to keep single-dispatch
            execution). ``None`` is the untouched fast path:
            bit-identical results, zero events, no obs import.
        check_finite: enable the divergence guard — ``"nan"``/True
            trips on NaN state, ``"all"`` additionally on ±Inf
            (BFS/SSSP carry legitimate Inf sentinels, so ``"all"`` is
            only for algorithms with finite state). Flat programs check
            after every step (via the stepwise loop) and raise a
            structured :class:`repro.resilience.DivergenceError` naming
            the step; phase programs check the final state.
        checkpoint_every: snapshot the loop carry every N steps (flat
            programs only); an interrupted or faulted solve resumes
            from the last checkpoint automatically — bounded resume
            budget, bit-identical result — instead of restarting from
            scratch. 0 (default) disables; the engine's fully-jitted
            ``run`` path is used and nothing changes.
        **kw: algorithm-specific kwargs (``root``, ``source``, ``iters``,
            ``damp``, ``tol``, ...).

    Example::

        r = api.solve(g, "bfs", root=0, policy="auto")
        r = api.solve(g, "pagerank", iters=30, backend="pallas")
        r = api.solve(g, "sssp_delta", source=0, delta=2.0, trace=128)

    Raises:
        KeyError: unknown algorithm name.
        ValueError: unknown policy or backend shorthand, a (policy ×
            backend) combination the algorithm declares unsupported, or
            a ``root``/``source`` vertex index outside ``[0, n)``.
    """
    # host spans on the profiler's clock (no-ops while no profile is
    # being taken); the sequence number ties one call's spans together
    with TraceAnnotation("repro.solve", solve=next(_SOLVE_SEQ)):
        with TraceAnnotation("repro.solve.prepare"):
            spec = get_spec(algorithm)
            for vkey in _VERTEX_KEYS:
                if vkey in kw:
                    validate_vertex_indices(g, vkey, kw[vkey])
            policy = (spec.default_policy if policy is None
                      else _resolve_policy(policy))
            backend = _resolve_backend(backend, g)
            trace_capacity = (_DEFAULT_TRACE_CAPACITY if trace is True
                              else int(trace))
            if telemetry is not None and trace_capacity == 0:
                # telemetry needs the in-loop StepTrace rows to audit
                # against
                trace_capacity = _DEFAULT_TRACE_CAPACITY
            static_kw = {k: v for k, v in kw.items()
                         if k not in spec.runtime_keys}

            def build_engine() -> PushPullEngine:
                try:
                    program, default_steps = spec.build(
                        g, policy=policy, backend=backend, **static_kw)
                except (NotImplementedError, ValueError) as e:
                    raise ValueError(
                        f"algorithm {algorithm!r} does not support the "
                        f"combination policy={policy.name} × "
                        f"backend={backend.name}: {e}") from e
                return PushPullEngine(
                    program=program, policy=policy,
                    max_steps=(default_steps if max_steps is None
                               else max_steps),
                    backend=backend, trace_capacity=trace_capacity)

            # key on the spec itself: re-registering a name invalidates
            # cached engines built from the old spec
            engine = _ENGINE_CACHE.get_or_build(
                (algorithm, spec, policy, backend,
                 tuple(sorted(static_kw.items())),
                 g.n, g.m, g.d_ell, max_steps, trace_capacity),
                build_engine)
        with TraceAnnotation("repro.solve.init"):
            init_state, init_frontier = spec.init(g, **kw)
        with TraceAnnotation("repro.solve.run"):
            if checkpoint_every and not engine.supports_stepwise:
                raise ValueError(
                    f"checkpoint_every is supported for flat programs "
                    f"only; {algorithm!r} is phase-structured (its "
                    "epoch/phase loop runs fully jitted)")
            guards = bool(check_finite) or checkpoint_every > 0
            if telemetry is None:
                if guards and engine.supports_stepwise:
                    res = _run_stepwise_resilient(
                        engine, g, init_state, init_frontier,
                        check_finite=check_finite,
                        checkpoint_every=checkpoint_every)
                else:
                    res = engine.run(g, init_state, init_frontier)
                    if check_finite:
                        # phase programs run fully jitted: the guard
                        # still refuses to hand back poisoned state, at
                        # run end
                        PushPullEngine._check_finite(
                            res.state, check_finite, int(res.steps))
            else:
                res = _solve_observed(telemetry, engine, g, init_state,
                                      init_frontier, algorithm=algorithm,
                                      policy=policy, backend=backend,
                                      check_finite=check_finite,
                                      checkpoint_every=checkpoint_every)
        with TraceAnnotation("repro.solve.finalize"):
            return RunResult(state=spec.finalize(g, res.state),
                             cost=res.cost, steps=res.steps,
                             push_steps=res.push_steps,
                             converged=res.converged, epochs=res.epochs,
                             trace=res.trace)


def _run_stepwise_resilient(engine: PushPullEngine, g: Graph,
                            init_state, init_frontier, *, on_step=None,
                            check_finite=None, checkpoint_every: int = 0,
                            max_resumes: int = 4):
    """Stepwise execution with checkpoint-resume: a transient failure
    mid-loop (an injected ``engine.step`` fault, a flaky device) resumes
    from the last snapshot — or restarts, when the failure predates the
    first one; the replayed steps run the identical jitted body, so the
    result is bit-identical to an uninterrupted run. ``max_resumes``
    bounds *consecutive resumes without checkpoint progress*: a
    recoverable fault pattern can interrupt a long solve arbitrarily
    often as long as each resume advances the checkpoint, while a
    permanent failure (no progress between interrupts) re-raises the
    structured :class:`~repro.resilience.SolveInterrupted` after
    ``max_resumes`` stalled attempts (``__cause__`` carries the
    original error)."""
    from .resilience import SolveInterrupted, note
    ckpt = None
    stalled = 0
    while True:
        try:
            return engine.run_stepwise(
                g, init_state, init_frontier, on_step=on_step,
                check_finite=check_finite,
                checkpoint_every=checkpoint_every, resume_from=ckpt)
        except SolveInterrupted as e:
            progressed = e.checkpoint is not None and (
                ckpt is None or e.checkpoint.step > ckpt.step)
            stalled = 0 if progressed else stalled + 1
            if stalled > max_resumes:
                raise
            if e.checkpoint is not None:
                ckpt = e.checkpoint
            note("resume.engine.step", failed_step=e.step,
                 resume_from=(ckpt.step if ckpt is not None else 0),
                 stalled=stalled)


def _solve_observed(tel, engine: PushPullEngine, g: Graph, init_state,
                    init_frontier, *, algorithm: str,
                    policy: DirectionPolicy, backend: ExchangeBackend,
                    check_finite=None, checkpoint_every: int = 0):
    """The telemetry glue behind ``solve(..., telemetry=...)``.

    Runs the engine (stepwise + per-step host timing when the handle
    asks for it and the program is single-phase), then folds the result
    into the handle: step/run events via
    :func:`repro.obs.metrics.record_solve`, the tuner's probe counters,
    the resilience layer's fault/recovery counters and events, and a
    direction-decision ``audit`` event whenever the run produced
    auditable step rows.
    """
    from .obs.metrics import collect_resilience, collect_tuner, record_solve
    from .obs.report import decision_audit

    run = tel.new_run()
    step_times: dict[int, float] = {}
    t0 = tel.now_us()
    guards = bool(check_finite) or checkpoint_every > 0
    with tel.span(f"solve:{algorithm}", run=run, algorithm=algorithm,
                  policy=policy.name, backend=backend.name) as sp:
        if ((tel.step_timing or guards) and engine.supports_stepwise):
            res = _run_stepwise_resilient(
                engine, g, init_state, init_frontier,
                on_step=(lambda i, us: step_times.__setitem__(i, us))
                if tel.step_timing else None,
                check_finite=check_finite,
                checkpoint_every=checkpoint_every)
        else:
            res = engine.run(g, init_state, init_frontier)
            jax.block_until_ready(res.state)  # span times execution
            if check_finite:
                PushPullEngine._check_finite(res.state, check_finite,
                                             int(res.steps))
        sp["steps"] = int(res.steps)
    record_solve(tel, algorithm=algorithm, policy=policy,
                 backend=backend, result=res, run=run,
                 step_times=step_times or None, t0_us=t0)
    collect_tuner(tel)
    collect_resilience(tel)
    audit = decision_audit(tel.events_for(run, "step"), run=run)
    if audit is not None:
        tel.emit("audit", run=run, basis=audit["basis"],
                 audited_steps=audit["audited_steps"],
                 flagged=audit["flagged"],
                 mispredict_rate=audit["mispredict_rate"])
    return res


def solve_batch(g: Graph, algorithm: str, *, sources,
                policy: Optional[DirectionPolicy | str] = None,
                backend: Optional[ExchangeBackend | str] = None,
                max_steps: Optional[int] = None, telemetry=None, **kw):
    """Run one *batched* multi-query solve: B queries of ``algorithm``
    (one per entry of ``sources``) over one shared graph and backend.

    The batch rides as B payload columns through a single engine run —
    one jitted program, one graph scan per pull step, one union-frontier
    scatter per push step — so per-query results are bit-identical to a
    loop of single-source :func:`solve` calls while throughput scales
    with the batch width (see ``docs/architecture.md``, service layer).

    Only source-parameterized algorithms with a registered batched
    program support this path (``repro.service.batchable()``: BFS,
    Δ-stepping SSSP, personalized PageRank).

    Example::

        br = api.solve_batch(g, "bfs", sources=[0, 5, 9])
        br.states[1]["dist"]       # == solve(g, "bfs", root=5)["dist"]
        br.cost.weighted_total()   # whole-batch counter total

    Returns a :class:`repro.service.BatchResult`.

    Raises:
        KeyError: unknown algorithm, or one without a batched program.
        ValueError: empty ``sources``, a source index outside
            ``[0, n)``, or an unsupported (policy × backend) cell.
    """
    from .service.batch import solve_batch as _solve_batch
    return _solve_batch(g, algorithm, sources=sources, policy=policy,
                        backend=backend, max_steps=max_steps,
                        telemetry=telemetry, **kw)


# ---------------------------------------------------------------------
# Built-in registrations: all of the paper's workloads.
register(AlgorithmSpec(
    name="bfs", build=bfs_program, init=bfs_init,
    runtime_keys=("root",), paper="§3.3/§4.3 Alg. 3"))

register(AlgorithmSpec(
    name="pagerank", build=pagerank_program, init=pagerank_init,
    default_policy=Fixed(Direction.PULL), paper="§3.1/§4.1 Alg. 1"))

register(AlgorithmSpec(
    name="wcc", build=wcc_program, init=wcc_init,
    paper="§3.3 (label propagation)"))

register(AlgorithmSpec(
    name="ppr", build=ppr_program, init=ppr_init,
    finalize=ppr_finalize,
    default_policy=Fixed(Direction.PULL),
    runtime_keys=("source",),
    backends=("dense", "ell", "pallas", "shard"),
    paper="§3.1 (personalized variant; service-layer batching)"))

register(AlgorithmSpec(
    name="pr_delta", build=pr_delta_program, init=pr_delta_init,
    finalize=pr_delta_finalize,
    default_policy=Fixed(Direction.PUSH),
    paper="§3.1 (Whang [60])"))

register(AlgorithmSpec(
    name="sssp_delta", build=sssp_delta_program, init=sssp_delta_init,
    finalize=sssp_delta_finalize,
    default_policy=Fixed(Direction.PUSH),
    runtime_keys=("source",),
    backends=("dense", "ell", "pallas", "shard"),
    paper="§3.4/§4.4 Alg. 4"))

register(AlgorithmSpec(
    name="betweenness", build=betweenness_program, init=betweenness_init,
    finalize=betweenness_finalize,
    default_policy=Fixed(Direction.PULL),
    backends=("dense", "ell", "pallas"),
    paper="§3.5/§4.5 Alg. 5"))

register(AlgorithmSpec(
    name="coloring", build=coloring_program, init=coloring_init,
    finalize=coloring_finalize,
    default_policy=Fixed(Direction.PUSH),
    backends=("dense", "ell", "pallas"),
    paper="§3.6/§4.6 Alg. 6"))

register(AlgorithmSpec(
    name="mst_boruvka", build=mst_program, init=mst_init,
    finalize=mst_finalize,
    default_policy=Fixed(Direction.PULL),
    backends=("dense", "ell", "pallas"),
    paper="§3.7/§4.7 Alg. 7"))

register(AlgorithmSpec(
    name="triangle_count", build=triangle_program, init=triangle_init,
    finalize=triangle_finalize,
    default_policy=Fixed(Direction.PULL),
    backends=("dense", "ell", "pallas"),
    paper="§3.2/§4.2 Alg. 2"))
