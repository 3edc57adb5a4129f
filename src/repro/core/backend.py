"""ExchangeBackend — pluggable k-relaxation execution (paper §4, §6, §7).

The paper's thesis is that push and pull are two *implementations* of one
abstract primitive; this module adds the third axis: the same primitive
over different *memory systems*. A backend answers one question — "given
wire values and a frontier, combine messages per destination" — and the
engine/API never care how:

  * ``DenseBackend``       — the dense-frontier segment ops
    (``push_relax`` / ``pull_relax``), shared-memory semantics.
  * ``EllBackend``         — pull in the ELL (padded-row) layout the
    Pallas ``ell_spmv`` kernel tiles; push falls back to the CSC
    (push-major) segment scatter (ELL is a pull-major layout).
  * ``PallasBackend``      — the ELL semantics executed by the actual
    Pallas kernels (``ell_spmv_pallas`` pull, ``coo_push_pallas`` push)
    with autotuned block sizes; cells the kernels do not cover
    (exotic ``msg_fn``, unsupported combine/dtype/payload rank) fall
    back transparently to the jnp primitives, so every registered
    algorithm and policy string still runs.
  * ``DistributedBackend`` — the paper's §6 DM setting: a 1D partition +
    PA edge split; local edges are plain per-owner writes, remote edges
    go through ``dist.collectives`` (combined-alltoall push or
    all_gather pull), with collective bytes charged to the Cost.

``relax`` accepts ``direction`` as a static ``Direction`` or a traced
boolean (True = push) so direction-switching policies can pick per step
inside jitted loops.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..graphs.structure import Graph
from ..resilience import CircuitBreaker, FaultInjected, fault_point, note
from .cost_model import Cost, counter, counter_dtype
from .direction import Direction
from .primitives import (COMBINE_FNS, combine_identity, frontier_in_edges,
                         frontier_out_edges, mask_untouched, pull_relax,
                         pull_relax_ell, push_relax)

__all__ = ["ExchangeBackend", "DenseBackend", "EllBackend",
           "PallasBackend", "DistributedBackend", "require_backend",
           "classify_msg_fn"]


def require_backend(algorithm: str, backend, *allowed) -> None:
    """Raise when ``backend`` is not one of the ``allowed`` classes.

    Algorithm ``build`` hooks call this to reject (policy, backend)
    combinations they have no execution path for; ``api.solve`` converts
    the raise into a ValueError naming the combination.
    """
    if backend is None or isinstance(backend, tuple(allowed)):
        return
    names = ", ".join(c.__name__ for c in allowed)
    raise NotImplementedError(
        f"{algorithm} supports only [{names}] backends, "
        f"not {type(backend).__name__}")


@dataclasses.dataclass(frozen=True)
class ExchangeBackend:
    """Protocol: how one k-relaxation step touches memory.

    Subclasses implement ``push`` (scatter from the frontier, combining
    writes at destinations) and ``pull`` (private gather into touched
    destinations); ``relax`` dispatches between them, including runtime
    (traced-bool) direction switching so direction policies can choose
    per step inside jitted loops. Both must return
    ``(combined_msgs, cost)`` with the §4 counters charged.

        >>> from repro.core import EllBackend
        >>> r = api.solve(g, "pagerank", iters=20,
        ...               backend=EllBackend())      # doctest: +SKIP

    ``pull_scans_all`` tells the cost predictor whether this backend's
    pull reads every edge regardless of the touched destination set
    (true for rectangular layouts like ELL); the engine folds it into
    the :class:`~repro.core.cost_model.StepStats` it hands to switching
    policies.
    """

    # ELL-style layouts gather all m edges even for sparse destination
    # sets; dense/distributed pulls only scan the touched rows. Class
    # attribute, not a field: it is a property of the layout, not of an
    # instance.
    pull_scans_all = False

    def push(self, g: Graph, values: jax.Array, frontier: jax.Array,
             combine: str, msg_fn: Optional[Callable],
             cost: Cost) -> tuple[jax.Array, Cost]:
        raise NotImplementedError

    def pull(self, g: Graph, values: jax.Array,
             touched: Optional[jax.Array], combine: str,
             msg_fn: Optional[Callable],
             cost: Cost) -> tuple[jax.Array, Cost]:
        raise NotImplementedError

    def relax(self, g: Graph, values: jax.Array, frontier: jax.Array, *,
              direction, combine: str = "sum",
              msg_fn: Optional[Callable] = None,
              touched: Optional[jax.Array] = None,
              cost: Cost = Cost()) -> tuple[jax.Array, Cost]:
        def push(v, f, c):
            with jax.named_scope("exchange.push"):
                return self.push(g, v, f, combine, msg_fn, c)

        def pull(v, f, c):
            with jax.named_scope("exchange.pull"):
                return self.pull(g, v, touched, combine, msg_fn, c)

        if isinstance(direction, Direction):
            return (push if direction == Direction.PUSH else pull)(
                values, frontier, cost)
        return jax.lax.cond(direction, push, pull, values, frontier, cost)

    # -- graph-sized views the engine passes in ----------------------------
    def operands(self):
        """Arrays this backend reads inside the engine's compiled loop
        (views built by ``prepare``). The engine passes them as
        arguments of its program: closed over, they would be baked into
        it as constants, which no compiler accepts at real graph sizes.
        The default holds none."""
        return ()

    def bind(self, operands) -> "ExchangeBackend":
        """This backend reading ``operands`` (the engine's traced
        arguments) in place of its own arrays."""
        return self

    # -- cross-step exchange state (sharded/compressed backends) ----------
    def init_exchange_state(self, g: Graph):
        """Initial exchange-carried state for a run on ``g``.

        Backends whose exchange is stateful *across steps* — e.g. the
        sharded push's error-feedback compression accumulator — return a
        pytree here; the engine threads it through the loop carry and
        hands it back to every :meth:`relax_ex` call. The default is an
        empty pytree: stateless, zero carry overhead.
        """
        return ()

    def relax_ex(self, g: Graph, values: jax.Array, frontier: jax.Array,
                 *, direction, combine: str = "sum",
                 msg_fn: Optional[Callable] = None,
                 touched: Optional[jax.Array] = None,
                 cost: Cost = Cost(), xstate=()) -> tuple:
        """``relax`` with exchange-state threading: returns
        ``(combined_msgs, cost, new_xstate)``. The default forwards to
        :meth:`relax` and passes ``xstate`` through unchanged — the
        engine always calls this surface, so stateless backends pay
        nothing while stateful ones override it."""
        out, cost = self.relax(g, values, frontier, direction=direction,
                               combine=combine, msg_fn=msg_fn,
                               touched=touched, cost=cost)
        return out, cost, xstate

    def predict_comm_bytes(self, g: Graph, values, frontier) -> tuple:
        """Predicted inter-device wire bytes of one (push, pull) step.

        The engine folds the pair into ``StepStats.push_wire_bytes`` /
        ``pull_wire_bytes`` so ``AutoSwitch`` prices the §6 comm
        asymmetry; the formulas must match what the backend's own
        ``push``/``pull`` then charge to ``Cost.collective_bytes``
        (keeping the predictor exact for exchange steps). Single-device
        backends move nothing: (0, 0).
        """
        return counter(0), counter(0)

    def predict_pull_scan(self, g: Graph, touched, values=None,
                          combine: str = "sum",
                          msg_fn: Optional[Callable] = None) -> tuple:
        """Predicted ``(edges_read, vertices_written)`` of one pull step
        of this backend, per payload column.

        The engine folds the pair into ``StepStats.pull_edges`` /
        ``pull_vertices``, so this is where a backend's *layout* enters
        the crossover: full-scan layouts (``pull_scans_all``) report all
        ``m`` edges regardless of the touched set, dense pulls the
        touched in-degree sum, and the frontier-aware kernel pull its
        restricted ``touched × d_ell`` gather. Must mirror exactly what
        this backend's ``pull`` then charges (× width) — predictor
        exactness is what the AutoSwitch never-worse guarantees rest on.
        ``values``/``combine``/``msg_fn`` let kernel backends fold their
        trace-time dispatch (kernel vs jnp fallback) into the price.
        """
        if touched is None or self.pull_scans_all:
            return counter(g.m), counter(g.n)
        return (frontier_in_edges(g, touched),
                jnp.sum(touched.astype(counter_dtype())))

    @property
    def name(self) -> str:
        return type(self).__name__

    def telemetry_counters(self) -> dict:
        """Backend-specific counters for :mod:`repro.obs` — dispatch
        tallies, layout geometry, anything the backend accumulates that
        a trace should surface under ``backend.<name>.*``. Values must
        already be totals (the obs registry ``put``s, never re-adds).
        Stateless backends report nothing."""
        return {}


@dataclasses.dataclass(frozen=True)
class DenseBackend(ExchangeBackend):
    """Shared-memory dense-frontier segment ops (the seed primitives)."""

    def push(self, g, values, frontier, combine, msg_fn, cost):
        return push_relax(g, values, frontier, combine=combine,
                          msg_fn=msg_fn, cost=cost)

    def pull(self, g, values, touched, combine, msg_fn, cost):
        return pull_relax(g, values, touched=touched, combine=combine,
                          msg_fn=msg_fn, cost=cost)


@dataclasses.dataclass(frozen=True)
class EllBackend(ExchangeBackend):
    """Pull in the ELL layout (rectangular VMEM tiles — what the
    ``ell_spmv`` Pallas kernel consumes); push falls back to the CSC
    (push-major) segment scatter."""

    pull_scans_all = True

    def push(self, g, values, frontier, combine, msg_fn, cost):
        return push_relax(g, values, frontier, combine=combine,
                          msg_fn=msg_fn, cost=cost)

    def pull(self, g, values, touched, combine, msg_fn, cost):
        out, cost = pull_relax_ell(g, values, combine=combine,
                                   msg_fn=msg_fn, cost=cost)
        if touched is not None:
            out = mask_untouched(out, touched, combine)
        return out, cost


# -- Pallas kernel dispatch --------------------------------------------
# msg_fn classification: the kernels implement the three wire-message
# shapes every registered algorithm uses. A msg_fn is classified by
# probing it on concrete values (msg_fns are pure elementwise jnp
# lambdas, so the probe runs eagerly even while an outer jit trace is
# being built) and matching the result against the candidate modes. The
# probe mixes signs, zero, and large magnitudes so functions that only
# coincide with a mode on tame inputs (clipping/saturation, piecewise
# definitions) are rejected rather than silently mis-dispatched.
_MSG_PROBE_X = (0.5, -1.25, 2.0, 0.0, 3e6, -7e5, 1e-4, 64.0)
_MSG_PROBE_W = (1.5, 0.25, -3.0, 2.0, -2e6, 4e5, 5e3, -0.125)
_MSG_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def classify_msg_fn(msg_fn: Optional[Callable]) -> Optional[str]:
    """Kernel message mode for ``msg_fn``: ``"copy"`` (msg = value,
    the primitives' ``msg_fn=None`` convention), ``"mul"`` (value ×
    weight — SpMV), ``"add"`` (value + weight — the min-plus
    relaxation), or None when the function matches none of them (the
    caller falls back to the jnp primitives)."""
    if msg_fn is None:
        return "copy"
    try:
        return _MSG_CACHE[msg_fn]
    except (KeyError, TypeError):
        pass
    mode = None
    try:
        # escape any ambient jit trace: the probe must execute eagerly
        # even while the engine's loop is being traced
        with jax.ensure_compile_time_eval():
            x = jnp.asarray(_MSG_PROBE_X, jnp.float32)
            w = jnp.asarray(_MSG_PROBE_W, jnp.float32)
            got = np.asarray(msg_fn(x, w))
            cands = (("copy", x), ("mul", x * w), ("add", x + w))
            for cand, want in cands:
                if got.shape == x.shape and np.allclose(
                        got, np.asarray(want), rtol=1e-6, atol=1e-6):
                    mode = cand
                    break
    except Exception:      # arbitrary callables may reject the probe
        mode = None
    try:
        _MSG_CACHE[msg_fn] = mode
    except TypeError:      # non-weakrefable callables skip the cache
        pass
    return mode


_PALLAS_DTYPES = ("float32", "float64", "int32", "int64")


@dataclasses.dataclass(frozen=True, eq=False)
class PallasBackend(EllBackend):
    """The ELL backend's semantics executed by the Pallas kernels.

    ``pull`` is frontier-aware: with a touched destination set it
    compacts the set to row ids and dispatches to
    ``ell_pull_frontier_pallas`` (gather/reduce of *touched rows only*
    over the dual layout's ELL-in side — ``touched × d_ell`` work),
    falling back to the full-scan ``ell_spmv_pallas`` when the set is
    too dense for the restriction to pay (or, in-trace, overflows the
    static row capacity — ``pull_frontier_cap``, default
    ``default_pull_cap``); an empty touched set returns the combine
    identity without launching any kernel. ``push`` dispatches to
    ``coo_push_pallas`` (two-phase contention-free bin reduce over a
    per-graph bin layout). Both directions therefore run on their
    native rectangular layout, held by a per-graph ``DualEllLayout``
    (ELL-in + ELL-out) cached here alongside the bin plans — and
    ``pull_scans_all`` is **False**: ``predict_pull_scan`` prices pull
    steps by the restricted gather the kernel will actually do, which
    moves AutoSwitch's predicted push/pull crossover pull-ward.

    Block sizes and the push reduce strategy come from
    ``kernels/tune.py`` — probed once per (graph shape, payload shape,
    platform; the frontier pull additionally keys on the compacted row
    capacity), cached on this instance and on disk — unless pinned via
    ``block_n``/``block_e``/``push_block_n``/``push_strategy``.
    ``interpret=None`` auto-detects (compiled on TPU, interpreter
    elsewhere).

    Cells outside the kernels' coverage — a ``msg_fn`` that is not one
    of the three wire-message shapes, a combine outside {sum, max, min},
    payload rank > 2, a dtype outside float32/float64/int32/int64, or a
    64-bit payload on a compiled (TPU) kernel — fall back to the jnp
    primitives (``EllBackend``'s paths), charging identical costs and
    counting ``fallback_*``, so every (algorithm × policy) cell keeps
    running. A kernel that *fails* is not served from jnp: the error
    propagates, naming the kernel. Only an injected fault
    (``repro.resilience``) takes the degradation ladder.

        >>> r = api.solve(g, "bfs", root=0, backend="pallas")  # doctest: +SKIP

    ``stats`` counts trace-time dispatch decisions (kernel vs fallback,
    per direction) — observability for tests and benchmarks — plus
    ``fallback_push_overflow``, counted at run time each time a traced
    push's bin capacity overflows and the jnp branch serves the step.
    """
    # the ELL-in gather no longer scans all edges when a touched set is
    # given: the frontier kernel restricts it, and predict_pull_scan
    # prices the restriction
    pull_scans_all = False

    interpret: Optional[bool] = None
    block_n: Optional[int] = None     # pull tile rows (None = autotune)
    block_e: Optional[int] = None     # push edge-chunk size
    push_block_n: Optional[int] = None  # push destination-bin width
    push_strategy: Optional[str] = None  # phase-2 reduce ("scan"|"mxu")
    push_bin_cap: Optional[int] = None  # traced-bin capacity override
    pull_frontier_cap: Optional[int] = None  # traced touched-row capacity
    autotune: bool = True
    stats: dict = dataclasses.field(
        default_factory=lambda: {"kernel_pull": 0, "kernel_push": 0,
                                 "kernel_pull_frontier": 0,
                                 "skip_empty_pull": 0,
                                 "fallback_pull": 0, "fallback_push": 0,
                                 "fallback_push_overflow": 0,
                                 "fault_fallback_pull": 0,
                                 "fault_fallback_push": 0,
                                 "breaker_skip_pull": 0,
                                 "breaker_skip_push": 0,
                                 "breaker_open": 0})
    # the degradation ladder's middle rung: a (kernel, shape) cell that
    # keeps *failing* at dispatch (not merely unsupported) opens here
    # and skips straight to the jnp fallback for a call-counted
    # cooldown — see repro.resilience.breaker
    breaker: CircuitBreaker = dataclasses.field(
        default_factory=CircuitBreaker, repr=False)
    _tuned: dict = dataclasses.field(default_factory=dict, repr=False)
    _plans: dict = dataclasses.field(default_factory=dict, repr=False)
    _layouts: dict = dataclasses.field(default_factory=dict, repr=False)

    # identity eq/hash, explicitly: instances carry mutable caches and
    # distinct block/interpret configs, and the engine cache keys on the
    # backend. eq=False alone would inherit EllBackend's *value*-based
    # __eq__/__hash__ (which see no PallasBackend fields), making every
    # instance compare equal and collide in the cache.
    __hash__ = object.__hash__

    def __eq__(self, other):
        return self is other

    # -- dispatch help -----------------------------------------------------
    def _compiled(self) -> bool:
        from ..kernels.ell_spmv import default_interpret
        return not (default_interpret() if self.interpret is None
                    else self.interpret)

    def _mode(self, values, combine, msg_fn) -> Optional[str]:
        from ..kernels.ell_spmv import compiled_dtype_ok
        if combine not in ("sum", "max", "min"):
            return None
        if values.ndim not in (1, 2):
            return None
        if str(values.dtype) not in _PALLAS_DTYPES:
            return None
        if self._compiled() and not compiled_dtype_ok(values.dtype):
            return None
        return classify_msg_fn(msg_fn)

    def _pull_block_n(self, g: Graph, values, combine, mode) -> int:
        if self.block_n is not None:
            return self.block_n
        from ..kernels.tune import pull_candidates, tune_pull
        width = 1 if values.ndim == 1 else int(values.shape[-1])
        key = ("pull", g.n, g.d_ell, width, str(values.dtype), combine,
               mode)
        if key not in self._tuned:
            self._tuned[key] = (
                tune_pull(g.n, g.d_ell, width, values.dtype, combine,
                          mode, self.interpret)
                if self.autotune else pull_candidates(
                    g.n, width, d_ell=g.d_ell,
                    compiled=self._compiled())[0])
        return self._tuned[key]

    def _push_blocks(self, g: Graph, values, combine,
                     mode) -> tuple[int, int, str]:
        if (self.block_e is not None and self.push_block_n is not None
                and self.push_strategy is not None):
            return self.block_e, self.push_block_n, self.push_strategy
        from ..kernels.tune import push_candidates, tune_push
        width = 1 if values.ndim == 1 else int(values.shape[-1])
        key = ("push", g.n, g.m, width, str(values.dtype), combine, mode)
        if key not in self._tuned:
            self._tuned[key] = (
                tune_push(g.n, g.m, width, values.dtype, combine, mode,
                          self.interpret)
                if self.autotune else push_candidates(
                    g.n, g.m, width=width, compiled=self._compiled())[0])
        be, bn, strat = self._tuned[key]
        # partial pins override only their own component
        if self.block_e is not None:
            be = self.block_e
        if self.push_block_n is not None:
            bn = self.push_block_n
        if self.push_strategy is not None:
            strat = self.push_strategy
        return be, bn, strat

    def _push_plan(self, g: Graph, block_n: int, block_e: int):
        """Cached phase-1 bin layout for a concrete graph — built once
        per (graph, bin width, edge block) via the host regroup and
        stored alongside the tuner results. Keys carry a weakref so an
        id() reused by a new Graph cannot resurrect a stale plan."""
        from ..kernels.coo_push import build_push_plan
        key = (id(g), block_n, block_e)
        hit = self._plans.get(key)
        if hit is not None and hit[0]() is g:
            return hit[1]
        plan = build_push_plan(g.coo_src, g.coo_dst, g.coo_w, g.n,
                               block_n, align=block_e)
        self._plans[key] = (weakref.ref(g), plan)
        return plan

    def dual_layout(self, g: Graph):
        """Cached dual ELL-in/ELL-out layout for a concrete graph —
        built once per graph on the host (the in side shares the
        graph's own ELL arrays) and stored next to the bin plans, with
        the same weakref guard against id() reuse. Traced graphs use
        ``g.ell_idx``/``g.ell_w`` directly (the layout's in side *is*
        those arrays)."""
        from ..kernels.layout import build_dual_ell
        key = ("dual", id(g))
        hit = self._layouts.get(key)
        if hit is not None and hit[0]() is g:
            return hit[1]
        layout = build_dual_ell(g)
        self._layouts[key] = (weakref.ref(g), layout)
        return layout

    def _pull_cap(self, g: Graph) -> int:
        if self.pull_frontier_cap is not None:
            return self.pull_frontier_cap
        from ..kernels.ell_pull_frontier import default_pull_cap
        return default_pull_cap(g.n, g.m, g.d_ell)

    def _pull_frontier_block(self, g: Graph, rows: int, values, combine,
                             mode) -> int:
        from ..kernels.tune import (pull_frontier_candidates,
                                    tune_pull_frontier)
        width = 1 if values.ndim == 1 else int(values.shape[-1])
        key = ("pullf", g.n, g.d_ell, rows, width, str(values.dtype),
               combine, mode)
        if key not in self._tuned:
            self._tuned[key] = (
                tune_pull_frontier(g.n, g.d_ell, rows, width,
                                   values.dtype, combine, mode,
                                   self.interpret)
                if self.autotune
                else pull_frontier_candidates(
                    g.n, rows, width=width, d_ell=g.d_ell,
                    compiled=self._compiled())[0])
        return self._tuned[key]

    def _pull_scan_stats(self, g: Graph, touched):
        """(edges_read, rows_written, count, fits) of a kernel pull
        with this touched set — the single formula behind both
        ``predict_pull_scan`` and the charge ``pull`` makes, so the
        predictor stays exact. The restriction pays only when the
        touched rows fit the static capacity AND their rectangular
        gather (``count × d_ell``) undercuts the full scan's ``m`` —
        otherwise the full-scan price (m, n) applies, which is exactly
        the old ``pull_scans_all`` pricing (a 100%-touched frontier can
        never be priced worse than before)."""
        cnt = jnp.sum(touched.astype(counter_dtype()))
        cap = self._pull_cap(g)
        fits = (cnt > 0) & (cnt <= cap) & (cnt * g.d_ell < g.m)
        edges = jnp.where(cnt == 0, counter(0),
                          jnp.where(fits, cnt * g.d_ell, counter(g.m)))
        verts = jnp.where(cnt == 0, counter(0),
                          jnp.where(fits, cnt, counter(g.n)))
        return edges, verts, cnt, fits

    def predict_pull_scan(self, g, touched, values=None, combine="sum",
                          msg_fn=None):
        # a step the kernels cannot cover falls back to the full-scan
        # jnp ELL path, so it must be priced as one
        if (touched is None or values is None
                or self._mode(values, combine, msg_fn) is None):
            return counter(g.m), counter(g.n)
        edges, verts, _, _ = self._pull_scan_stats(g, touched)
        return edges, verts

    def telemetry_counters(self) -> dict:
        out = dict(self.stats)
        out.update({f"breaker_{k}": v
                    for k, v in self.breaker.stats().items()})
        return out

    def _kernel_failed(self, cell, direction: str, exc) -> None:
        """One rung down the ladder after an injected fault: count it,
        inform the breaker, surface a resilience event. The caller then
        serves this call from the jnp fallback."""
        self.stats[f"fault_fallback_{direction}"] += 1
        opened = self.breaker.record_failure(cell)
        note(f"fallback.pallas.{direction}",
             error=type(exc).__name__, cell=str(cell))
        if opened:
            self.stats["breaker_open"] += 1
            note("breaker.open", cell=str(cell),
                 cooldown=self.breaker.cooldown)

    # -- ExchangeBackend ---------------------------------------------------
    def pull(self, g, values, touched, combine, msg_fn, cost):
        mode = self._mode(values, combine, msg_fn)
        if mode is None:
            self.stats["fallback_pull"] += 1
            return super().pull(g, values, touched, combine, msg_fn, cost)
        width = 1 if values.ndim == 1 else int(values.shape[-1])
        cell = ("pull", g.n, g.d_ell, width, str(values.dtype), combine,
                mode)
        if not self.breaker.allow(cell):
            self.stats["breaker_skip_pull"] += 1
            return super().pull(g, values, touched, combine, msg_fn, cost)
        try:
            fault_point("pallas.pull")
            out = self._pull_kernel(g, values, touched, combine, mode,
                                    cost)
        except FaultInjected as exc:
            # injected dispatch fault: degrade to the jnp path
            # (identical semantics, full-scan pricing)
            self._kernel_failed(cell, "pull", exc)
            return super().pull(g, values, touched, combine, msg_fn, cost)
        self.breaker.record_success(cell)
        return out

    def _pull_kernel(self, g, values, touched, combine, mode, cost):
        from ..graphs.structure import pad_values
        from ..kernels.ell_spmv import _out_dtype, ell_spmv_pallas
        width = 1 if values.ndim == 1 else values.shape[-1]

        def full_scan():
            out = ell_spmv_pallas(
                pad_values(values), g.ell_idx, g.ell_w, combine=combine,
                msg=mode,
                block_n=self._pull_block_n(g, values, combine, mode),
                interpret=self.interpret)
            if touched is not None:
                out = mask_untouched(out, touched, combine)
            return out

        if touched is None:
            # every destination is live: the rectangular full scan is
            # the native path (identical charge to pull_relax_ell)
            self.stats["kernel_pull"] += 1
            return full_scan(), cost.charge(reads=counter(g.m) * width,
                                            writes=counter(g.n) * width)

        from ..kernels.ell_pull_frontier import (ell_pull_frontier_full,
                                                 frontier_rows)
        edges, verts, cnt, fits = self._pull_scan_stats(g, touched)
        odt = _out_dtype(values.dtype, g.ell_w.dtype, mode, combine)
        ident = combine_identity(combine, odt)

        def identity_out():
            return jnp.full((g.n,) + values.shape[1:], ident, odt)

        def frontier(rows, in_idx, in_w, block_r):
            return ell_pull_frontier_full(
                pad_values(values), in_idx, in_w, rows, combine=combine,
                msg=mode, block_r=block_r, interpret=self.interpret)

        if not isinstance(touched, jax.core.Tracer) and not isinstance(
                g.ell_idx, jax.core.Tracer):
            # concrete call (direct use, benchmarks): dispatch eagerly.
            # The compaction is sized to the actual touched count,
            # rounded to a power of two so the kernel's jit cache and
            # the tuner see a bounded family of row capacities.
            cnt_c = int(cnt)
            if cnt_c == 0:
                self.stats["skip_empty_pull"] += 1
                out = identity_out()
            elif bool(fits):
                self.stats["kernel_pull_frontier"] += 1
                layout = self.dual_layout(g)
                rows_n = max(8, 1 << (cnt_c - 1).bit_length())
                block_r = self._pull_frontier_block(g, rows_n, values,
                                                    combine, mode)
                out = frontier(frontier_rows(touched, rows_n),
                               layout.in_idx, layout.in_w, block_r)
            else:
                self.stats["kernel_pull"] += 1
                out = full_scan()
        else:
            # in-trace (the engine jits the graph): compact under the
            # static capacity and guard on the runtime fits bit —
            # mirroring the push bin plan's lax.cond capacity guard.
            # An empty touched set short-circuits to the identity
            # without any kernel launch.
            self.stats["kernel_pull_frontier"] += 1
            cap = self._pull_cap(g)
            block_r = self._pull_frontier_block(g, cap, values, combine,
                                                mode)
            rows = frontier_rows(touched, cap)
            out = jax.lax.cond(
                cnt == 0, identity_out,
                lambda: jax.lax.cond(
                    fits,
                    lambda: frontier(rows, g.ell_idx, g.ell_w, block_r),
                    full_scan))
        # exactly what predict_pull_scan promised (× payload width)
        return out, cost.charge(reads=edges * width,
                                writes=verts * width)

    def push(self, g, values, frontier, combine, msg_fn, cost):
        mode = self._mode(values, combine, msg_fn)
        if mode is None:
            self.stats["fallback_push"] += 1
            return super().push(g, values, frontier, combine, msg_fn,
                                cost)
        width = 1 if values.ndim == 1 else int(values.shape[-1])
        cell = ("push", g.n, g.m, width, str(values.dtype), combine,
                mode)
        if not self.breaker.allow(cell):
            self.stats["breaker_skip_push"] += 1
            return super().push(g, values, frontier, combine, msg_fn,
                                cost)
        try:
            fault_point("pallas.push")
            out = self._push_kernel(g, values, frontier, combine, mode,
                                    cost)
        except FaultInjected as exc:
            self._kernel_failed(cell, "push", exc)
            return super().push(g, values, frontier, combine, msg_fn,
                                cost)
        self.breaker.record_success(cell)
        return out

    def _count_overflow(self) -> None:
        self.stats["fallback_push_overflow"] += 1

    def _push_kernel(self, g, values, frontier, combine, mode, cost):
        from ..kernels.coo_push import (bin_plan_traced, coo_push_pallas,
                                        default_bin_cap)
        self.stats["kernel_push"] += 1
        block_e, block_n, strategy = self._push_blocks(g, values,
                                                       combine, mode)

        def kernel(v, f, plan):
            return coo_push_pallas(
                v, f, g.coo_src, g.coo_dst, g.coo_w, g.n, combine=combine,
                msg=mode, block_e=block_e, block_n=block_n,
                interpret=self.interpret, plan=plan, strategy=strategy)

        if not isinstance(g.coo_src, jax.core.Tracer):
            # concrete graph (direct calls, benchmarks): the host
            # regroup builds the exact bin layout once; cached per
            # (graph, bin width, edge block) next to the tuner results
            out = kernel(values, frontier,
                         self._push_plan(g, block_n, block_e))
        else:
            # the engine jits the graph: bin in-trace from in_ptr (one
            # gather, no scatter) under a static capacity, guarded by
            # the plan's fits bit. The guard is traced per step on
            # purpose: engines are cached per graph *shape* — deciding
            # eagerly per concrete graph would bake one graph's answer
            # into an engine other same-shape graphs reuse.
            cap = (self.push_bin_cap
                   or default_bin_cap(g.n, g.m, g.d_ell, block_n,
                                      block_e))
            plan, fits = bin_plan_traced(
                g.coo_src, g.coo_dst, g.coo_w, g.in_ptr, g.n, block_n,
                cap=cap, align=block_e)

            def overflow(v, f):
                jax.debug.callback(self._count_overflow)
                return _coo_push_jnp(g, v, f, combine, mode)

            out = jax.lax.cond(fits, lambda v, f: kernel(v, f, plan),
                               overflow, values, frontier)
        k = frontier_out_edges(g, frontier)
        width = 1 if values.ndim == 1 else values.shape[-1]
        # the phase-1 binning pass reads and rewrites every edge once
        # (frontier-independent: the layout covers the whole edge list)
        cost = cost.charge(reads=counter(g.m), writes=counter(g.m))
        cost = cost.charge(reads=k * width).charge_combining_writes(
            k * width,
            float_data=jnp.issubdtype(values.dtype, jnp.floating))
        return out, cost


def _coo_push_jnp(g: Graph, values, frontier, combine: str, mode: str):
    """Segment-op push over the *dst-sorted* edge order — the runtime
    fallback branch when the traced binning pass's static capacity
    cannot hold the skewest bin (same combine, same order, so the two
    branches agree)."""
    x = jnp.take(values, g.coo_src, axis=0, mode="fill", fill_value=0)
    if mode == "mul":
        w = g.coo_w
        msgs = x * (w[:, None] if x.ndim == 2 else w)
    elif mode == "add":
        w = g.coo_w
        msgs = x + (w[:, None] if x.ndim == 2 else w)
    else:
        msgs = x
    active_e = jnp.take(frontier, g.coo_src, axis=0, mode="fill",
                        fill_value=False)
    if msgs.ndim == 2:
        active_e = active_e[:, None]
    msgs = jnp.where(active_e, msgs, combine_identity(combine, msgs.dtype))
    return COMBINE_FNS[combine](msgs, g.coo_dst, g.n)


@dataclasses.dataclass(frozen=True, eq=False)
class DistributedBackend(ExchangeBackend):
    """DM k-relaxation over a 1D partition + PA split (paper §6).

    Local edges (both endpoints owned) are plain segment writes; only the
    cut crosses shards, by the combined-alltoall push or the all_gather
    pull. Build with :meth:`prepare`; the instance is graph-specific.

    Restriction: messages must be a function of the *wire value only*
    (``msg_fn(v, w)`` with masked sources carrying the combine identity),
    which holds for every algorithm in ``repro.api``.
    """
    mesh: object = None
    part: object = None
    local: object = None          # edges grouped by owner (src==dst owner)
    remote_by_src: object = None  # cut edges grouped by src owner (push)
    remote_by_dst: object = None  # cut edges grouped by dst owner (pull)
    cut_edges: int = 0
    axis: str = "data"

    # identity hash/eq, explicitly (eq=False would inherit the parent
    # dataclass's value-based comparison, which sees none of this
    # class's fields — two backends prepared for different same-shape
    # graphs would collide in the engine cache): instances hold jnp
    # arrays, and jit static-arg hashing only needs per-instance
    # identity.
    __hash__ = object.__hash__

    def __eq__(self, other):
        return self is other

    @classmethod
    def prepare(cls, g: Graph, mesh=None, num_parts: Optional[int] = None,
                axis: str = "data") -> "DistributedBackend":
        from ..graphs.partition import (pa_regroup_by_dst, pa_split,
                                        partition_1d)
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()).reshape(-1, 1),
                        (axis, "model"))
        else:
            # Auto axes: the exchanges slice the sharded [n_padded]
            # result down to [n], which Explicit axes reject whenever n
            # is not a multiple of the shard count
            mesh = Mesh(mesh.devices, mesh.axis_names)
        if num_parts is None:
            num_parts = mesh.shape[axis]
        if num_parts != mesh.shape[axis]:
            raise ValueError(
                f"num_parts={num_parts} must equal the mesh '{axis}' axis "
                f"size ({mesh.shape[axis]}): the exchanges map partitions "
                "to mesh shards 1:1.")
        part = partition_1d(g.n, num_parts)
        local, remote_src, stats = pa_split(g, part)
        # only the cut needs the pull grouping; the local set and stats
        # are grouping-independent (local edges share one owner)
        remote_dst = pa_regroup_by_dst(part, remote_src, g.n)
        return cls(mesh=mesh, part=part, local=local,
                   remote_by_src=remote_src, remote_by_dst=remote_dst,
                   cut_edges=int(stats["cut_edges"]), axis=axis)

    # -- helpers -----------------------------------------------------------
    def _pad(self, values: jax.Array, fill) -> jax.Array:
        extra = max(0, self.part.n_padded - values.shape[0])
        widths = ((0, extra),) + ((0, 0),) * (values.ndim - 1)
        return jnp.pad(values, widths, constant_values=fill)

    def _wire_msg_fn(self, msg_fn):
        # primitives treat msg_fn=None as "value, unweighted"; collectives
        # default to value*weight — normalize to the primitive convention.
        return msg_fn if msg_fn is not None else (lambda v, w: v)

    # -- ExchangeBackend ---------------------------------------------------
    def push(self, g, values, frontier, combine, msg_fn, cost):
        from ..dist.collectives import pa_exchange
        ident = combine_identity(combine, values.dtype)
        vpad = self._pad(jnp.where(frontier, values, ident), ident)
        out, nbytes = pa_exchange(
            self.mesh, self.part, self.local, self.remote_by_src, vpad,
            direction="push", msg_fn=self._wire_msg_fn(msg_fn),
            combine=combine, axis=self.axis)
        k = frontier_out_edges(g, frontier)
        cost = cost.charge(reads=k).charge_combining_writes(
            jnp.minimum(k, self.cut_edges),
            float_data=jnp.issubdtype(values.dtype, jnp.floating))
        cost = cost.charge(messages=jnp.minimum(k, self.cut_edges),
                           collective_bytes=nbytes * self.part.num_parts)
        return out[:g.n], cost

    def pull(self, g, values, touched, combine, msg_fn, cost):
        from ..dist.collectives import pa_exchange
        ident = combine_identity(combine, values.dtype)
        vpad = self._pad(values, ident)
        out, nbytes = pa_exchange(
            self.mesh, self.part, self.local, self.remote_by_dst, vpad,
            direction="pull", msg_fn=self._wire_msg_fn(msg_fn),
            combine=combine, axis=self.axis)
        out = out[:g.n]
        if touched is not None:
            out = mask_untouched(out, touched, combine)
            k = frontier_in_edges(g, touched)
            wr = jnp.sum(touched.astype(counter_dtype()))
        else:
            k = counter(g.m)
            wr = counter(g.n)
        cost = cost.charge(reads=k, writes=wr,
                           collective_bytes=nbytes * self.part.num_parts)
        return out, cost

    def predict_comm_bytes(self, g, values, frontier):
        # mirror exactly what push/pull charge: the combined-alltoall
        # moves n_padded·itemsize per device, the all_gather
        # n_padded·itemsize·(P-1)/P — both scaled by P devices
        Pn = self.part.num_parts
        npad = self.part.n_padded
        item = values.dtype.itemsize
        push_b = counter(npad * item) * Pn
        pull_b = counter(npad * item * (Pn - 1) // max(Pn, 1)) * Pn
        return push_b, pull_b
