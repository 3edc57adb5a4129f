"""k-relaxation / k-filter as JAX primitives (paper §4 'Cost Derivations').

The paper reduces every algorithm to two primitives:

  * **k-relaxation** — propagate updates along k edges. Push: from the k
    active sources to their neighbors (combining writes). Pull: into each
    destination from its neighbors (private accumulation).
  * **k-filter** — compact the set of updated vertices (only needed when
    pushing; pulling inspects every vertex anyway).

Here both directions are dense-frontier JAX ops with identical *results*
and different *memory-access structure*; each returns (value, Cost) where
the Cost charges exactly what the paper's Table 1 counts:

  push: reads = Σ out_deg(frontier); combining writes = same (atomics for
        int payloads, locks for float payloads — CPUs lack float atomics).
  pull: reads = Σ in_deg(touched dst) (all m when dst set is dense);
        writes = |touched dst|, zero atomics/locks.

Push without ``msg_fn`` masks the wire table once per step, O(n): an
edge's message ``where(frontier[src], values[src], ident)`` is the same
selection as ``where(frontier, values, ident)[src]``, bit for bit, so one
gather of the masked table replaces the per-edge frontier gather. With a
``msg_fn`` the two differ (``msg_fn(ident, w)`` need not be the identity:
SSSP's ``x + w`` overflows int32), so that path masks per edge.

A min/max push of 32-bit payloads without ``msg_fn`` touches only the
frontier's out-edges while they fit one of a few static capacities
derived from m (:func:`push_caps`): shapes stay static, and the smallest
capacity that holds the step's frontier edges runs. Larger frontiers,
sums (whose float rounding follows the scatter's order), other payload
widths and ``msg_fn`` pushes take the all-edge path. Either way the Cost model charges the *algorithmic* counts,
which is what the roofline's collective term consumes.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.structure import Graph
from ..sparse.segment import segment_max, segment_min, segment_sum
from .cost_model import Cost, counter, counter_dtype

__all__ = [
    "push_relax", "pull_relax", "pull_relax_ell", "k_filter",
    "frontier_out_edges", "frontier_in_edges", "COMBINE_FNS",
    "combine_identity", "mask_untouched",
]

COMBINE_FNS = {
    "sum": segment_sum,
    "max": segment_max,
    "min": segment_min,
}


def _identity_scalar(combine: str, dtype):
    """:func:`combine_identity` as a static numpy scalar (for ``fill_value``)."""
    if combine == "sum":
        val = 0
    elif jnp.issubdtype(dtype, jnp.floating):
        val = np.inf if combine == "min" else -np.inf
    else:
        info = jnp.iinfo(dtype)
        val = info.max if combine == "min" else info.min
    return np.dtype(dtype).type(val)


def combine_identity(combine: str, dtype) -> jax.Array:
    """Reduce identity: what an edge contributes when masked out, and what
    an empty segment holds after the reduce (callers test against it)."""
    return jnp.asarray(_identity_scalar(combine, dtype), dtype)


def mask_untouched(out: jax.Array, touched: jax.Array,
                   combine: str) -> jax.Array:
    """Set untouched destinations to the reduce identity (= 'no update');
    broadcasts a bool[n] mask over [n] or [n, d] outputs."""
    tb = touched.reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(tb, out, combine_identity(combine, out.dtype))


def frontier_out_edges(g: Graph, frontier: jax.Array) -> jax.Array:
    """Counter-typed count of frontier-incident out-edges = push work."""
    return jnp.sum(jnp.where(frontier, g.out_deg, 0).astype(counter_dtype()))


def frontier_in_edges(g: Graph, touched: jax.Array) -> jax.Array:
    """Counter-typed count of in-edges of touched destinations = pull work."""
    return jnp.sum(jnp.where(touched, g.in_deg, 0).astype(counter_dtype()))


def _edge_messages(values: jax.Array, src: jax.Array, w: jax.Array,
                   msg_fn: Optional[Callable]) -> jax.Array:
    """Per-edge message = msg_fn(value[src], w); default value*1."""
    x = jnp.take(values, src, axis=0, mode="fill", fill_value=0)
    if msg_fn is None:
        return x
    return msg_fn(x, w)


def push_caps(m: int) -> tuple[int, ...]:
    """Edge capacities of the frontier push on a graph of ``m`` edges,
    ascending: the largest power of two up to m/8, and every fourth of it
    down to 2**10 (none below 8,192 edges). Each is one branch of the
    compiled push; at no more than m/8 slots, the largest one's tables
    stay under the all-edge push's per-edge message table."""
    top = (m // 8).bit_length() - 1
    return tuple(1 << e for e in range(10 + (top - 10) % 2, top + 1, 2))


def _cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of an ``int32[L]``, modulo 2**32, on the
    matrix unit: each 128-lane row's prefix is its product with an upper
    triangle of ones, taken byte by byte in bfloat16 so that every
    operand and float32 partial sum is an exact integer; each row is then
    offset by the prefix of the row totals. (``jnp.cumsum`` lowers to a
    reduce-window that takes the TPU compiler seconds at 2**21.)"""
    size = x.shape[0]
    rows = jnp.pad(x, (0, -size % 128)).reshape(-1, 128)
    upper = jnp.triu(jnp.ones((128, 128), jnp.bfloat16))
    within = sum(
        jnp.dot(((rows >> s) & 255).astype(jnp.bfloat16), upper,
                preferred_element_type=jnp.float32).astype(jnp.int32) << s
        for s in (0, 8, 16, 24))
    if within.shape[0] > 1:
        total = within[:, -1]
        within = within + (_cumsum(total) - total)[:, None]
    return within.reshape(-1)[:size]


def _frontier_push(g: Graph, values: jax.Array, active: jax.Array,
                   rank: jax.Array, combine: str, cap: int) -> jax.Array:
    """Min/max push of 32-bit payloads over the frontier's out-edges
    alone, which must number at most ``cap``.

    The ``active`` vertices (frontier vertices with edges; ``rank`` is
    their running count) are compacted in vertex order, and their
    push-major ranges ``out_ptr[v]:out_ptr[v+1]`` laid end to end in
    ``cap`` edge slots. What is constant over a vertex's slots, its edge
    offset and its payload's bits, is written once, at the range's first
    slot, as the difference from the previous vertex's, and a prefix sum
    modulo 2**32 fills the range: no per-slot gather but the
    destinations'. The messages reach the combine in the all-edge push's
    edge order, so the result is the same bit for bit. Scatter indices
    are sorted, which the TPU compiler needs to build a large scatter
    quickly.
    """
    n, i32 = g.n, jnp.int32
    kv = min(cap, n)
    with jax.named_scope("push.frontier"):
        if kv * n.bit_length() <= n // 2:
            # a binary search per row beats a scatter of all n vertices
            verts = jnp.searchsorted(rank, jnp.arange(1, kv + 1, dtype=i32),
                                     side="left").astype(i32)
        else:
            # the r-th active vertex lands in row r of kv + 1 rows; the
            # vertices after it send n, which the min absorbs
            verts = jnp.full((kv + 1,), n, i32).at[rank].min(
                jnp.where(active, jnp.arange(n, dtype=i32), n),
                mode="drop", indices_are_sorted=True)[1:]
        deg = g.out_deg.at[verts].get(mode="fill", fill_value=0)
        ends = _cumsum(deg)
        begins = ends - deg
        at = jnp.where(deg > 0, begins, cap)

        def fill(per_vertex):
            """``per_vertex[i]`` in every slot of vertex i's range."""
            step = per_vertex - jnp.pad(per_vertex[:-1], (1, 0))
            return _cumsum(jnp.zeros((cap,), i32).at[at].set(
                step, mode="drop", indices_are_sorted=True))
        j = jnp.arange(cap, dtype=i32)
        slot = j + fill(g.out_ptr[verts] - begins)
        dst = jnp.where(j < ends[-1], g.push_dst.at[slot].get(
            mode="fill", fill_value=n, indices_are_sorted=True), n)
        words = jax.lax.bitcast_convert_type(
            values.at[verts].get(mode="fill", fill_value=0), i32)
        msgs = jnp.stack([fill(w) for w in words.reshape(kv, -1).T], axis=1)
        msgs = jax.lax.bitcast_convert_type(
            msgs.reshape((cap,) + values.shape[1:]), values.dtype)
        return COMBINE_FNS[combine](msgs, dst, n)


def _capped_push(g: Graph, k: jax.Array, values: jax.Array,
                 frontier: jax.Array, combine: str,
                 caps: tuple[int, ...]) -> jax.Array:
    """The frontier push in the smallest of ``caps`` that holds the ``k``
    frontier edges, after counting the frontier's vertices with edges."""
    with jax.named_scope("push.frontier"):
        active = frontier & (g.out_deg > 0)
        rank = _cumsum(active.astype(jnp.int32))
    branches = [partial(_frontier_push, g, combine=combine, cap=c)
                for c in caps]
    bucket = sum((k > c).astype(jnp.int32) for c in caps[:-1])
    return jax.lax.switch(bucket, branches, values, active, rank)


def _masked_push(g: Graph, values: jax.Array, frontier: jax.Array,
                 combine: str) -> jax.Array:
    """Push over all m edges: the wire table masked once (scope
    ``push.premask``), then gathered per edge with the identity for
    inactive sources, which the combine absorbs."""
    ident = _identity_scalar(combine, values.dtype)
    fb = frontier.reshape((-1,) + (1,) * (values.ndim - 1))
    with jax.named_scope("push.premask"):
        masked = jnp.where(fb, values, ident)
    msgs = jnp.take(masked, g.push_src, axis=0, mode="fill",
                    fill_value=ident)
    return COMBINE_FNS[combine](msgs, g.push_dst, g.n)


def push_relax(g: Graph, values: jax.Array, frontier: jax.Array,
               combine: str = "sum",
               msg_fn: Optional[Callable] = None,
               cost: Cost = Cost()) -> tuple[jax.Array, Cost]:
    """Push k-relaxation over the push-major (CSC) edge order.

    values: float/int [n] or [n, d] source payloads.
    frontier: bool[n]; only edges whose src is active contribute.
    Returns combined updates per destination, [n] or [n, d].

    Without ``msg_fn`` the wire table is masked once (scope
    ``push.premask``) and gathered once per edge: inactive sources send
    the combine identity, which the combine absorbs. That is exact only
    without ``msg_fn``, as ``msg_fn(ident, w)`` need not be the identity;
    with one, the frontier is gathered per edge and masks the messages.

    A min/max push of 32-bit payloads without ``msg_fn`` whose frontier
    has at most ``push_caps(g.m)[-1]`` out-edges touches only those edges
    (scope ``push.frontier``), in the smallest capacity that holds them;
    above it, the masked table is gathered over all m edges. A sum keeps
    the all-edge path: its float rounding follows the scatter's order.
    """
    k = frontier_out_edges(g, frontier)
    if msg_fn is None:
        caps = (push_caps(g.m) if combine != "sum"
                and values.dtype.itemsize == 4 else ())
        dense = partial(_masked_push, g, combine=combine)
        if caps:
            out = jax.lax.cond(k <= caps[-1], partial(
                _capped_push, g, k, combine=combine, caps=caps), dense,
                values, frontier)
        else:
            out = dense(values, frontier)
    else:
        active_e = jnp.take(frontier, g.push_src, axis=0, mode="fill",
                            fill_value=False)
        msgs = _edge_messages(values, g.push_src, g.push_w, msg_fn)
        active_b = active_e.reshape((-1,) + (1,) * (msgs.ndim - 1))
        msgs = jnp.where(active_b, msgs,
                         combine_identity(combine, msgs.dtype))
        out = COMBINE_FNS[combine](msgs, g.push_dst, g.n)
    width = 1 if values.ndim == 1 else values.shape[-1]
    cost = cost.charge(reads=k * width).charge_combining_writes(
        k * width, float_data=jnp.issubdtype(values.dtype, jnp.floating))
    return out, cost


def pull_relax(g: Graph, values: jax.Array, touched: Optional[jax.Array] = None,
               combine: str = "sum",
               msg_fn: Optional[Callable] = None,
               cost: Cost = Cost()) -> tuple[jax.Array, Cost]:
    """Pull k-relaxation over the pull-major (CSR) edge order.

    Each destination privately combines messages from ALL of its
    in-neighbors; ``touched`` (bool[n]) restricts which destinations are
    updated (their reads are still charged — pull must scan to know).
    """
    msgs = _edge_messages(values, g.coo_src, g.coo_w, msg_fn)
    out = COMBINE_FNS[combine](msgs, g.coo_dst, g.n)
    if touched is None:
        k = counter(g.m)
        wr = counter(g.n)
    else:
        out = mask_untouched(out, touched, combine)
        k = frontier_in_edges(g, touched)
        wr = jnp.sum(touched.astype(counter_dtype()))
    width = 1 if values.ndim == 1 else values.shape[-1]
    cost = cost.charge(reads=k * width, writes=wr * width)
    return out, cost


def pull_relax_ell(g: Graph, values: jax.Array,
                   combine: str = "sum",
                   msg_fn: Optional[Callable] = None,
                   cost: Cost = Cost()) -> tuple[jax.Array, Cost]:
    """Pull relaxation in ELL layout — dense [n, d_ell] gather+reduce.
    Mathematically equals pull_relax with touched=None; this is the layout
    the `ell_spmv` Pallas kernel tiles (rectangular VMEM blocks)."""
    v_pad = jnp.pad(values, [(0, 1)] + [(0, 0)] * (values.ndim - 1))
    gathered = jnp.take(v_pad, g.ell_idx, axis=0)  # [n, d_ell, ...]
    if msg_fn is not None:
        w = g.ell_w
        if gathered.ndim == 3:
            w = w[..., None]
        gathered = msg_fn(gathered, w)
    valid = (g.ell_idx < g.n)
    if gathered.ndim == 3:
        valid = valid[..., None]
    ident = combine_identity(combine, gathered.dtype)
    gathered = jnp.where(valid, gathered, ident)
    if combine == "sum":
        out = gathered.sum(axis=1)
    elif combine == "max":
        out = gathered.max(axis=1)
    else:
        out = gathered.min(axis=1)
    width = 1 if values.ndim == 1 else values.shape[-1]
    cost = cost.charge(reads=counter(g.m) * width,
                       writes=counter(g.n) * width)
    return out, cost


def k_filter(updated: jax.Array, cost: Cost = Cost()) -> tuple[jax.Array, Cost]:
    """k-filter: extract the updated-vertex set. Dense-mask world: identity
    on the mask, but charges the prefix-sum cost O(min(k, n)) the paper
    assigns (push only — pull checks every vertex anyway)."""
    k = jnp.sum(updated.astype(counter_dtype()))
    return updated, cost.charge(reads=k, writes=k, barriers=1)
