"""Graph data structures.

The paper's push/pull dichotomy is a *layout* dichotomy (§7.1):

  * pull  <-> CSR (in-edges grouped by destination; gather-reduce)
  * push  <-> CSC (out-edges grouped by source; scatter-combine)

On TPU we additionally keep an ELL (padded-row) view because rectangular
tiles are what VMEM/BlockSpecs want, and a raw COO view because edge-
parallel `segment_sum` formulations want flat index vectors.

All views are materialized once on the host (numpy) and stored as jnp
arrays inside a frozen pytree, so jitted code can pick whichever layout the
chosen direction needs without retracing.

Conventions
-----------
* Vertices are ``int32`` ids in ``[0, n)``.
* ``coo_src/coo_dst`` are sorted by ``dst`` (pull-major). ``csc_*``
  describes the same edges sorted by ``src`` (push-major).
* For undirected graphs every edge appears in both directions, i.e. ``m``
  counts *directed* edges (2x the undirected edge count).
* ELL rows are padded with the sentinel ``n`` (one past the last vertex);
  gathers index into value vectors padded with a zero row at index ``n``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Graph", "build_graph", "pad_values"]


def _to_i32(x) -> np.ndarray:
    return np.asarray(x, dtype=np.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Multi-layout immutable graph container (a JAX pytree).

    Attributes
    ----------
    n, m: static python ints (auxiliary data, not traced).
    coo_src, coo_dst: ``int32[m]`` edges sorted by ``dst`` (pull-major).
    coo_w: ``float32[m]`` weights aligned with ``coo_src/dst``.
    in_ptr: ``int32[n+1]`` CSR row pointer over the pull-major edges, i.e.
        in-edges of vertex ``v`` are slots ``in_ptr[v]:in_ptr[v+1]``.
    push_src, push_dst, push_w: the same edges sorted by ``src``.
    out_ptr: ``int32[n+1]`` pointer for the push-major order.
    ell_idx: ``int32[n, d_ell]`` padded in-neighbor lists (sentinel ``n``).
    ell_w: ``float32[n, d_ell]`` weights aligned with ``ell_idx`` (0 pad).
    in_deg, out_deg: ``int32[n]``.
    """

    coo_src: jax.Array
    coo_dst: jax.Array
    coo_w: jax.Array
    in_ptr: jax.Array
    push_src: jax.Array
    push_dst: jax.Array
    push_w: jax.Array
    out_ptr: jax.Array
    ell_idx: jax.Array
    ell_w: jax.Array
    in_deg: jax.Array
    out_deg: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    d_ell: int = dataclasses.field(metadata=dict(static=True))

    # -- convenience -------------------------------------------------------
    @property
    def num_vertices(self) -> int:
        return self.n

    @property
    def num_edges(self) -> int:
        return self.m

    def out_neighbors_slice(self, v: int) -> tuple[int, int]:
        """Host-side helper (numpy semantics) for tests/greedy tails."""
        ptr = np.asarray(self.out_ptr)
        return int(ptr[v]), int(ptr[v + 1])

    def reverse(self) -> "Graph":
        """Graph with every edge direction flipped (for directed use)."""
        return build_graph(
            np.asarray(self.coo_dst),
            np.asarray(self.coo_src),
            n=self.n,
            weights=np.asarray(self.coo_w),
            d_ell=self.d_ell,
        )


def _ell_from_ptr(ptr: np.ndarray, nbr: np.ndarray, w: np.ndarray, n: int,
                  d_ell: int) -> tuple[np.ndarray, np.ndarray]:
    """Pack CSR-ordered neighbor lists into a padded [n, d_ell] matrix."""
    deg = np.diff(ptr)
    d_max = int(deg.max()) if n else 0
    if d_ell < d_max:
        raise ValueError(f"d_ell={d_ell} < max degree {d_max}")
    idx = np.full((n, d_ell), n, dtype=np.int32)
    val = np.zeros((n, d_ell), dtype=w.dtype)
    # vectorized ragged fill: position of each edge within its row
    within = np.arange(len(nbr), dtype=np.int64) - np.repeat(ptr[:-1], deg)
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    idx[rows, within] = nbr
    val[rows, within] = w
    return idx, val


def build_graph(src, dst, n: int, weights=None, d_ell: Optional[int] = None,
                pad_rows_to: int = 8) -> Graph:
    """Build all layouts from a COO edge list.

    ``d_ell`` may be given to force a specific (e.g. tile-aligned) padded
    width; otherwise max in-degree rounded up to ``pad_rows_to``.

    Edge endpoints must lie in ``[0, n)`` and weights must be finite;
    violations raise ``ValueError`` naming the first offending edge.
    (An out-of-range endpoint would otherwise corrupt the CSR pointer
    build silently; a NaN/Inf weight poisons every distance it touches.)
    """
    src = _to_i32(src)
    dst = _to_i32(dst)
    m = int(src.shape[0])
    if dst.shape != src.shape:
        raise ValueError(
            f"build_graph: src has {m} edges but dst has "
            f"{int(dst.shape[0])} — the COO views must be aligned")
    for name, arr in (("src", src), ("dst", dst)):
        if m and (arr.min() < 0 or arr.max() >= n):
            bad = int(np.flatnonzero((arr < 0) | (arr >= n))[0])
            raise ValueError(
                f"build_graph: {name}[{bad}] = {int(arr[bad])} is "
                f"outside the vertex range [0, {n}) — every edge "
                f"endpoint must name an existing vertex")
    unweighted = weights is None
    if unweighted:
        weights = np.ones(m, dtype=np.float32)
    w = np.asarray(weights, dtype=np.float32)
    if w.shape != (m,):
        raise ValueError(
            f"build_graph: weights shape {w.shape} does not match the "
            f"{m} edges")
    if m and not np.isfinite(w).all():
        bad = int(np.flatnonzero(~np.isfinite(w))[0])
        raise ValueError(
            f"build_graph: weights[{bad}] = {w[bad]} is not finite — "
            f"NaN/Inf edge weights are rejected at construction")

    # pull-major: sort by dst (stable keeps generator order within a row)
    order = np.argsort(dst, kind="stable")
    p_src, p_dst, p_w = src[order], dst[order], w[order]
    in_ptr = np.zeros(n + 1, dtype=np.int32)
    in_ptr[1:] = np.cumsum(np.bincount(p_dst, minlength=n))

    # push-major: sort by src
    order2 = np.argsort(src, kind="stable")
    q_src, q_dst, q_w = src[order2], dst[order2], w[order2]
    out_ptr = np.zeros(n + 1, dtype=np.int32)
    out_ptr[1:] = np.cumsum(np.bincount(q_src, minlength=n))

    in_deg = np.diff(in_ptr).astype(np.int32)
    out_deg = np.diff(out_ptr).astype(np.int32)

    d_max = int(in_deg.max()) if n else 0
    if d_ell is None:
        d_ell = max(pad_rows_to, -(-d_max // pad_rows_to) * pad_rows_to)
    ell_idx, ell_w = _ell_from_ptr(in_ptr, p_src, p_w, n, d_ell)

    dev = jnp.asarray
    coo_w = dev(p_w)
    # unit weights read the same in either edge order: one device array
    push_w = coo_w if unweighted else dev(q_w)
    return Graph(
        coo_src=dev(p_src), coo_dst=dev(p_dst), coo_w=coo_w,
        in_ptr=dev(in_ptr),
        push_src=dev(q_src), push_dst=dev(q_dst), push_w=push_w,
        out_ptr=dev(out_ptr),
        ell_idx=dev(ell_idx), ell_w=dev(ell_w),
        in_deg=dev(in_deg), out_deg=dev(out_deg),
        n=n, m=m, d_ell=int(d_ell),
    )


@partial(jax.jit, static_argnames=())
def pad_values(x: jax.Array) -> jax.Array:
    """Append a zero row/scalar at index ``n`` so ELL sentinel gathers
    read zeros. Works for [n] vectors and [n, d] matrices."""
    pad_width = [(0, 1)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeView:
    """Duck-typed Graph stand-in for GNN layers: one edge order, shared by
    both directions (the provider chooses pull- or push-major order).
    Used by the dry-run where full multi-layout Graphs would waste input
    memory, and by sampled-subgraph training."""
    src: jax.Array
    dst: jax.Array
    w: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))

    @property
    def coo_src(self):
        return self.src

    @property
    def coo_dst(self):
        return self.dst

    @property
    def coo_w(self):
        return self.w

    @property
    def push_src(self):
        return self.src

    @property
    def push_dst(self):
        return self.dst

    @property
    def push_w(self):
        return self.w
