"""Pallas TPU kernel: frontier-aware pull over the ELL in-edge layout.

The paper's pull primitive is a private gather per destination — but the
rectangular ELL kernel (``ell_spmv_pallas``) gathers *every* row, so a
step whose program only needs a sparse touched-destination set (BFS's
unvisited set late in the run, an incremental recompute's affected set)
still pays the full ``n × d_ell`` scan. Grossman & Kozyrakis ("A New
Frontier for Pull-Based Graph Processing", PAPERS.md) show that
restricting pull to the touched frontier recovers the asymptotics that
make direction switching worthwhile; this kernel is that restriction on
the TPU layout:

    out[rows[r]] = combine_{j < d_ell} msg(x[ell_idx[rows[r], j]],
                                           ell_w[rows[r], j])

``rows`` is the *compacted* touched-destination id list (sentinel ``n``
in padding slots — see :func:`frontier_rows`). The grid tiles ``rows``,
not the vertex range: XLA gathers the ELL-in rows of those ids (a row
gather of contiguous ``d_ell``-wide slices), and the rectangular
``ell_spmv_pallas`` kernel then gathers and combines their neighbors'
payloads — writes remain private per touched row (the pull property,
unchanged). Work is ``R_pad × d_ell`` instead of ``n × d_ell``: at a
10% frontier the kernel does a tenth of the full scan's gathers.

Coverage matches ``ell_spmv_pallas`` exactly — combine ∈
{sum, max, min}, payloads ``[n]``/``[n, B]``, 32-bit payloads compiled
(any width interpreted), msg ∈ {copy, mul, add} — and untouched rows
come back as the
combine identity, so the full-vector result
(:func:`ell_pull_frontier_full`) equals
``mask_untouched(ell_spmv_pallas(...), touched)``: bit-identical for
the order-independent combines (min/max, integer sums); float sums
agree to reduction-order rounding (XLA schedules the row reduce per
tile shape, so even the full kernel differs in ULPs across block
sizes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.primitives import combine_identity
from .ell_spmv import _out_dtype, ell_spmv_pallas

__all__ = ["ell_pull_frontier_pallas", "ell_pull_frontier_full",
           "frontier_rows", "default_pull_cap"]


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


def default_pull_cap(n: int, m: int, d_ell: int) -> int:
    """Static row capacity for the traced frontier-pull path.

    Engines are compiled per graph *shape*, so the compacted row list
    needs a static size; the capacity is the point where the restricted
    gather is still guaranteed cheaper than the full scan — at most
    half the full kernel's ``n × d_ell`` slot reads (``cap × d_ell ≤
    m/2`` ⇒ touched sets that fit do at most half the scan's work).
    Denser touched sets overflow the capacity and take the full-scan
    kernel, which is exactly how they would have been priced before.
    """
    cap = min(n, m // (2 * max(d_ell, 1)))
    return max(8, _round_up(cap, 8))


def frontier_rows(touched: jax.Array, size: int) -> jax.Array:
    """Compact a bool[n] touched mask into int32 row ids, padded with
    the sentinel ``n`` to the static ``size``. Rows beyond ``size`` are
    dropped — callers guard with a fits bit (count ≤ size) before
    trusting the compaction."""
    n = touched.shape[0]
    # rank of each touched row by a two-level prefix sum (within 1024-
    # wide chunks, then over chunk totals), then one scatter. Equal to
    # jnp.nonzero(size=...), which the TPU compiler takes minutes over
    # at millions of rows.
    chunk = 1024
    x = jnp.pad(touched, (0, -n % chunk)).astype(jnp.int32)
    inner = jnp.cumsum(x.reshape(-1, chunk), axis=1)
    outer = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    rank = (inner + outer[:, None]).reshape(-1)[:n] - 1
    return jnp.full((size,), n, jnp.int32).at[
        jnp.where(touched, rank, size)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")


@functools.partial(jax.jit,
                   static_argnames=("combine", "msg", "block_r",
                                    "interpret", "num_sources"))
def ell_pull_frontier_pallas(x_padded: jax.Array, ell_idx: jax.Array,
                             ell_w: jax.Array, rows: jax.Array,
                             combine: str = "sum", msg: str = "mul",
                             block_r: int = 256,
                             interpret: bool | None = None,
                             num_sources: int | None = None) -> jax.Array:
    """Frontier-restricted pull: combined messages for ``rows`` only.

    x_padded: [n+1] or [n+1, B] payloads (zero row at index n);
    ell_idx/ell_w: the [n, d_ell] ELL-in layout; rows: int32[R]
    compacted touched row ids (sentinel ``n`` in padding slots).
    Returns the *compacted* [R] or [R, B] combined messages, aligned
    with ``rows``; sentinel slots hold the combine identity. Use
    :func:`ell_pull_frontier_full` for the scattered full-vector form.
    """
    n = ell_idx.shape[0]
    n_src = n if num_sources is None else num_sources
    live = rows < n
    safe = jnp.where(live, rows, 0)
    # row gather of the touched ELL-in rows; dead slots read as all-
    # sentinel rows, so the kernel returns the combine identity there
    idx = jnp.where(live[:, None], jnp.take(ell_idx, safe, axis=0),
                    n_src)
    w = jnp.take(ell_w, safe, axis=0)
    return ell_spmv_pallas(x_padded, idx, w, combine=combine, msg=msg,
                           block_n=block_r, interpret=interpret,
                           num_sources=n_src)


@functools.partial(jax.jit,
                   static_argnames=("combine", "msg", "block_r",
                                    "interpret"))
def ell_pull_frontier_full(x_padded: jax.Array, ell_idx: jax.Array,
                           ell_w: jax.Array, rows: jax.Array,
                           combine: str = "sum", msg: str = "mul",
                           block_r: int = 256,
                           interpret: bool | None = None) -> jax.Array:
    """Frontier pull scattered back to the full vertex range: touched
    rows carry their combined messages, every other row the combine
    identity — equal to ``mask_untouched(ell_spmv_pallas(...),
    touched)`` when ``rows`` compacts ``touched`` (bit-identical for
    order-independent combines; see the module docstring)."""
    n = ell_idx.shape[0]
    compact = ell_pull_frontier_pallas(
        x_padded, ell_idx, ell_w, rows, combine=combine, msg=msg,
        block_r=block_r, interpret=interpret)
    out_dtype = _out_dtype(x_padded.dtype, ell_w.dtype, msg, combine)
    shape = (n,) + compact.shape[1:]
    base = jnp.full(shape, combine_identity(combine, out_dtype),
                    out_dtype)
    # sentinel slots (rows == n) fall outside [0, n) and are dropped
    return base.at[rows].set(compact, mode="drop")
