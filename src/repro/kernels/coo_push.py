"""Pallas TPU kernel: two-phase contention-free push relaxation.

Paper hot spot: the push k-relaxation — active sources scatter combined
updates into destination slots (CSC SpMSpV, §7.1). On CPU this is an
atomic per edge; the old TPU adaptation serialized the whole grid to
avoid write conflicts and lost to jnp ``segment_sum`` everywhere. This
version removes the contention instead of serializing around it:

**Phase 1 — binning.** Edges are regrouped by destination *bin*: bin
``b`` owns destinations ``[b·bin_n, (b+1)·bin_n)``. Because the COO
edges are already dst-sorted, each bin is a *contiguous slice* of the
edge list, so the layout — a padded ``[nb, cap]`` matrix — is one
windowed slice per bin at ``in_ptr``-derived offsets
(:func:`bin_plan_traced`); :func:`build_push_plan` does the same for a
concrete graph with the exact capacity, and under a trace (the engine
jits the graph) the capacity is static with a runtime fits guard.

**Phase 2 — per-bin reduce.** XLA gathers each edge's source payload
and frontier bit and forms the messages in the plan layout, transposed
to ``[nb, B, cap]`` (edges on the 128-wide lane axis) — Mosaic cannot
gather with a vector of indices inside a tile. The kernel's grid runs
*in parallel over destination bins* (axis 0) while streaming edge
blocks (axis 1, which Pallas double-buffers); each bin owns a private
``[B, bin_n]`` accumulator block, so no two grid cells ever write the
same destination — contention-free by construction, no atomics, no
sequential grid. Each edge block is matched against the bin's rows as
a ``[bin_n, block_e]`` one-hot, reduced by one of two strategies,
selected by the autotuner:

  * ``"scan"`` — the VPU: every row scans the edge block through the
    one-hot mask and reduces over the lanes (a masked window reduce);
    the ``[bin_n, 1]`` column is turned into a lane-major row by one
    aligned tile transpose. Covers every combine and dtype.
  * ``"mxu"`` — float sums as a one-hot matmul on the MXU (messages
    ``[B, block_e]`` against the ``[bin_n, block_e]`` one-hot,
    f32-exact precision); other cells reduce as ``"scan"`` does.

Both do ``O(bin_n × cap)`` work per bin, which bounds the bin width.

Sentinel discipline: padded slots carry ``n`` on both endpoints and
weight 0; their messages are the combine identity, and their
destination matches no row. Destinations with no (active) in-edge hold
the combine identity — including whole edgeless bins (all-padding
blocks).

Production surface matches ``ell_spmv_pallas``: combine ∈
{sum, max, min}, payloads [n] or [n, B], 32-bit payloads compiled (any
width interpreted), msg ∈ {"mul", "copy", "add"}, ``interpret=None``
auto-detect.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..core.primitives import combine_identity
from .ell_spmv import (Z, check_compiled, compiler_params,
                       default_interpret, msg_dtype, reduce_keep)

__all__ = ["PushBinPlan", "build_push_plan", "bin_plan_traced",
           "default_bin_cap", "coo_push_pallas", "push_vmem_bytes",
           "PUSH_STRATEGIES"]

PUSH_STRATEGIES = ("scan", "mxu")


def _round_up(x: int, q: int) -> int:
    return -(-x // q) * q


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PushBinPlan:
    """Phase-1 output: the per-graph bin layout the reduce phase tiles.

    ``src/dst/w`` are ``[nb, cap]`` — row ``b`` holds the (dst-sorted)
    edges whose destination falls in bin ``b``, padded with the
    sentinel ``n`` / weight 0.
    """
    src: jax.Array   # int32[nb, cap]
    dst: jax.Array   # int32[nb, cap]
    w: jax.Array     # [nb, cap]
    bin_n: int = dataclasses.field(metadata=dict(static=True))
    cap: int = dataclasses.field(metadata=dict(static=True))
    nb: int = dataclasses.field(metadata=dict(static=True))


def _bin_offsets(in_ptr, n: int, bin_n: int, nb: int):
    """First edge slot of every bin, plus the end of the last one."""
    starts = np.minimum(np.arange(nb + 1) * bin_n, n)
    return in_ptr[starts]


def build_push_plan(src, dst, w, n: int, bin_n: int,
                    align: int = 128) -> PushBinPlan:
    """Host-side (concrete-graph) binning pass: the same windowed layout
    as :func:`bin_plan_traced`, with the capacity set to the fullest
    bin (aligned to the edge block, so the reduce grid divides evenly).
    Requires dst-sorted edges, which ``Graph.coo_*`` are."""
    dst = np.asarray(dst)
    nb = max(1, _round_up(n, bin_n) // bin_n)
    in_ptr = np.searchsorted(dst, np.arange(n + 1)).astype(np.int32)
    cap = int(np.diff(_bin_offsets(in_ptr, n, bin_n, nb)).max())
    plan, _ = bin_plan_traced(
        jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
        jnp.asarray(w, jnp.float32), jnp.asarray(in_ptr), n, bin_n,
        cap=max(cap, 1), align=align)
    return plan


def default_bin_cap(n: int, m: int, d_ell: int, bin_n: int,
                    align: int) -> int:
    """Static bin capacity for the traced binning pass: twice the mean
    bin load with at least one full max-degree row, never more than the
    whole edge list. Real skew on the benchmark families is ~1.2–1.5×
    the mean, so the 2× slack fits; callers guard the residual risk
    with ``lax.cond`` on the plan's ``fits`` bit."""
    nb = max(1, _round_up(n, bin_n) // bin_n)
    mean = -(-max(m, 1) // nb)
    return _round_up(min(max(m, 1), max(d_ell, 2 * mean)),
                     max(align, 1))


def bin_plan_traced(src, dst, w, in_ptr, n: int, bin_n: int, cap: int,
                    align: int = 128) -> tuple[PushBinPlan, jax.Array]:
    """In-trace binning pass (the engine jits the graph, so the host
    regroup is unavailable). dst-sorted edges make every bin a
    contiguous slice of the edge list: the layout is one ``cap``-wide
    window per bin at ``in_ptr``-derived offsets — O(nb·cap) contiguous
    reads, no scatter. Returns ``(plan, fits)`` where ``fits`` is the
    runtime guard (true iff no bin overflows the static ``cap``);
    callers branch to the jnp segment fallback when it fails."""
    m = src.shape[0]
    nb = max(1, _round_up(n, bin_n) // bin_n)
    cap = _round_up(max(cap, 1), max(align, 1))
    if m == 0:     # edgeless graph: all-sentinel layout, trivially fits
        return PushBinPlan(
            src=jnp.full((nb, cap), n, jnp.int32),
            dst=jnp.full((nb, cap), n, jnp.int32),
            w=jnp.zeros((nb, cap), w.dtype),
            bin_n=int(bin_n), cap=int(cap), nb=int(nb)), jnp.bool_(True)
    off = _bin_offsets(in_ptr, n, bin_n, nb)         # [nb+1]
    counts = off[1:] - off[:-1]
    fits = jnp.max(counts) <= cap
    in_bin = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]

    def windows(a, fill):
        # padding the tail by cap keeps every window in bounds, so no
        # start index is clamped (which would shift the window)
        a = jnp.concatenate([a, jnp.full((cap,), fill, a.dtype)])
        rows = jax.vmap(lambda o: lax.dynamic_slice(a, (o,), (cap,)))(
            off[:-1])
        return jnp.where(in_bin, rows, jnp.asarray(fill, a.dtype))

    return PushBinPlan(src=windows(src, n), dst=windows(dst, n),
                       w=windows(w, 0), bin_n=int(bin_n), cap=int(cap),
                       nb=int(nb)), fits


def push_vmem_bytes(block_e: int, bin_n: int, width: int = 1) -> int:
    """VMEM working set of one grid step: double-buffered message and
    destination blocks, the resident accumulator, and the
    ``[bin_n, block_e]`` one-hot and masked window."""
    return (2 * (width + 1) * block_e * 4 + 2 * width * bin_n * 4
            + 3 * bin_n * block_e * 4 + bin_n * 128 * 4)


def _kernel(m_ref, dst_ref, acc_ref, *, n: int, bin_n: int, combine: str,
            mxu: bool):
    # m_ref: [B, block_e] messages (identity where masked); dst_ref:
    # [1, block_e]; acc_ref: [B, bin_n], resident across the edge axis
    b = pl.program_id(0)
    dt = acc_ref.dtype
    ident = combine_identity(combine, dt)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, ident, dt)

    dst = dst_ref[...]
    block_e = dst.shape[-1]
    # bin-relative destination; padding gets the one-past row bin_n so
    # it can never one-hot onto a real destination
    rel = jnp.where(dst < np.int32(n), dst - b * np.int32(bin_n),
                    np.int32(bin_n))
    hit = lax.broadcasted_iota(jnp.int32, (bin_n, block_e), 0) == rel
    msgs = m_ref[...]
    if mxu:
        # CRCW-CB combine on the MXU: [B, block_e] x [bin_n, block_e]^T
        local = lax.dot_general(
            msgs, hit.astype(dt), (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=dt)
        acc_ref[...] += local
        return
    rows = []
    for j in range(msgs.shape[0]):
        win = jnp.where(hit, msgs[j:j + 1, :], ident)
        col = reduce_keep(win, combine, axis=1)            # [bin_n, 1]
        # column -> lane-major row through one aligned tile transpose
        rows.append(jnp.transpose(
            jnp.broadcast_to(col, (bin_n, 128)))[0:1, :])
    local = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    acc = acc_ref[...]
    if combine == "sum":
        acc_ref[...] = acc + local
    elif combine == "max":
        acc_ref[...] = jnp.maximum(acc, local)
    else:
        acc_ref[...] = jnp.minimum(acc, local)


@functools.partial(jax.jit,
                   static_argnames=("n", "combine", "msg", "block_e",
                                    "block_n", "interpret", "strategy"))
def coo_push_pallas(x: jax.Array, active: jax.Array, src: jax.Array,
                    dst: jax.Array, w: jax.Array, n: int,
                    combine: str = "sum", msg: str = "mul",
                    block_e: int = 512, block_n: int = 256,
                    interpret: bool | None = None,
                    plan: PushBinPlan | None = None,
                    strategy: str = "scan") -> jax.Array:
    """Two-phase push-combine over dst-sorted COO edges.

    x: [n] or [n, B] source payloads; active: bool[n] frontier;
    src/dst: i32[m] (sorted by dst); w: f32[m]. Returns combined
    updates per destination ([n] or [n, B]); destinations with no
    active in-edge hold the combine identity.

    ``block_n`` is the destination-bin width, ``block_e`` the streamed
    edge-chunk size, ``strategy`` the phase-2 reduce ("scan" |
    "mxu") — all three are the autotuner's search axes. ``plan`` is
    the phase-1 bin layout; pass one built by :func:`build_push_plan`
    (the PallasBackend caches it per graph) to skip re-binning. The
    plan-free path bins in-trace with ``cap = m`` — always correct,
    sized for tests and small graphs, not the hot path.
    """
    if strategy not in PUSH_STRATEGIES:
        raise ValueError(f"strategy={strategy!r} not in "
                         f"{PUSH_STRATEGIES}")
    if interpret is None:
        interpret = default_interpret()
    m = src.shape[0]
    out_dtype = msg_dtype(x.dtype, w.dtype, msg)
    if m == 0:
        # edgeless graph: no edges means every destination holds the
        # combine identity, like the segment primitives
        shape = (n,) if x.ndim == 1 else (n, x.shape[1])
        return jnp.full(shape, combine_identity(combine, out_dtype),
                        out_dtype)
    if plan is None:
        in_ptr = jnp.searchsorted(dst, jnp.arange(n + 1, dtype=dst.dtype)
                                  ).astype(jnp.int32)
        plan, _ = bin_plan_traced(src, dst, w, in_ptr, n, block_n,
                                  cap=m, align=block_e)
    bin_n, cap, nb = plan.bin_n, plan.cap, plan.nb
    block_e = min(block_e, cap)
    if cap % block_e:
        raise ValueError(
            f"plan cap={cap} not a multiple of block_e={block_e}: "
            "build the plan with align=block_e")
    check_compiled("coo_push_pallas", interpret,
                   dtypes=(x.dtype, out_dtype),
                   lane_blocks=((block_e, cap), (bin_n, None)))
    # phase 2, XLA side: per-edge messages in the plan layout, masked
    # to the combine identity off the frontier and on padding slots
    valid = plan.dst < n
    safe = jnp.where(valid, plan.src, 0)
    vals = jnp.take(x, safe, axis=0, mode="clip")      # [nb, cap(, B)]
    if msg != "copy":
        wb = plan.w[..., None] if x.ndim == 2 else plan.w
        vals = vals * wb if msg == "mul" else vals + wb
    ok = valid & jnp.take(active, safe, axis=0, mode="clip")
    if x.ndim == 2:
        ok = ok[..., None]
    msgs = jnp.where(ok, vals.astype(out_dtype),
                     combine_identity(combine, out_dtype))
    msgs = msgs[:, None, :] if x.ndim == 1 else jnp.swapaxes(msgs, 1, 2)
    width = msgs.shape[1]
    mxu = (strategy == "mxu" and combine == "sum"
           and jnp.issubdtype(out_dtype, jnp.floating))
    acc = pl.pallas_call(
        functools.partial(_kernel, n=n, bin_n=bin_n, combine=combine,
                          mxu=mxu),
        grid=(nb, cap // block_e),
        in_specs=[
            pl.BlockSpec((None, width, block_e), lambda b, e: (b, Z, e)),
            pl.BlockSpec((None, 1, block_e), lambda b, e: (b, Z, e)),
        ],
        out_specs=pl.BlockSpec((None, width, bin_n),
                               lambda b, e: (b, Z, Z)),
        out_shape=jax.ShapeDtypeStruct((nb, width, bin_n), out_dtype),
        compiler_params=compiler_params(
            interpret, ("parallel", "arbitrary"),
            push_vmem_bytes(block_e, bin_n, width)),
        interpret=interpret,
    )(msgs, plan.dst[:, None, :])
    # [nb, B, bin_n] -> [n(, B)]
    acc = jnp.swapaxes(acc, 0, 1).reshape(width, nb * bin_n)[:, :n]
    return acc[0] if x.ndim == 1 else acc.T
