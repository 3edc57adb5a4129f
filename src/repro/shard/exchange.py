"""Fused shard_map exchanges — the whole k-relaxation step on-mesh.

Unlike ``dist.collectives.pa_exchange`` (which computes local edges
replicated, outside the shard_map), both schedules here run local AND
remote work inside one shard_map block, so every shard touches only its
own slice — the paper's §6 DM execution model, end to end:

  * ``sharded_push`` — per shard: frontier-masked local scatter into the
    owned slice, frontier-masked remote scatter into a full-length
    private accumulator, then one combining collective delivers the
    owner slices (``psum_scatter`` for sum; ``pmin``/``pmax`` + slice
    otherwise). The remote accumulator can pass through error-feedback
    top-k compression (``dist.compression``) before the collective —
    the paper's "reduce what crosses the wire" lever applied to the
    message exchange itself.
  * ``sharded_pull`` — per shard: all_gather the value vector, then
    privately combine ALL in-edges of the owned destinations. Three
    interchangeable inner executors: ``dense`` (segment ops over the
    dst-grouped COO rows, preserving the single-device combine order),
    ``ell`` (rectangular gather+reduce over the per-shard ELL row
    block), and ``pallas`` (the ``ell_spmv`` kernel on the same block).

Message convention matches ``core.primitives``: ``msg_fn=None`` means
copy (the wire value itself); ``msg_fn(x, w)`` receives the raw
per-edge weight vector (un-broadcast — batched algorithms broadcast
inside their own msg_fn). Frontiers mask per *edge* inside the block,
so non-frontier sources contribute the combine identity exactly as
``push_relax`` does.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.backend import classify_msg_fn
from ..core.primitives import combine_identity
from ..dist.collectives import merge_combine
from ..dist.compression import CompressionConfig, compress_tree
from ..sparse.segment import segment_max, segment_min, segment_sum
from .topology import ShardTopology

__all__ = ["sharded_push", "sharded_pull", "active_remote_edges"]

_SEGMENT = {"sum": segment_sum, "min": segment_min, "max": segment_max}


def _edge_messages(vals, w, msg_fn, combine, active):
    """Per-edge payloads, inactive slots carrying the combine identity."""
    msg = vals if msg_fn is None else msg_fn(vals, w)
    if msg.ndim == 2:
        active = active[:, None]
    return jnp.where(active, msg, combine_identity(combine, msg.dtype))


def active_remote_edges(topo: ShardTopology, frontier: jax.Array):
    """Number of cut edges whose source is in the frontier — the sparse
    wire-message count a real DM push would send as (index, value)
    pairs. ``frontier`` is the unpadded ``[n]`` mask; sentinel slots
    fall outside it and count as inactive."""
    from ..core.cost_model import counter_dtype
    src = topo.remote.src.reshape(-1)
    ok = topo.remote.valid.reshape(-1)
    act = jnp.take(frontier, src, axis=0, mode="fill", fill_value=False)
    return jnp.sum((act & ok).astype(counter_dtype()))


def _scatter(msg, dst, ok, base, num_local, npad, combine, local: bool):
    """Segment-combine ``msg`` by destination; padding slots go to a
    trailing scratch row that is dropped (never aliasing a real
    vertex, which would perturb sum combine order)."""
    if local:
        seg = jnp.where(ok, dst - base, num_local)
        return _SEGMENT[combine](
            msg, jnp.clip(seg, 0, num_local), num_local + 1)[:num_local]
    seg = jnp.where(ok, dst, npad)
    return _SEGMENT[combine](msg, jnp.clip(seg, 0, npad), npad + 1)[:npad]


def sharded_push(mesh: Mesh, topo: ShardTopology, values_pad: jax.Array,
                 frontier_pad: jax.Array,
                 combine: str = "sum",
                 msg_fn: Optional[Callable] = None,
                 axis: str = "data",
                 cfg: Optional[CompressionConfig] = None,
                 err: Optional[jax.Array] = None):
    """Fused PA push step. ``values_pad``: ``[n_padded(,B)]``;
    ``frontier_pad``: ``bool[n_padded]``. When ``cfg``/``err`` are given
    (sum combine, 1-D float payload) the remote accumulator is
    compressed with error feedback before the collective. Returns
    ``(out [n_padded(,B)], new_err)`` — ``new_err`` is ``err`` (possibly
    None) when compression is off."""
    part = topo.part
    shard, npad = part.shard_size, part.n_padded
    loc_e, rem_e = topo.local, topo.remote
    compressing = (cfg is not None and cfg.kind != "none"
                   and err is not None)

    edge_spec = P(axis, None)

    def body(vb, fb, ls, ld, lw, lok, rs, rd, rw, rok, *eb):
        base = jax.lax.axis_index(axis) * shard

        def gather_side(sb, db, wb, okb, local):
            src = sb.reshape(-1)
            ok = okb.reshape(-1)
            lidx = jnp.clip(src - base, 0, shard - 1)
            act = ok & fb[lidx]
            msg = _edge_messages(vb[lidx], wb.reshape(-1), msg_fn,
                                 combine, act)
            return _scatter(msg, db.reshape(-1), ok, base, shard, npad,
                            combine, local)

        loc = gather_side(ls, ld, lw, lok, local=True)
        acc = gather_side(rs, rd, rw, rok, local=False)

        if compressing:
            (e,) = eb
            dec, res = compress_tree(acc + e.reshape(-1),
                                     jnp.zeros_like(acc), cfg)
            # error feedback: carry acc + err - sent forward
            new_err = res.reshape(e.shape)
            acc = dec

        if combine == "sum":
            rem = jax.lax.psum_scatter(acc, axis, scatter_dimension=0,
                                       tiled=True)
        else:
            red = (jax.lax.pmin if combine == "min"
                   else jax.lax.pmax)(acc, axis)
            rem = jax.lax.dynamic_slice_in_dim(red, base, shard)
        out = merge_combine(combine, loc, rem)
        return (out, new_err) if compressing else out

    # the error carry enters and leaves the block only when compressing
    extra = (edge_spec,) if compressing else ()
    in_specs = (P(axis), P(axis)) + (edge_spec,) * 8 + extra
    out_specs = (P(axis), edge_spec) if compressing else P(axis)
    block = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    res = block(values_pad, frontier_pad,
                loc_e.src, loc_e.dst, loc_e.w, loc_e.valid,
                rem_e.src, rem_e.dst, rem_e.w, rem_e.valid,
                *((err,) if compressing else ()))
    return res if compressing else (res, err)


def sharded_pull(mesh: Mesh, topo: ShardTopology, values_pad: jax.Array,
                 combine: str = "sum",
                 msg_fn: Optional[Callable] = None,
                 axis: str = "data", inner: str = "dense",
                 n: int = 0,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Fused pull step: all_gather + private per-shard combine of ALL
    in-edges. ``inner`` picks the per-shard executor (``dense`` |
    ``ell`` | ``pallas``); ``n`` is the true vertex count (the ELL
    sentinel / index validity bound). Returns ``[n_padded(,B)]``."""
    part = topo.part
    shard, npad = part.shard_size, part.n_padded
    mode = classify_msg_fn(msg_fn) if inner == "pallas" else None
    if inner == "pallas" and mode is None:
        inner = "ell"     # exotic msg_fn: same layout, jnp executor

    edges = topo.pull_edges

    def dense_body(vb, sb, db, wb, okb):
        full = jax.lax.all_gather(vb, axis, tiled=True)   # [npad(,B)]
        base = jax.lax.axis_index(axis) * shard
        src = sb.reshape(-1)
        ok = okb.reshape(-1)
        msg = _edge_messages(full[jnp.clip(src, 0, npad - 1)],
                             wb.reshape(-1), msg_fn, combine, ok)
        return _scatter(msg, db.reshape(-1), ok, base, shard, npad,
                        combine, local=True)

    def ell_body(vb, idxb, wb):
        full = jax.lax.all_gather(vb, axis, tiled=True)
        fullp = jnp.pad(full, [(0, 1)] + [(0, 0)] * (full.ndim - 1))
        idx = idxb.reshape((shard,) + idxb.shape[2:])
        w = wb.reshape((shard,) + wb.shape[2:])
        if inner == "pallas":
            from ..kernels.ell_spmv import ell_spmv_pallas
            return ell_spmv_pallas(
                fullp, idx, w, combine=combine, msg=mode,
                block_n=min(256, shard), interpret=interpret,
                num_sources=n).astype(vb.dtype)
        gathered = jnp.take(fullp, jnp.clip(idx, 0, npad), axis=0)
        if msg_fn is not None:
            we = w[..., None] if gathered.ndim == 3 else w
            gathered = msg_fn(gathered, we)
        valid = idx < n
        if gathered.ndim == 3:
            valid = valid[..., None]
        ident = combine_identity(combine, gathered.dtype)
        gathered = jnp.where(valid, gathered, ident)
        if combine == "sum":
            return gathered.sum(axis=1).astype(vb.dtype)
        if combine == "max":
            return gathered.max(axis=1)
        return gathered.min(axis=1)

    if inner == "dense":
        block = jax.shard_map(
            dense_body, mesh=mesh,
            in_specs=(P(axis),) + (P(axis, None),) * 4,
            out_specs=P(axis), check_vma=False)
        return block(values_pad, edges.src, edges.dst, edges.w,
                     edges.valid)
    block = jax.shard_map(
        ell_body, mesh=mesh,
        in_specs=(P(axis), P(axis, None, None), P(axis, None, None)),
        out_specs=P(axis), check_vma=False)
    return block(values_pad, topo.ell_idx, topo.ell_w)
