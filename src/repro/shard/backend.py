"""ShardedBackend — the engine's k-relaxation under shard_map (§6).

Where ``DistributedBackend`` demonstrates the paper's DM *exchanges*
(local work replicated, remote through collectives), this backend runs
the whole step on-mesh: values live sharded ``[P × shard_size]``, local
and remote edges are both processed inside one shard_map block per
direction (``shard.exchange``), and only the remote accumulator crosses
devices. It is the production surface behind ``api.solve(...,
backend="shard")``.

Wire-byte accounting is *adaptive*, mirroring the paper's sparse/dense
message tradeoff: a push step charges
``min(dense alltoall, active_cut_edges · (index + payload))`` per device
— so a frontier-sparse push (BFS early steps) prices below the flat
all_gather pull, and ``AutoSwitch`` can flip direction for distributed
reasons alone. ``predict_comm_bytes`` computes the identical formulas,
keeping the predictor exact for exchange steps.

Optional push-side compression (``dist.compression``): the remote
accumulator passes through error-feedback top-k / int8 before the
combining collective. The error carry rides the engine loop via
``init_exchange_state``/``relax_ex``. Compression applies to sum
combines with 1-D float32 payloads (PageRank-shaped exchanges); other
cells pass the carry through untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..core.backend import ExchangeBackend
from ..core.cost_model import Cost, counter, counter_dtype
from ..resilience import resilient_call
from ..core.direction import Direction
from ..core.primitives import (combine_identity, frontier_out_edges,
                               mask_untouched)
from ..dist.compression import CompressionConfig
from ..graphs.structure import Graph
from .exchange import active_remote_edges, sharded_pull, sharded_push
from .mesh import make_shard_mesh
from .topology import ShardTopology, build_topology

__all__ = ["ShardedBackend"]

_IDX_BYTES = 4          # int32 vertex index on the sparse push wire


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedBackend(ExchangeBackend):
    """Multi-device k-relaxation over a 1D vertex partition.

    Build with :meth:`prepare`; instances are graph-specific (they hold
    the per-shard topology). ``inner`` selects the pull executor:
    ``"dense"`` (order-preserving segment ops — bit-compatible with the
    single-device dense pull), ``"ell"`` or ``"pallas"`` (rectangular
    per-shard row blocks — the ELL/kernel semantics).
    """
    mesh: object = None
    topo: Optional[ShardTopology] = None
    axis: str = "data"
    inner: str = "dense"
    compression: Optional[CompressionConfig] = None
    interpret: Optional[bool] = None

    # the pull gathers the full vector and (for ELL inners) scans every
    # row; dense inner also reads all m edges — rectangular semantics
    pull_scans_all = True

    # identity hash/eq (see DistributedBackend: instances hold jnp
    # arrays; engine caches key on backend identity, and value-based
    # dataclass comparison would collide across same-shape graphs)
    __hash__ = object.__hash__

    def __eq__(self, other):
        return self is other

    @classmethod
    def prepare(cls, g: Graph, mesh=None, num_shards: Optional[int] = None,
                axis: str = "data", inner: str = "dense",
                compression: Optional[CompressionConfig] = None,
                interpret: Optional[bool] = None) -> "ShardedBackend":
        from ..graphs.partition import partition_1d
        if mesh is None:
            mesh = make_shard_mesh(num_shards, axis=axis)
        P = mesh.shape[axis]
        if num_shards is not None and num_shards != P:
            raise ValueError(
                f"num_shards={num_shards} must equal the mesh '{axis}' "
                f"axis size ({P}): partitions map to mesh shards 1:1.")
        if inner not in ("dense", "ell", "pallas"):
            raise ValueError(f"unknown inner executor {inner!r}")
        part = partition_1d(g.n, P)      # validates 1 <= P <= n
        # every view is [P, ...] grouped by owner shard: place row p on
        # device p once, instead of moving it on every solve
        topo = jax.device_put(build_topology(g, part),
                              NamedSharding(mesh, PartitionSpec(axis)))
        return cls(mesh=mesh, topo=topo, axis=axis, inner=inner,
                   compression=compression, interpret=interpret)

    # -- helpers -----------------------------------------------------------
    @property
    def part(self):
        return self.topo.part

    @property
    def cut_edges(self) -> int:
        return self.topo.cut_edges

    def telemetry_counters(self) -> dict:
        """Shard geometry for obs traces: the facts the §6 wire-byte
        charges are priced from (device count, the PA cut, padded row
        count), plus whether compression is on."""
        return {"num_shards": self.part.num_parts,
                "cut_edges": self.cut_edges,
                "n_padded": self.part.n_padded,
                "compression": int(self.compression is not None)}

    def _pad(self, values: jax.Array, fill) -> jax.Array:
        extra = max(0, self.part.n_padded - values.shape[0])
        widths = ((0, extra),) + ((0, 0),) * (values.ndim - 1)
        return jnp.pad(values, widths, constant_values=fill)

    def _compresses(self, values, combine: str) -> bool:
        """Trace-time gate: compression covers the PageRank-shaped
        exchange — sum combine over a 1-D float32 payload."""
        return (self.compression is not None
                and self.compression.kind != "none"
                and combine == "sum" and values.ndim == 1
                and values.dtype == jnp.float32)

    def _zero_err(self) -> jax.Array:
        return jnp.zeros((self.part.num_parts, self.part.n_padded),
                         jnp.float32)

    def _wire_push_bytes(self, values, frontier):
        """Per-run total push wire bytes: adaptive min(dense combined
        alltoall, sparse (index, payload) pairs over the active cut),
        or the compressed top-k/int8 footprint."""
        Pn = self.part.num_parts
        npad = self.part.n_padded
        width = 1 if values.ndim == 1 else values.shape[-1]
        item = values.dtype.itemsize * width
        if (self.compression is not None
                and self.compression.kind != "none"
                and values.ndim == 1 and values.dtype == jnp.float32):
            if self.compression.kind == "topk":
                k = max(1, int(self.compression.topk_frac * npad))
                per_dev = counter(k * (_IDX_BYTES + 4))
            else:                               # int8: payload + scale
                per_dev = counter(npad + 4)
            return per_dev * Pn
        dense = counter(npad * item)
        sparse = active_remote_edges(self.topo, frontier) * (
            _IDX_BYTES + item)
        return jnp.minimum(dense, sparse).astype(counter_dtype()) * Pn

    def _wire_pull_bytes(self, values):
        Pn = self.part.num_parts
        npad = self.part.n_padded
        width = 1 if values.ndim == 1 else values.shape[-1]
        item = values.dtype.itemsize * width
        return counter(npad * item * (Pn - 1) // max(Pn, 1)) * Pn

    def operands(self):
        return self.topo

    def bind(self, operands) -> "ShardedBackend":
        return dataclasses.replace(self, topo=operands)

    # -- exchange state (error-feedback carry) ----------------------------
    def init_exchange_state(self, g: Graph):
        if self.compression is not None and self.compression.kind != "none":
            return self._zero_err()
        return ()

    # -- ExchangeBackend ---------------------------------------------------
    def _push_ex(self, g, values, frontier, combine, msg_fn, cost, err):
        vpad = self._pad(values, 0)
        fpad = self._pad(frontier, False)
        compressing = err is not None and self._compresses(values, combine)
        # the collective build is pure trace-time work, so a transient
        # failure (injected or a flaky mesh) is safely retried in place
        out, new_err = resilient_call(
            "shard.exchange.push",
            lambda: sharded_push(
                self.mesh, self.topo, vpad, fpad, combine=combine,
                msg_fn=msg_fn, axis=self.axis,
                cfg=self.compression if compressing else None,
                err=err if compressing else None))
        width = 1 if values.ndim == 1 else values.shape[-1]
        k = frontier_out_edges(g, frontier) * width
        kc = jnp.minimum(k, counter(self.cut_edges) * width)
        cost = cost.charge(reads=k).charge_combining_writes(
            kc, float_data=jnp.issubdtype(values.dtype, jnp.floating))
        cost = cost.charge(
            messages=kc,
            collective_bytes=self._wire_push_bytes(values, frontier))
        return out[:g.n], cost, (new_err if compressing else err)

    def push(self, g, values, frontier, combine, msg_fn, cost):
        # stateless surface: compression (when configured) runs with a
        # zero error carry — single-step view; feedback accumulates only
        # through relax_ex / the engine loop.
        err = (self._zero_err()
               if self._compresses(values, combine) else None)
        out, cost, _ = self._push_ex(g, values, frontier, combine,
                                     msg_fn, cost, err)
        return out, cost

    def pull(self, g, values, touched, combine, msg_fn, cost):
        ident = combine_identity(combine, values.dtype)
        vpad = self._pad(values, ident)
        out = resilient_call(
            "shard.exchange.pull",
            lambda: sharded_pull(
                self.mesh, self.topo, vpad, combine=combine,
                msg_fn=msg_fn, axis=self.axis, inner=self.inner, n=g.n,
                interpret=self.interpret))[:g.n]
        if touched is not None:
            out = mask_untouched(out, touched, combine)
        width = 1 if values.ndim == 1 else values.shape[-1]
        # rectangular semantics: every in-edge is read, every owned
        # vertex written, regardless of the touched set
        cost = cost.charge(
            reads=counter(g.m) * width, writes=counter(g.n) * width,
            collective_bytes=self._wire_pull_bytes(values))
        return out, cost

    def relax_ex(self, g, values, frontier, *, direction,
                 combine: str = "sum",
                 msg_fn: Optional[Callable] = None,
                 touched: Optional[jax.Array] = None,
                 cost: Cost = Cost(), xstate=()):
        stateless = isinstance(xstate, tuple)
        if stateless or not self._compresses(values, combine):
            out, cost = self.relax(g, values, frontier,
                                   direction=direction, combine=combine,
                                   msg_fn=msg_fn, touched=touched,
                                   cost=cost)
            return out, cost, xstate

        def push(v, f, c, e):
            with jax.named_scope("exchange.push"):
                return self._push_ex(g, v, f, combine, msg_fn, c, e)

        def pull(v, f, c, e):
            with jax.named_scope("exchange.pull"):
                return self.pull(g, v, touched, combine, msg_fn, c) + (e,)

        if isinstance(direction, Direction):
            return (push if direction == Direction.PUSH else pull)(
                values, frontier, cost, xstate)
        return jax.lax.cond(direction, push, pull, values, frontier, cost,
                            xstate)

    def predict_comm_bytes(self, g, values, frontier):
        return (self._wire_push_bytes(values, frontier),
                self._wire_pull_bytes(values))
