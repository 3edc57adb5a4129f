"""Pallas TPU kernel: ELL-format pull relaxation (gather + combine).

Paper hot spot: the pull k-relaxation — every destination vertex privately
combines messages from its in-neighbors (CSR SpMV, §7.1). TPU adaptation
(DESIGN.md §9): CSR row-pointer chasing is hostile to VMEM tiling, so the
graph substrate materializes an ELL view — a rectangular [n, d_ell]
padded neighbor matrix — and the kernel becomes a dense-shaped
gather+reduce with sentinel masking:

    out[v] = combine_{j < d_ell} msg(x[ell_idx[v, j]], ell_w[v, j])

Mosaic cannot gather a vector with a vector of indices inside a tile, so
the work is split at that line: XLA gathers the neighbor payloads into
the transposed ``[B, d_ell, n]`` layout (vertices on the 128-wide lane
axis), and the kernel streams ``[B, d_ell, block_n]`` tiles of it with
the matching index/weight tiles, applies the message, masks sentinel
slots and reduces over the ``d_ell`` sublanes. The result is a
lane-major ``[B, 1, block_n]`` row, so every block is tile-aligned.
Writes are private per block (zero synchronization — the pull
property).

Production surface (the PallasBackend hot path):

  * combine ∈ {sum, max, min};
  * payloads [n] or [n, B] (the service layer's batched multi-query
    columns ride the same tile, amortizing the structure scan);
  * 32-bit payloads compiled on the TPU; the interpreter also takes
    float64/int64 (BFS parent ids are int32);
  * msg ∈ {"mul", "copy", "add"} — the wire-message shapes every
    registered algorithm uses (x·w SpMV, unweighted label copy, min-plus
    x+w relaxation);
  * empty rows return the combine identity (exactly what
    ``pull_relax_ell`` returns, so ``mask_untouched``/convergence checks
    agree bit-for-bit);
  * ``interpret=None`` auto-detects: compiled on TPU, interpreter
    elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.primitives import combine_identity

__all__ = ["ell_spmv_pallas", "default_interpret", "ell_vmem_bytes",
           "compiled_dtype_ok", "VMEM_CAP"]

# index-map literal: a bare ``0`` is int64 once x64 is on, which Mosaic
# cannot lower
Z = np.int32(0)
_MIB = 1 << 20
# the most VMEM a tuner candidate may plan for (v5e has 128 MiB; the
# rest is headroom for Mosaic's own scratch)
VMEM_CAP = 48 * _MIB


def default_interpret() -> bool:
    """Interpret unless a real TPU backs the default device."""
    return jax.default_backend() != "tpu"


def compiled_dtype_ok(dtype) -> bool:
    """Compiled kernels take 32-bit payloads only (Mosaic has no 64-bit
    vectors); the interpreter takes any width."""
    return jnp.dtype(dtype).itemsize <= 4


def check_compiled(kernel: str, interpret: bool, dtypes=(),
                   lane_blocks=()) -> None:
    """Refuse, naming ``kernel``, what the TPU compiler would refuse
    less legibly: 64-bit payloads, and lane blocks that are neither a
    multiple of 128 nor the whole axis (``(block, full)`` pairs; a
    ``full`` of None admits multiples of 128 only)."""
    if interpret:
        return
    wide = [jnp.dtype(d).name for d in dtypes if not compiled_dtype_ok(d)]
    if wide:
        raise TypeError(f"{kernel}: compiled TPU kernels take 32-bit "
                        f"payloads, got {wide}")
    for block, full in lane_blocks:
        if block % 128 and block != full:
            raise ValueError(f"{kernel}: lane block {block} is neither a "
                             f"multiple of 128 nor the whole axis {full}")


def compiler_params(interpret: bool, semantics: tuple, nbytes: int):
    """Mosaic parameters with an explicit scoped-VMEM limit sized to the
    kernel's working set (``nbytes``) plus headroom."""
    if interpret:
        return None
    limit = int(min(max(nbytes + 8 * _MIB, 16 * _MIB), 100 * _MIB))
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def ell_vmem_bytes(block_n: int, d_ell: int, width: int = 1,
                   msg: str = "mul") -> int:
    """VMEM working set of one grid step: double-buffered payload,
    index (and weight) tiles and the output row, plus the masked tile."""
    tile = d_ell * block_n * 4
    blocks = tile * (width + 1 + (msg != "copy")) + width * block_n * 4
    return 2 * blocks + 2 * width * tile


def reduce_keep(x, combine: str, axis: int):
    """Combine-reduce along ``axis`` in ``x``'s own dtype (jnp.sum would
    widen int32 to int64 under x64, which Mosaic cannot hold)."""
    if combine == "sum":
        return jnp.sum(x, axis=axis, keepdims=True, dtype=x.dtype)
    if combine == "max":
        return jnp.max(x, axis=axis, keepdims=True)
    return jnp.min(x, axis=axis, keepdims=True)


def _kernel(g_ref, idx_ref, *rest, combine: str, msg: str, n_src: int):
    # g_ref: [B, d_ell, block_n] gathered payloads; idx_ref (and w_ref
    # unless msg == "copy"): [d_ell, block_n]; out_ref: [B, 1, block_n]
    out_ref = rest[-1]
    dt = out_ref.dtype
    valid = idx_ref[...] < np.int32(n_src)
    x = g_ref[...].astype(dt)
    if msg != "copy":
        w = rest[0][...].astype(dt)[None]
        x = x * w if msg == "mul" else x + w
    x = jnp.where(valid[None], x, combine_identity(combine, dt))
    out_ref[...] = reduce_keep(x, combine, axis=1)


def msg_dtype(x_dtype, w_dtype, msg: str):
    """Per-edge message dtype: the msg_fn's promotion."""
    return x_dtype if msg == "copy" else jnp.result_type(x_dtype, w_dtype)


def _out_dtype(x_dtype, w_dtype, msg: str, combine: str):
    """Mirror pull_relax_ell exactly: msg_fn promotion plus jnp.sum's
    sub-default-int widening (int32 sums accumulate as int64 under x64)."""
    d = msg_dtype(x_dtype, w_dtype, msg)
    if combine == "sum":
        d = jnp.zeros((1,), d).sum().dtype
    return d


@functools.partial(jax.jit,
                   static_argnames=("combine", "msg", "block_n",
                                    "interpret", "num_sources"))
def ell_spmv_pallas(x_padded: jax.Array, ell_idx: jax.Array,
                    ell_w: jax.Array, combine: str = "sum",
                    msg: str = "mul", block_n: int = 256,
                    interpret: bool | None = None,
                    num_sources: int | None = None) -> jax.Array:
    """Pull k-relaxation over the ELL layout.

    x_padded: [n+1] or [n+1, B] payloads (sentinel row at index n);
    ell_idx: i32[n, d_ell]; ell_w: f32[n, d_ell]. Returns [n] or [n, B]
    combined messages; empty rows hold the combine identity.

    ``num_sources`` decouples the index validity bound from the row
    count: by default indices are valid below ``n`` (the square-matrix
    case), but a *row block* of a larger graph — the sharded backend's
    per-shard ELL slice, whose rows gather from the full gathered value
    vector — passes the global vertex count here. Requires
    ``x_padded.shape[0] > max valid index`` as usual.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d_ell = ell_idx.shape
    n_src = n if num_sources is None else num_sources
    dt = msg_dtype(x_padded.dtype, ell_w.dtype, msg)
    n_pad = -(-n // block_n) * block_n
    check_compiled("ell_spmv_pallas", interpret,
                   dtypes=(x_padded.dtype, dt),
                   lane_blocks=((block_n, n_pad),))
    # vertices on lanes: [n, d_ell] -> [d_ell, n_pad]
    idx_t = jnp.pad(ell_idx.T, ((0, 0), (0, n_pad - n)),
                    constant_values=n_src)
    xt = x_padded[None] if x_padded.ndim == 1 else x_padded.T
    width = xt.shape[0]
    gathered = jnp.take(xt, idx_t, axis=1, mode="clip")  # [B, d, n_pad]
    tile = pl.BlockSpec((d_ell, block_n), lambda i: (Z, i))
    operands = [gathered, idx_t]
    in_specs = [pl.BlockSpec((width, d_ell, block_n),
                             lambda i: (Z, Z, i)), tile]
    if msg != "copy":
        operands.append(jnp.pad(ell_w.T, ((0, 0), (0, n_pad - n))))
        in_specs.append(tile)
    out = pl.pallas_call(
        functools.partial(_kernel, combine=combine, msg=msg, n_src=n_src),
        grid=(n_pad // block_n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((width, 1, block_n), lambda i: (Z, Z, i)),
        out_shape=jax.ShapeDtypeStruct((width, 1, n_pad), dt),
        compiler_params=compiler_params(
            interpret, ("parallel",),
            ell_vmem_bytes(block_n, d_ell, width, msg)),
        interpret=interpret,
    )(*operands)
    out = out[:, 0, :n]
    out = out[0] if x_padded.ndim == 1 else out.T
    return out.astype(_out_dtype(x_padded.dtype, ell_w.dtype, msg,
                                 combine))
