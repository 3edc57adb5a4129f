"""Compile rehearsals for a TPU v5e that is described, not attached.

JAX ships the TPU compiler, and it compiles for a topology it is only
told about. It refuses what the chip would refuse — 64-bit vectors in a
kernel, blocks that break the 8x128 tiling, more VMEM than a kernel may
use, a program that does not fit HBM — which interpret-mode tests never
see. These tests compile, with ``interpret=False``:

  * the three graph kernels at the chip smoke's shapes (n = 2**21,
    d_ell = 64, 32-bit payloads, the GAP urand graph's edge count);
  * the dense engine's jitted loop for ``bfs``/auto and ``pagerank``,
    and the shape of its BFS push (no per-edge frontier gather);
  * the frontier push in each of its capacities at the urand graph's
    size, within the dense push's temporary memory;
  * the sharded ``bfs``/auto engine on a 4-chip mesh.

Nothing runs, so they say nothing about results or times. The topology
is described inside a module fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports every
test file. Keep all such compiles in this one file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro import api
from repro.core.backend import DenseBackend, PallasBackend
from repro.core.engine import PushPullEngine
from repro.core.primitives import (_frontier_push, _masked_push, push_caps,
                                   push_relax)
from repro.graphs import Graph, erdos_renyi
from repro.graphs.partition import partition_1d
from repro.kernels.coo_push import (PushBinPlan, coo_push_pallas,
                                    default_bin_cap)
from repro.kernels.ell_pull_frontier import (default_pull_cap,
                                             ell_pull_frontier_pallas)
from repro.kernels.ell_spmv import ell_spmv_pallas
from repro.shard import ShardedBackend, build_topology

N = 2 ** 21
D_ELL = 64
M = 67_108_864          # directed edges of erdos_renyi(2**21, 16.0)
I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot
    # be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_in_program(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _graph(n: int, m: int, d_ell: int, sharding) -> Graph:
    """A Graph of shapes: what the engine's jitted loop takes."""
    s = lambda shape, dt: _sds(shape, dt, sharding)  # noqa: E731
    return Graph(coo_src=s((m,), I32), coo_dst=s((m,), I32),
                 coo_w=s((m,), F32), in_ptr=s((n + 1,), I32),
                 push_src=s((m,), I32), push_dst=s((m,), I32),
                 push_w=s((m,), F32), out_ptr=s((n + 1,), I32),
                 ell_idx=s((n, d_ell), I32), ell_w=s((n, d_ell), F32),
                 in_deg=s((n,), I32), out_deg=s((n,), I32),
                 n=n, m=m, d_ell=d_ell)


def _engine(g, algorithm: str, policy: str, backend, **kw):
    spec = api.get_spec(algorithm)
    pol = api._resolve_policy(policy)
    program, steps = spec.build(g, policy=pol, backend=backend, **kw)
    return PushPullEngine(program=program, policy=pol, max_steps=steps,
                          backend=backend)


def _init(g, algorithm: str, sharding, **kw):
    spec = api.get_spec(algorithm)
    shapes = jax.eval_shape(lambda g_: spec.init(g_, **kw), g)
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), shapes)


# -- the three graph kernels -------------------------------------------
@pytest.mark.parametrize("combine,msg,dtype,width", [
    ("sum", "copy", F32, 1),     # pagerank pull
    ("min", "copy", I32, 1),     # bfs pull (candidate parent ids)
    ("min", "add", F32, 1),      # sssp min-plus relaxation
    ("sum", "copy", F32, 8),     # batched ppr columns
], ids=["sum-f32", "min-i32", "minplus-f32", "sum-f32-b8"])
def test_ell_spmv_compiles(one_chip, combine, msg, dtype, width):
    xs = (N + 1,) if width == 1 else (N + 1, width)
    c = ell_spmv_pallas.lower(
        _sds(xs, dtype, one_chip), _sds((N, D_ELL), I32, one_chip),
        _sds((N, D_ELL), F32, one_chip), combine=combine, msg=msg,
        block_n=512, interpret=False).compile()
    assert _kernel_in_program(c)


@pytest.mark.parametrize("combine,dtype", [("min", I32), ("sum", F32)])
def test_ell_pull_frontier_compiles(one_chip, combine, dtype):
    rows = default_pull_cap(N, M, D_ELL)
    c = ell_pull_frontier_pallas.lower(
        _sds((N + 1,), dtype, one_chip), _sds((N, D_ELL), I32, one_chip),
        _sds((N, D_ELL), F32, one_chip), _sds((rows,), I32, one_chip),
        combine=combine, msg="copy", block_r=1024,
        interpret=False).compile()
    assert _kernel_in_program(c)


@pytest.mark.parametrize("combine,dtype,strategy,width", [
    ("min", I32, "scan", 1),     # bfs push
    ("sum", F32, "scan", 1),     # pagerank push, VPU reduce
    ("sum", F32, "mxu", 1),      # pagerank push, one-hot matmul
    ("max", F32, "scan", 4),     # batched columns
])
def test_coo_push_compiles(one_chip, combine, dtype, strategy, width):
    block_e, bin_n = 1024, 128
    nb = N // bin_n
    cap = default_bin_cap(N, M, D_ELL, bin_n, block_e)
    plan = PushBinPlan(src=_sds((nb, cap), I32, one_chip),
                       dst=_sds((nb, cap), I32, one_chip),
                       w=_sds((nb, cap), F32, one_chip),
                       bin_n=bin_n, cap=cap, nb=nb)
    xs = (N,) if width == 1 else (N, width)
    c = coo_push_pallas.lower(
        _sds(xs, dtype, one_chip), _sds((N,), jnp.bool_, one_chip),
        _sds((M,), I32, one_chip), _sds((M,), I32, one_chip),
        _sds((M,), F32, one_chip), N, combine=combine, msg="copy",
        block_e=block_e, block_n=bin_n, interpret=False, plan=plan,
        strategy=strategy).compile()
    assert _kernel_in_program(c)


def test_compiled_kernels_refuse_64bit_payloads(one_chip):
    with pytest.raises(TypeError, match="ell_spmv_pallas.*32-bit"):
        ell_spmv_pallas.lower(
            _sds((N + 1,), jnp.float64, one_chip),
            _sds((N, D_ELL), I32, one_chip),
            _sds((N, D_ELL), F32, one_chip), combine="sum", msg="copy",
            block_n=512, interpret=False)


# -- whole engine loops ------------------------------------------------
@pytest.mark.parametrize("backend", ["dense", "pallas"])
@pytest.mark.parametrize("algorithm,policy,kw", [
    ("bfs", "auto", {"root": 0}),
    ("pagerank", "pull", {}),
])
def test_engine_compiles(one_chip, algorithm, policy, kw, backend):
    g = _graph(N, M, D_ELL, one_chip)
    be = (DenseBackend() if backend == "dense"
          else PallasBackend(interpret=False, autotune=False))
    eng = _engine(g, algorithm, policy, be)
    c = jax.jit(eng.run).lower(
        g, *_init(g, algorithm, one_chip, **kw)).compile()
    assert backend == "dense" or _kernel_in_program(c)
    # the graph's layouts plus the loop carry fit one chip's 16 GB
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_dense_bfs_push_gathers_a_materialized_masked_table(one_chip):
    """The compiled BFS push gathers no ``pred[m]`` frontier per edge: the
    masked wire table is its own fusion (scope ``push.premask``), which
    the value gather reads, not a select fused back into the gather."""
    n, m = 2 ** 16, 1_818_572        # the Graph500 Kronecker 2^16 cell
    g = _graph(n, m, 8, one_chip)
    eng = _engine(g, "bfs", "auto", DenseBackend())
    text = jax.jit(eng.run).lower(
        g, *_init(g, "bfs", one_chip, root=0)).compile().as_text()
    push = [ln for ln in text.splitlines() if "exchange.push" in ln]
    assert not [ln for ln in push if f"= pred[{m}]" in ln
                and (" gather(" in ln or "/gather" in ln)]
    assert [ln for ln in push if f"= s32[{n}]" in ln and " fusion(" in ln
            and "push.premask" in ln]


def _temp_bytes(fn, *args):
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    return None if mem is None else mem.temp_size_in_bytes


@pytest.mark.parametrize("width", [1, 8])
def test_frontier_push_compiles_in_every_capacity(one_chip, width):
    """Each capacity of the frontier push compiles with static shapes at
    the urand graph's size, within the temporary memory of the all-edge
    masked push it stands in for, and so does the switched push."""
    g = _graph(N, M, D_ELL, one_chip)
    x = _sds((N,) if width == 1 else (N, width), I32, one_chip)
    f = _sds((N,), jnp.bool_, one_chip)
    rank = ids = _sds((N,), I32, one_chip)
    dense = _temp_bytes(lambda g_, x_, f_: _masked_push(g_, x_, f_, "min"),
                        g, x, f)
    caps = push_caps(M)
    assert caps[0] >= 2 ** 10 and caps[-1] == M // 8
    for cap in caps:
        temp = _temp_bytes(lambda g_, x_, r_, i_, c=cap: _frontier_push(
            g_, x_, r_, i_, "min", c), g, x, rank, ids)
        assert dense is None or temp <= dense, (cap, temp, dense)
    # switched, the capacities' tables share the all-edge push's space;
    # what the compiler adds around the branches stays within 2%
    temp = _temp_bytes(lambda g_, x_, f_: push_relax(g_, x_, f_, "min")[0],
                       g, x, f)
    assert dense is None or temp <= 1.02 * dense, (temp, dense)


def test_sharded_bfs_auto_compiles_on_four_chips(mesh4):
    """The sharded engine's views enter its program as arguments, each
    [P, ...] view row-sharded over the mesh."""
    small = erdos_renyi(2 ** 14, 16.0, seed=0, weighted=True)
    views = build_topology(small, partition_1d(small.n, 4))
    rows = NamedSharding(mesh4, PartitionSpec("data"))
    views_s = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, rows), views)
    backend = ShardedBackend(mesh=mesh4, topo=views_s)
    full = NamedSharding(mesh4, PartitionSpec())
    g = _graph(small.n, small.m, small.d_ell, full)
    eng = _engine(g, "bfs", "auto", backend)
    c = type(eng)._run.lower(eng, g, *_init(g, "bfs", full, root=0),
                             views_s).compile()
    text = c.as_text()
    assert "all-gather" in text or "all-reduce" in text


# -- every candidate the tuner may probe on the chip ---------------------
def test_tuner_candidates_compile(one_chip):
    """A compiled probe that fails raises (it never degrades), so every
    rung of the compiled ladders must compile — at the probe's shape."""
    from repro.kernels.coo_push import build_push_plan
    from repro.kernels.tune import (_PROBE_N, pull_candidates,
                                    push_candidates)
    n = _PROBE_N
    m = M * n // N
    for block_n in pull_candidates(N, 1, d_ell=D_ELL, compiled=True):
        ell_spmv_pallas.lower(
            _sds((n + 1,), I32, one_chip), _sds((n, D_ELL), I32, one_chip),
            _sds((n, D_ELL), F32, one_chip), combine="min", msg="copy",
            block_n=block_n, interpret=False).compile()
    rng = np.random.default_rng(0)
    dst = np.sort(rng.integers(0, n, m)).astype(np.int32)
    for block_e, bin_n, strategy in push_candidates(N, M, compiled=True):
        plan = build_push_plan(dst, dst, np.ones(m, np.float32), n, bin_n,
                               align=block_e)
        shapes = jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), plan)
        for combine, dtype in (("min", I32), ("sum", F32)):
            coo_push_pallas.lower(
                _sds((n,), dtype, one_chip), _sds((n,), jnp.bool_, one_chip),
                _sds((m,), I32, one_chip), _sds((m,), I32, one_chip),
                _sds((m,), F32, one_chip), n, combine=combine, msg="copy",
                block_e=block_e, block_n=bin_n, interpret=False,
                plan=shapes, strategy=strategy).compile()
