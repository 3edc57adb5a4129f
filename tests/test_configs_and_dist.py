"""Assigned-arch smoke tests (deliverable f) + cell/dist invariants."""

import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ALL_ARCHS, ARCH_FAMILY, all_cells, full_config,
                           smoke_config)
from repro.graphs import erdos_renyi
from repro.models import gnn as gnn_mod
from repro.models.recsys import xdeepfm_apply, xdeepfm_init
from repro.models.transformer import init_params, lm_loss
from repro.train import OptConfig, apply_updates, init_opt

KEY = jax.random.PRNGKey(0)


def test_cell_enumeration_is_40():
    cells = all_cells()
    assert len(cells) == 40
    per_family = {}
    for a, s in cells:
        per_family.setdefault(ARCH_FAMILY[a], set()).add(s)
    assert len(per_family["lm"]) == 4
    assert len(per_family["gnn"]) == 4
    assert len(per_family["recsys"]) == 4


def test_full_configs_match_assignment():
    c = full_config("llama3.2-1b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (16, 2048, 32, 8, 8192, 128256)
    c = full_config("qwen1.5-32b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (64, 5120, 40, 40, 27392, 152064)
    assert c.qkv_bias
    c = full_config("gemma2-9b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (42, 3584, 16, 8, 14336, 256000)
    assert c.local_window == 4096 and c.attn_softcap and c.final_softcap
    for arch, L, V in (("moonshot-v1-16b-a3b", 48, 163840),
                       ("deepseek-moe-16b", 28, 102400)):
        c = full_config(arch)
        assert (c.n_layers, c.d_model, c.vocab) == (L, 2048, V)
        assert c.moe.n_experts == 64 and c.moe.top_k == 6
        assert c.moe.n_shared == 2
    g = full_config("graphcast")
    assert g.n_layers == 16 and g.d_hidden == 512 and g.n_vars == 227
    x = full_config("xdeepfm")
    assert x.n_fields == 39 and x.cin_layers == (200, 200, 200)
    assert x.mlp_dims == (400, 400) and x.embed_dim == 10


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS
                                  if ARCH_FAMILY[a] == "lm"])
def test_smoke_lm_train_step(arch):
    """Reduced same-family config: one forward + optimizer step on CPU."""
    cfg = smoke_config(arch)
    p = init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    oc = OptConfig(lr=1e-3, total_steps=10)
    opt = init_opt(p, oc)
    loss, grads = jax.value_and_grad(
        lambda pp: lm_loss(pp, cfg, toks, toks))(p)
    p2, opt2 = apply_updates(p, grads, opt, oc)
    assert bool(jnp.isfinite(loss))
    assert loss.shape == ()
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)):
        assert a.shape == b.shape
        assert bool(jnp.all(jnp.isfinite(b.astype(jnp.float32))))


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS
                                  if ARCH_FAMILY[a] == "gnn"])
def test_smoke_gnn_train_step(arch):
    cfg = smoke_config(arch)
    g = erdos_renyi(60, 4.0, seed=3, weighted=True)
    init_fn = {"egnn": gnn_mod.egnn_init, "gin-tu": gnn_mod.gin_init,
               "graphsage-reddit": gnn_mod.sage_init,
               "graphcast": gnn_mod.graphcast_init}[arch]
    p = init_fn(KEY, cfg)

    if arch == "graphcast":
        nv = jax.random.normal(KEY, (g.n, cfg.n_vars))
        fn = lambda pp: jnp.mean(  # noqa: E731
            (gnn_mod.graphcast_apply(pp, cfg, g, nv) - nv) ** 2)
    elif arch == "egnn":
        h = jax.random.normal(KEY, (g.n, cfg.d_in))
        x = jax.random.normal(jax.random.fold_in(KEY, 1), (g.n, 3))
        fn = lambda pp: jnp.mean(  # noqa: E731
            gnn_mod.egnn_apply(pp, cfg, g, h, x)[0] ** 2)
    else:
        h = jax.random.normal(KEY, (g.n, cfg.d_in))
        apply_fn = (gnn_mod.gin_apply if arch == "gin-tu"
                    else gnn_mod.sage_apply)
        fn = lambda pp: jnp.mean(apply_fn(pp, cfg, g, h) ** 2)  # noqa

    loss, grads = jax.value_and_grad(fn)(p)
    oc = OptConfig(lr=1e-3, total_steps=10)
    p2, _ = apply_updates(p, grads, init_opt(p, oc), oc)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in jax.tree.leaves(p2))


def test_smoke_recsys_train_step():
    cfg = smoke_config("xdeepfm")
    p = xdeepfm_init(KEY, cfg)
    ids = jax.random.randint(KEY, (32, cfg.n_fields), 0,
                             cfg.vocab_per_field)
    y = jax.random.bernoulli(KEY, 0.3, (32,)).astype(jnp.float32)
    from repro.train.losses import bce_with_logits
    loss, grads = jax.value_and_grad(
        lambda pp: bce_with_logits(xdeepfm_apply(pp, cfg, ids), y))(p)
    assert bool(jnp.isfinite(loss))
    oc = OptConfig(lr=1e-3, total_steps=10)
    p2, _ = apply_updates(p, grads, init_opt(p, oc), oc)
    assert p2["tables"].shape == p["tables"].shape


DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.graphs import erdos_renyi, partition_1d, pa_split
from repro.graphs.partition import _pack
from repro.dist.collectives import push_exchange, pull_exchange
from repro.dist.overlap import ring_allreduce_psum
from jax.sharding import PartitionSpec as P
import functools

from jax.sharding import AxisType
AUTO = (AxisType.Auto,) * 2
mesh = jax.make_mesh((8, 1), ("data", "model"), axis_types=AUTO)
g = erdos_renyi(128, 4.0, seed=5, weighted=True)
part = partition_1d(g.n, 8)
local, remote, stats = pa_split(g, part)
vals = jnp.arange(part.n_padded, dtype=jnp.float32) % 7 + 1.0

# reference: full masked segment sum over remote edges
import numpy as onp
src = onp.asarray(g.push_src); dst = onp.asarray(g.push_dst); w = onp.asarray(g.push_w)
own_s = part.owner_np(src); own_d = part.owner_np(dst)
cut = own_s != own_d
want = onp.zeros(part.n_padded, onp.float32)
onp.add.at(want, dst[cut], onp.asarray(vals)[src[cut]] * w[cut])

out, nbytes = push_exchange(mesh, part, remote, vals)
ok_push = bool(onp.allclose(onp.asarray(out), want, atol=1e-4))
print("push_exchange ok:", ok_push, "bytes:", nbytes)

# pull: group the same cut edges by destination shard
rows = [[] for _ in range(8)]; cols = [[] for _ in range(8)]; ws = [[] for _ in range(8)]
for s, d, ww in zip(src[cut], dst[cut], w[cut]):
    p = int(part.owner_np(onp.array([d]))[0])
    rows[p].append(s); cols[p].append(d); ws[p].append(ww)
rows = [onp.array(r, onp.int64) for r in rows]
cols = [onp.array(c, onp.int64) for c in cols]
ws = [onp.array(x, onp.float32) for x in ws]
edges_by_dst = _pack(rows, cols, ws, 8, g.n, 128)
out2, nbytes2 = pull_exchange(mesh, part, edges_by_dst, vals)
ok_pull = bool(onp.allclose(onp.asarray(out2), want, atol=1e-4))
print("pull_exchange ok:", ok_pull, "bytes:", nbytes2)

# ring allreduce equals psum (each device holds a distinct 8-vector)
x = jnp.arange(64, dtype=jnp.float32)
@functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
def ring(xb):
    return ring_allreduce_psum(xb.reshape(-1), "data", 8).reshape(xb.shape)
@functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
def psum_ref(xb):
    return jax.lax.psum(xb, "data")
a = ring(x); b = psum_ref(x)
print("ring==psum:", bool(onp.allclose(onp.asarray(a), onp.asarray(b))))
assert ok_push and ok_pull

# MoE EP paths vs the single-device reference (psum + a2a schedules)
import dataclasses
from repro.models.moe import MoEConfig, moe_init, moe_apply, moe_apply_ep
from repro.dist.sharding import set_activation_mesh
mesh2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO)
cfg = MoEConfig(d_model=16, d_ff_expert=8, n_experts=8, top_k=2,
                n_shared=1, capacity_factor=8.0, dispatch="pull")
params = moe_init(jax.random.PRNGKey(0), cfg)
xx = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
ref = moe_apply(params, cfg, xx)
set_activation_mesh(mesh2)
got_psum = moe_apply_ep(params, cfg, xx)
got_a2a = moe_apply_ep(params, dataclasses.replace(cfg, ep_mode="a2a"), xx)
set_activation_mesh(None)
# capacity_factor is generous so neither schedule drops tokens
print("moe psum-ep ok:", bool(onp.allclose(onp.asarray(got_psum),
                                           onp.asarray(ref), atol=1e-4)))
print("moe a2a-ep ok:", bool(onp.allclose(onp.asarray(got_a2a),
                                          onp.asarray(ref), atol=1e-4)))
"""


@pytest.mark.dist
@pytest.mark.subprocess
def test_dist_exchanges_multidevice():
    """shard_map exchanges need >1 device: run in a subprocess with 8
    fake host devices."""
    import os
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", DIST_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=str(root))
    assert "push_exchange ok: True" in r.stdout, r.stdout + r.stderr
    assert "pull_exchange ok: True" in r.stdout, r.stdout + r.stderr
    assert "ring==psum: True" in r.stdout, r.stdout + r.stderr
    assert "moe psum-ep ok: True" in r.stdout, r.stdout + r.stderr
    assert "moe a2a-ep ok: True" in r.stdout, r.stdout + r.stderr
