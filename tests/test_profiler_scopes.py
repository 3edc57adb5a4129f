"""The program's profiler names: ``jax.named_scope`` scopes on the
engine step and the exchange directions (compile-time op metadata), and
``TraceAnnotation`` host spans on ``api.solve`` and ``Telemetry.span``.
The benchmark's ``bench/scopes.py`` reads both from a chip trace."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import Direction, GenericSwitch
from repro.core.backend import DenseBackend, EllBackend
from repro.core.cost_model import Cost
from repro.core.engine import PushPullEngine
from repro.dist.compression import CompressionConfig
from repro.graphs import kronecker
from repro.obs import Telemetry
from repro.shard import ShardedBackend

SOLVE_SPANS = ("repro.solve", "repro.solve.prepare", "repro.solve.init",
               "repro.solve.run", "repro.solve.finalize")


@pytest.fixture(scope="module")
def g():
    return kronecker(7, edge_factor=8, seed=5)


def _op_names(hlo_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def test_engine_step_scopes_in_hlo_metadata(g):
    """BFS under GenericSwitch on the dense backend: every op of the
    step carries the scope of the layer that emitted it."""
    spec = api.get_spec("bfs")
    policy = GenericSwitch()
    program, steps = spec.build(g, policy=policy, backend=DenseBackend())
    eng = PushPullEngine(program=program, policy=policy, max_steps=steps,
                         backend=DenseBackend())
    state, frontier = spec.init(g, root=0)
    names = _op_names(type(eng)._run.lower(
        eng, g, state, frontier, eng.backend.operands()).compile().as_text())
    step = "jit(_run)/while/body/engine.step"
    assert any(n.startswith(f"{step}/policy.decide/") for n in names)
    assert any(n.startswith(f"{step}/program.update/") for n in names)
    for direction in ("exchange.push", "exchange.pull"):
        ops = [n for n in names if f"/{direction}/" in n]
        assert ops and all(n.startswith(f"{step}/cond/") for n in ops)
    # the step's gathers and scatter-mins sit in a direction's scope
    for op in ("gather", "scatter-min"):
        assert all("/exchange.p" in n for n in names
                   if n.endswith(op) and n.startswith(step))


def _relax_op_names(backend, g, direction) -> set:
    """Op names of one ``relax_ex`` call; ``direction`` None switches on
    a traced bool, as a switching policy does."""
    values = jnp.linspace(0.0, 1.0, g.n, dtype=jnp.float32)
    frontier = jnp.arange(g.n) % 3 == 0

    def relax(v, f, push):
        return backend.relax_ex(
            g, v, f, direction=push if direction is None else direction,
            combine="sum", msg_fn=lambda x, w: x, cost=Cost(),
            xstate=backend.init_exchange_state(g))
    lowered = jax.jit(relax).lower(values, frontier, jnp.bool_(True))
    return _op_names(lowered.compile().as_text())


@pytest.mark.parametrize("make", [
    lambda g: DenseBackend(),
    lambda g: EllBackend(),
    lambda g: ShardedBackend.prepare(g, num_shards=1),
    lambda g: ShardedBackend.prepare(
        g, num_shards=1, compression=CompressionConfig(kind="int8")),
], ids=["dense", "ell", "shard", "shard-int8"])
@pytest.mark.parametrize("direction", [None, Direction.PUSH, Direction.PULL],
                         ids=["switched", "push", "pull"])
def test_backend_directions_carry_their_scope(g, make, direction):
    """A switched (traced) direction and a static one both name each
    direction's ops, on every backend's relax path."""
    names = _relax_op_names(make(g), g, direction)
    want = {None: {"exchange.push", "exchange.pull"},
            Direction.PUSH: {"exchange.push"},
            Direction.PULL: {"exchange.pull"}}[direction]
    seen = {s for s in ("exchange.push", "exchange.pull")
            if any(f"/{s}/" in n for n in names)}
    assert seen == want


@pytest.mark.parametrize("combine,want", [
    ("min", {"push.frontier", "push.premask"}), ("sum", {"push.premask"})])
def test_push_paths_carry_their_scope(combine, want):
    """On a graph with push capacities a min push compiles both of its
    paths, each under its own scope inside ``exchange.push``; a sum push
    has the all-edge path alone."""
    big = kronecker(10, edge_factor=16, seed=4)

    def push(v, f):
        return DenseBackend().relax(big, v, f, direction=Direction.PUSH,
                                    combine=combine)[0]
    names = _op_names(jax.jit(push).lower(
        jnp.zeros(big.n, jnp.int32), jnp.arange(big.n) % 5 == 0
    ).compile().as_text())
    seen = {s for s in ("push.frontier", "push.premask")
            if any(f"/exchange.push/" in n and f"/{s}/" in n
                   for n in names)}
    assert seen == want


def _host_spans(log_dir) -> list:
    """``[(start_ns, end_ns, name, stats)]`` of the host planes."""
    from jax.profiler import ProfileData
    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [(ev.start_ns, ev.start_ns + ev.duration_ns,
             ev.name.split("#")[0], dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_solve_emits_nested_host_spans(g, tmp_path):
    api.solve(g, "bfs", root=0)                  # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for root in (1, 2):
            jax.block_until_ready(api.solve(g, "bfs", root=root).state)
    finally:
        jax.profiler.stop_trace()
    spans = [sp for sp in _host_spans(tmp_path)
             if sp[2].startswith("repro.")]
    assert sorted({sp[2] for sp in spans}) == sorted(SOLVE_SPANS)
    solves = sorted(sp for sp in spans if sp[2] == "repro.solve")
    assert len(solves) == 2
    # the calls are numbered one after the other
    ids = [sp[3]["solve"] for sp in solves]
    assert ids[1] == ids[0] + 1
    for s, e, _, _ in solves:
        kids = sorted(sp for sp in spans
                      if sp[2] != "repro.solve" and s <= sp[0] <= e)
        assert [k[2] for k in kids] == list(SOLVE_SPANS[1:])
        assert all(k[1] <= e for k in kids)
        # the children follow each other without overlap
        assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def test_telemetry_span_lands_in_ring_and_profiler(g, tmp_path):
    tel = Telemetry()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tel.span("custom", tag=1) as sp:
            sp["seen"] = True
        api.solve(g, "bfs", root=0, telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    ring = [e for e in tel.events if e["kind"] == "span"]
    assert [e["name"] for e in ring] == ["custom", "solve:bfs"]
    assert ring[0]["tag"] == 1 and ring[0]["seen"] is True
    assert ring[0]["dur_us"] >= 0
    spans = _host_spans(tmp_path)
    assert {"custom", "solve:bfs"} <= {sp[2] for sp in spans}
    # the handle's span nests inside the solve's run span
    (run,) = [sp for sp in spans if sp[2] == "repro.solve.run"]
    (obs,) = [sp for sp in spans if sp[2] == "solve:bfs"]
    assert run[0] <= obs[0] and obs[1] <= run[1]
