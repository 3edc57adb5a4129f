"""The reduction from a profiler trace to the program's scopes and
spans (``bench/scopes.py``) and the metrics that read it."""

from __future__ import annotations

import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from conftest import REPO
from bench import scopes, tracing
from bench.spec import load_module

MS = 1_000_000  # ns
DATA = REPO / "bench" / "tests" / "data"
URAND21 = DATA / "urand21-bfs.xplane.pb"
KRON16 = DATA / "kron16-bfs.xplane.pb"
SOLVE_SPANS = ("repro.solve", "repro.solve.prepare", "repro.solve.init",
               "repro.solve.run", "repro.solve.finalize")
NEW_METRICS = ("push_step_ms.bfs", "pull_step_ms.bfs", "solve_idle_ms.bfs",
               "launches_per_root.bfs")


def test_op_paths_on_the_urand21_chip_trace():
    """The fixture predates the program's scopes: the ``tf_op`` paths
    still tell the directions apart by ``lax.cond`` branch, push being
    ``branch_1_fun``, and every op reads as unscoped."""
    named, _ = tracing.read_xspace(URAND21, {0})
    ops, modules, spans = scopes.read_xspace(URAND21, {0})
    assert [(s, e) for s, e, _ in named[0]] == [(s, e) for s, e, _ in ops[0]]
    path = {name.split(":")[1]: p
            for (_, _, name), (_, _, p) in zip(named[0], ops[0])}
    for op in ("fusion.2", "fusion.4", "fusion.6"):
        assert "/while/body/cond/branch_1_fun/" in path[op]
    for op in ("fusion.3", "fusion.5"):
        assert "/while/body/cond/branch_0_fun/" in path[op]
    assert path["copy-start"] == ""          # added by the compiler
    sc = scopes.reduce(ops, modules, spans)
    assert sc.busy_s == pytest.approx(14.227429, abs=1e-6)
    assert sc.window_s == pytest.approx(14.243342, abs=1e-6)
    assert set(sc.own_s) == {scopes.UNSCOPED}
    assert sc.own_s[scopes.UNSCOPED] == pytest.approx(sc.busy_s, rel=1e-9)
    # one root: the engine's loop and 26 small programs, all launched
    # while the host was in bench.solve
    assert sc.launches == {"bench.solve": 27}
    assert sc.spans == {"bench.request": 1, "bench.solve": 1,
                        "bench.block": 1, "bench.fetch": 1}
    assert sc.solve_idle_s == 0.0
    assert sum(sc.idle_s.values()) == pytest.approx(
        sc.window_s - sc.busy_s, abs=1e-9)
    assert max(sc.idle_s, key=sc.idle_s.get) == "bench.solve"


def test_scopes_and_spans_on_the_kron16_chip_trace():
    """A traced kron16-bfs run of one root (6 steps, 4 push) on a v5e
    chip, by a program that names its scopes and spans. The run printed
    busy_s=0.221507 and window_s=0.236525 from this file."""
    named, named_spans = tracing.read_xspace(KRON16, {0})
    ops, modules, spans = scopes.read_xspace(KRON16, {0})
    sc = scopes.reduce(ops, modules, spans)
    summary = tracing.summarize(named, named_spans)
    assert (sc.busy_s, sc.window_s) == (summary.busy_s, summary.window_s)
    assert sc.busy_s == pytest.approx(0.221507, abs=1e-6)
    assert sc.window_s == pytest.approx(0.236525, abs=1e-6)
    # every scope holds device time; the directions hold nearly all
    assert set(scopes.SCOPES) <= set(sc.own_s)
    assert all(sc.own_s[name] > 0 for name in scopes.SCOPES)
    both = sc.own_s["exchange.push"] + sc.own_s["exchange.pull"]
    assert both >= 0.95 * sc.busy_s
    assert sc.own_s[scopes.UNSCOPED] < 0.03 * sc.busy_s
    scope = {name.split(":")[1]: scopes._scope_of(p)
             for (_, _, name), (_, _, p) in zip(named[0], ops[0])}
    assert {scope[f"fusion.{i}"] for i in (2, 4, 6)} == {"exchange.push"}
    assert {scope[f"fusion.{i}"] for i in (3, 5)} == {"exchange.pull"}
    # the host spans of api.solve, once each, inside bench.solve
    assert {name: sc.spans[name] for name in SOLVE_SPANS} == dict.fromkeys(
        SOLVE_SPANS, 1)
    (outer,) = [sp for sp in spans if sp[2] == "bench.solve"]
    inner = [sp for sp in spans if sp[2] in SOLVE_SPANS]
    assert all(outer[0] <= s and e <= outer[1] for s, e, _ in inner)
    # the same 27 programs as before the scopes: the engine's loop, and
    # 26 small ones that the algorithm's init starts
    assert sc.n_launches == 27
    assert sc.launches == {"repro.solve.init": 26, "repro.solve.run": 1}
    # most of the idle time inside api.solve is the init's
    assert sc.solve_idle_s == pytest.approx(0.012586, abs=1e-6)
    assert sc.idle_s["repro.solve.init"] > 0.8 * sc.solve_idle_s


def test_nested_spans_take_idle_time_and_launches():
    """Idle gaps are cut at span boundaries and each piece goes to the
    innermost span; a launch goes to the span holding its start."""
    step = "jit(_run)/while/body/engine.step"
    ops = {0: [(3 * MS, 9 * MS, "jit(_run)/while"),
               (3 * MS, 5 * MS, f"{step}/cond/branch_1_fun/exchange.push/"
                                "jit(_take)/gather:"),
               (5 * MS, 6 * MS, f"{step}/policy.decide/reduce_sum:"),
               (6 * MS, 8 * MS, f"{step}/cond/branch_0_fun/exchange.pull/"
                                "scatter-min:"),
               (8 * MS, 9 * MS, f"{step}/program.update/select_n:"),
               (2 * MS, int(2.5 * MS), "jit(broadcast_in_dim)/"
                                       "broadcast_in_dim:")]}
    modules = {0: [2 * MS, 3 * MS, 11 * MS]}
    spans = [(0, 12 * MS, "bench.window"),
             (0, 1 * MS, "bench.request"),
             (1 * MS, 4 * MS, "bench.solve"),
             (1 * MS, 4 * MS, "repro.solve#solve=7#"),
             (1 * MS, 2 * MS, "repro.solve.prepare"),
             (2 * MS, 3 * MS, "repro.solve.init"),
             (3 * MS, int(3.5 * MS), "repro.solve.run"),
             (int(3.5 * MS), 4 * MS, "repro.solve.finalize"),
             (4 * MS, 10 * MS, "bench.block"),
             (10 * MS, 12 * MS, "bench.fetch")]
    sc = scopes.reduce(ops, modules, spans)
    assert sc.busy_s == pytest.approx(0.0065)
    assert sc.own_s == pytest.approx({
        "exchange.push": 0.002, "policy.decide": 0.001,
        "exchange.pull": 0.002, "program.update": 0.001,
        scopes.UNSCOPED: 0.0005})
    # idle: 0-2 ms (request, prepare), 2.5-3 ms (init), 9-12 ms (block,
    # fetch); the ``#solve=7#`` metadata is cut from the span's name
    assert sc.idle_s == pytest.approx({
        "bench.request": 0.001, "repro.solve.prepare": 0.001,
        "repro.solve.init": 0.0005, "bench.block": 0.001,
        "bench.fetch": 0.002})
    assert sc.solve_idle_s == pytest.approx(0.0015)
    assert sc.launches == {"repro.solve.init": 1, "repro.solve.run": 1,
                           "bench.fetch": 1}
    assert sc.n_launches == 3
    assert sc.spans["repro.solve"] == 1


def _traced_run(tmp_path, trace: Path, steps: int, push_steps: int):
    """A traced harness run as the metric readers see it: the trace
    where the harness writes it, and one root."""
    dest = tmp_path / "bench" / ".trace" / "cell" / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    shutil.copy(trace, dest / "host.xplane.pb")
    cell = SimpleNamespace(root=tmp_path, name="cell", chips=1)
    return SimpleNamespace(
        cell=cell, algorithm="bfs", trace=object(),
        solves=[SimpleNamespace(steps=steps, push_steps=push_steps)])


def _read(name, run):
    return load_module(REPO / "bench" / "metrics" / f"{name}.py").read(run)


def test_new_metrics_on_a_program_without_scopes(tmp_path):
    """A parent program names neither scopes nor spans: the new readers
    give nothing there, save the launch count, and raise nothing."""
    run = _traced_run(tmp_path, URAND21, steps=7, push_steps=5)
    got = {name: _read(name, run) for name in NEW_METRICS}
    assert got == {"push_step_ms.bfs": None, "pull_step_ms.bfs": None,
                   "solve_idle_ms.bfs": None, "launches_per_root.bfs": 27.0}


def test_new_metrics_on_the_kron16_chip_trace(tmp_path):
    run = _traced_run(tmp_path, KRON16, steps=6, push_steps=4)
    got = {name: _read(name, run) for name in NEW_METRICS}
    assert got == pytest.approx({
        "push_step_ms.bfs": 163.541869 / 4, "pull_step_ms.bfs": 57.849176 / 2,
        "solve_idle_ms.bfs": 12.585854, "launches_per_root.bfs": 27.0})


def test_new_metrics_read_nothing_untraced(tmp_path):
    run = _traced_run(tmp_path, URAND21, steps=7, push_steps=5)
    run.trace = None
    assert all(_read(name, run) is None for name in NEW_METRICS)



URAND21_PR = DATA / "urand21-pr.xplane.pb"


def _pr_run(tmp_path, trace: Path):
    """A traced PageRank run of one 20-iteration solve, as the readers
    see it."""
    run = _traced_run(tmp_path, trace, steps=20, push_steps=0)
    run.algorithm = "pagerank"
    run.cell.traffic = {"params": {"iters": 20}}
    return run


def test_pull_iter_ms_on_the_urand21_pr_chip_trace(tmp_path):
    """A traced urand21-pr run on a v5e chip: one solve of 20 pull
    iterations over 67M edges. The run printed busy_s=21.814473 and
    pull_iter_ms.pr 1090.6858448 from this file."""
    ops, modules, spans = scopes.read_xspace(URAND21_PR, {0})
    sc = scopes.reduce(ops, modules, spans)
    assert sc.busy_s == pytest.approx(21.814473, abs=1e-6)
    # the pull is all but ~0.003% of the device's work
    assert sc.own_s["exchange.pull"] >= 0.9999 * sc.busy_s
    assert "exchange.push" not in sc.own_s
    got = _read("pull_iter_ms.pr", _pr_run(tmp_path, URAND21_PR))
    assert got == pytest.approx(1090.6858448, rel=1e-9)
    assert got == pytest.approx(1e3 * sc.own_s["exchange.pull"] / 20)


def test_pull_iter_ms_on_a_program_without_scopes(tmp_path):
    assert _read("pull_iter_ms.pr", _pr_run(tmp_path, URAND21)) is None


def test_pull_iter_ms_reads_nothing_for_bfs(tmp_path):
    run = _traced_run(tmp_path, KRON16, steps=6, push_steps=4)
    run.cell.traffic = {"params": {}}
    assert _read("pull_iter_ms.pr", run) is None


def test_pull_iter_ms_reads_nothing_untraced(tmp_path):
    run = _pr_run(tmp_path, URAND21_PR)
    run.trace = None
    assert _read("pull_iter_ms.pr", run) is None
