"""The reduction from a profiler trace to busy time, ops and gaps."""

from __future__ import annotations

import pytest

from conftest import REPO
from bench.tracing import read_xspace, summarize

MS = 1_000_000  # ns
FIXTURE = REPO / "bench" / "tests" / "data" / "urand21-bfs.xplane.pb"


def test_union_self_time_and_gaps():
    ops = {0: [(1 * MS, 9 * MS, "jit__run:while.1"),   # holds the next two
               (1 * MS, 4 * MS, "jit__run:scatter.1"),
               (5 * MS, 8 * MS, "jit__run:gather.2"),
               (11 * MS, 13 * MS, "jit_add:add.3"),     # past the window
               (14 * MS, 15 * MS, "jit_add:add.3")]}    # outside it
    spans = [(0, 12 * MS, "bench.window"),
             (0, 1 * MS, "bench.request"),
             (1 * MS, 2 * MS, "bench.solve"),
             (2 * MS, 10 * MS, "bench.block"),
             (10 * MS, 12 * MS, "bench.fetch")]
    s = summarize(ops, spans)
    assert s.window_s == pytest.approx(0.012)
    assert s.busy_s == pytest.approx(0.008 + 0.001)
    assert dict((k, v) for k, v in s.top_ops) == pytest.approx(
        {"jit__run:scatter.1": 0.003, "jit__run:gather.2": 0.003,
         "jit__run:while.1": 0.002, "jit_add:add.3": 0.002})
    # gaps: 0-1 ms in request, 9-11 ms (its middle, 10 ms, is in block
    # and fetch: the shorter span wins)
    assert s.idle_gaps == [["bench.fetch", pytest.approx(0.002)],
                           ["bench.request", pytest.approx(0.001)]]


def test_devices_are_averaged():
    ops = {0: [(0, 2 * MS, "a")], 1: [(0, 4 * MS, "a")]}
    s = summarize(ops, [(0, 10 * MS, "bench.window")])
    assert s.busy_s == pytest.approx(0.003)
    assert s.top_ops == [["a", pytest.approx(0.003)]]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        summarize({0: []}, [])


def test_chip_trace():
    """A traced urand21-bfs run on a v5e chip: one root, 7 steps (5
    push, 2 pull). The run printed busy_s=14.227429 and
    window_s=14.243342 from this file."""
    ops, spans = read_xspace(FIXTURE, {0})
    assert {name for _, _, name in spans} == {
        "bench.window", "bench.request", "bench.solve", "bench.block",
        "bench.fetch"}
    s = summarize(ops, spans)
    assert s.busy_s == pytest.approx(14.227429, abs=1e-6)
    assert s.window_s == pytest.approx(14.243342, abs=1e-6)
    top = dict((k, v) for k, v in s.top_ops)
    # the engine loop's while and conditional hold the step's ops, so
    # their own time is small; the gathers and scatters hold the rest
    assert top.get("jit__run:while.6", 0) < 0.01
    assert s.top_ops[0][0] == "jit__run:fusion.2"
    assert s.top_ops[0][1] == pytest.approx(6.734, abs=0.01)
    assert sum(top.values()) == pytest.approx(s.busy_s, rel=0.01)
    assert s.idle_gaps[0][0] == "bench.fetch"
    assert all(g[1] < 0.003 for g in s.idle_gaps)
