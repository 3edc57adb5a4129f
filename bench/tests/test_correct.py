"""``correct`` comes out false for the control and for a broken timed
path: whole runs of the tiny cells, the look for a chip skipped."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from conftest import TINY_CELLS, run_tiny
from repro.core.engine import PushPullEngine


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_control_fails_where_the_program_passes(tiny_root, cell):
    line = run_tiny(tiny_root, cell, control=True)
    assert line["correct"] is True
    limits = {k: c["limit"] for k, c in line["checks"].items()}
    assert any(line["control"][k] > limits[k] for k in limits)


def _state_unchanged(orig):
    """Every step hands back the state it was given."""
    def run(self, g, init_state, init_frontier):
        return orig(self, g, init_state, init_frontier)._replace(
            state=init_state)
    return run


def _answer_altered(orig):
    """One vertex of the answer changed where the engine produces it:
    the farthest BFS level one hop longer, one rank 0.1% off."""
    def run(self, g, init_state, init_frontier):
        res = orig(self, g, init_state, init_frontier)
        state = res.state
        if isinstance(state, dict):
            dist = state["dist"]
            far = jnp.argmax(jnp.where(dist < 2**31 - 1, dist, -1))
            state = dict(state, dist=dist.at[far].add(1))
        else:
            state = state.at[0].multiply(1.001)
        return res._replace(state=state)
    return run


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered])
@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault):
    monkeypatch.setattr(PushPullEngine, "run",
                        fault(PushPullEngine.run))
    line = run_tiny(tiny_root, cell)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
