"""Device own time under the program's ``exchange.push`` scope per push
step of the traced BFS window, in ms (``bench/scopes.py``). None where
the program names no such scope."""

from bench import scopes


def read(run):
    if run.algorithm != "bfs" or run.trace is None:
        return None
    own = scopes.for_run(run).own_s.get("exchange.push")
    steps = sum(s.push_steps for s in run.solves)
    return None if own is None or steps == 0 else 1e3 * own / steps
