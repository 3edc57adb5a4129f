"""Device programs launched (``XLA Modules`` events) in the traced BFS
window per root (``bench/scopes.py``): the engine's loop and the small
programs ``api.solve`` starts around it."""

from bench import scopes


def read(run):
    if run.algorithm != "bfs" or run.trace is None:
        return None
    return scopes.for_run(run).n_launches / len(run.solves)
