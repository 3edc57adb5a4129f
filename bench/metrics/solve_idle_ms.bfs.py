"""Device idle time inside the program's ``repro.solve`` host spans
(``api.solve``, up to its return) per BFS root of the traced window, in
ms (``bench/scopes.py``). None where the program opens no such span."""

from bench import scopes


def read(run):
    if run.algorithm != "bfs" or run.trace is None:
        return None
    sc = scopes.for_run(run)
    if "repro.solve" not in sc.spans:
        return None
    return 1e3 * sc.solve_idle_s / len(run.solves)
