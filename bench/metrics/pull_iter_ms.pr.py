"""Device own time under the program's ``exchange.pull`` scope per
PageRank iteration of the traced window, in ms (``bench/scopes.py``).
None where the program names no such scope."""

from bench import scopes


def read(run):
    if run.algorithm != "pagerank" or run.trace is None:
        return None
    own = scopes.for_run(run).own_s.get("exchange.pull")
    iters = run.cell.traffic["params"]["iters"] * len(run.solves)
    return None if own is None else 1e3 * own / iters
