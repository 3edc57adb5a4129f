"""Window wall time over the PageRank iterations the window completed
(every solve runs the traffic's ``iters``)."""


def read(run):
    if run.algorithm != "pagerank":
        return None
    iters = run.cell.traffic["params"]["iters"] * len(run.solves)
    return 1e3 * run.window_s / iters
