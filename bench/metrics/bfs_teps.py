"""Graph500 TEPS as a rate: the undirected edges of each completed
root's connected component, summed over the window, over the window's
wall time from the first call to the last block."""


def read(run):
    if run.algorithm != "bfs":
        return None
    edges = run.host.component_edges
    return sum(edges[s.kwargs["root"]] for s in run.solves) / run.window_s
