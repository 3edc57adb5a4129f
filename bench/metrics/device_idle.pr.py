"""Idle share of the device in a traced PageRank window: 1 - busy /
window, in %."""


def read(run):
    if run.algorithm != "pagerank" or run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
