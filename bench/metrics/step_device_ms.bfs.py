"""Device busy time per BFS engine step: the union of device op
intervals in the traced window over the engine steps of its roots."""


def read(run):
    if run.algorithm != "bfs" or run.trace is None:
        return None
    return 1e3 * run.trace.busy_s / sum(s.steps for s in run.solves)
