"""Engine steps per BFS root (``RunResult.steps``), mean over the
window's roots."""


def read(run):
    if run.algorithm != "bfs":
        return None
    return sum(s.steps for s in run.solves) / len(run.solves)
