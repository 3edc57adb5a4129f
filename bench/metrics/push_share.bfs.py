"""Share of the window's BFS engine steps that the direction policy ran
as push (``RunResult.push_steps / steps``), in %."""


def read(run):
    if run.algorithm != "bfs":
        return None
    steps = sum(s.steps for s in run.solves)
    return 100.0 * sum(s.push_steps for s in run.solves) / steps
