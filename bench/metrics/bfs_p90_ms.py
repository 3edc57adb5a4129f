"""90th percentile of per-root wall time, call to block, over every
root in the window (numpy's linear interpolation)."""

import numpy as np


def read(run):
    if run.algorithm != "bfs":
        return None
    return 1e3 * float(np.percentile(
        [s.t_end - s.t_call for s in run.solves], 90))
