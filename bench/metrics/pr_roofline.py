"""Share of the HBM roofline one PageRank iteration reaches.

The least bytes one iteration's work must move, whatever the layout:
``m`` 32-bit neighbour ids, plus ``n`` 32-bit contributions read once
and ``n`` 32-bit ranks written once. Padding slots of any layout are
not counted. Priced at the chip's published HBM bandwidth, over the
device busy time per iteration in the traced window.
"""


def iteration_bytes(n: int, m: int) -> int:
    return 4 * m + 8 * n


def read(run):
    if run.algorithm != "pagerank" or run.trace is None:
        return None
    least_s = (iteration_bytes(run.host.n, run.host.m)
               / run.peaks["hbm_bytes_per_s"])
    iters = run.cell.traffic["params"]["iters"] * len(run.solves)
    return 100.0 * least_s / (run.trace.busy_s / iters)
