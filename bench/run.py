#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. With ``--trace 0`` the line's metrics are
the cell's end-to-end metrics; with ``--trace 1`` the window runs under
the profiler and the metrics are the cell's per-layer ones. The run
exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoAccelerator, print_line, run_cell
    from bench.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        t0=T0)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print_line(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
