#!/usr/bin/env python3
"""Read the program's numbers and the control's on several seeds of one
cell, in one process, at the cell's own size on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed it makes a whole run of the cell (set-up, a window of
``--seconds``, the check) and then puts the cell's control, the plain
reference with one guarantee broken or computed in the next precision
down, in the program's place on the same sample. It prints one JSON
line per seed with both sets of numbers beside the limits. The limits
in the traffic files were set from such readings; the benchmark's own
runs never run the control.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench.harness import NoAccelerator, run_cell
    from bench.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    for seed in args.seeds:
        t0 = T0 if seed == args.seeds[0] else time.perf_counter()
        try:
            line = run_cell(cell, seed, args.seconds, False, t0=t0,
                            control=True)
        except NoAccelerator as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "program": {k: c["value"] for k, c in
                                      line["checks"].items()},
                          "control": line["control"],
                          "limits": {k: c["limit"] for k, c in
                                     line["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
