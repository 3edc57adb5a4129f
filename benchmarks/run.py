"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus human tables); with
``--json PATH`` the same rows are written as a machine-readable report
validating against ``benchmarks/schema.json``, so perf trajectories
(``BENCH_*.json``) can be produced mechanically. ``--markdown PATH``
additionally renders the report as tables (the docs/results.md
snapshot); ``--smoke`` shrinks suites that honor it to CI-sized graphs.

    PYTHONPATH=src python -m benchmarks.run [--only NAME[,NAME...]]
        [--smoke] [--json PATH] [--markdown PATH]

One command per artifact — the committed BENCH_pushpull.json baseline
is the full decision-matrix sweep, BENCH_kernels.json is the full
wall-clock kernel sweep, and the committed docs/results.md snapshot is
the smoke sweep (decision matrix + service throughput + kernel tables):

    PYTHONPATH=src python -m benchmarks.run --only pushpull_matrix \
        --json BENCH_pushpull.json
    PYTHONPATH=src python -m benchmarks.run --only kernels \
        --json BENCH_kernels.json
    PYTHONPATH=src python -m benchmarks.run \
        --only pushpull_matrix,service_throughput,kernels \
        --smoke --json /tmp/BENCH_smoke.json --markdown docs/results.md
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from repro.compile_cache import enable_compile_cache

SUITES = [
    ("api_solve", "bench_api"),
    ("pushpull_matrix", "bench_auto"),
    ("service_throughput", "bench_service"),
    ("table1_counters", "bench_counters"),
    ("table3_pagerank", "bench_pagerank"),
    ("table3_tc", "bench_tc"),
    ("fig1_table6b_coloring", "bench_coloring"),
    ("fig2_sssp", "bench_sssp"),
    ("fig3_dm_scaling", "bench_dm_scaling"),
    ("scaling_throughput", "bench_scaling"),
    ("fig4_mst", "bench_mst"),
    ("fig5_bc", "bench_bc"),
    ("table6a_strategies", "bench_strategies"),
    ("kernels", "bench_kernels"),
    ("roofline", "roofline_run"),
]


def _json_row(row: dict) -> dict:
    """A report row; `derived` strings that are JSON payloads (bench_api,
    bench_auto) come through parsed."""
    derived = row["derived"]
    if isinstance(derived, str) and derived[:1] in "{[":
        try:
            derived = json.loads(derived)
        except ValueError:
            pass
    return {"name": row["name"], "us_per_call": row["us_per_call"],
            "derived": derived}


def build_report(failures: list) -> dict:
    from . import common
    return {"rows": [_json_row(r) for r in common.RESULTS],
            "failures": [{"suite": n, "error": e} for n, e in failures]}


def write_json(path: str, report: dict) -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"json report: {path} ({len(report['rows'])} rows)", flush=True)


def _fmt_weight(x: float) -> str:
    return f"{x:.3g}" if x else "0"


def render_markdown(report: dict) -> str:
    """The JSON report as markdown tables: one wide table for push/pull
    matrix cells, one for service throughput cells (both recognized by
    their structured derived payloads), one generic table for
    everything else."""
    matrix, service, kernels, scaling, generic = [], [], [], [], []
    for row in report["rows"]:
        d = row["derived"]
        if isinstance(d, dict) and {"algorithm", "policy",
                                    "counters"} <= d.keys():
            matrix.append((row, d))
        elif isinstance(d, dict) and {"algorithm", "batch",
                                      "qps_batched"} <= d.keys():
            service.append((row, d))
        elif isinstance(d, dict) and {"direction", "combine",
                                      "us_pallas"} <= d.keys():
            kernels.append((row, d))
        elif isinstance(d, dict) and {"algorithm", "shards",
                                      "collective_bytes"} <= d.keys():
            scaling.append((row, d))
        else:
            generic.append(row)

    out = ["# Benchmark report", "",
           "Generated by `PYTHONPATH=src python -m benchmarks.run "
           "--json ... --markdown ...` — do not edit by hand. "
           "Schema: [`benchmarks/schema.json`](../benchmarks/schema.json).",
           ""]
    if matrix:
        out += [
            "## Push/pull decision matrix",
            "",
            "Counter totals are the paper's §4 categories; `weighted` is "
            "`Cost.weighted_total()` (read=1, write=1, atomic=2, lock=4), "
            "the scalar AutoSwitch minimizes.",
            "",
            "| algorithm | graph | policy | backend | steps | push | "
            "epochs | reads | writes | atomics | locks | weighted | "
            "µs/call | mispredict% |",
            "|---|---|---|---|--:|--:|--:|--:|--:|--:|--:|--:|--:|--:|",
        ]
        for row, d in matrix:
            c = d["counters"]
            # only auto-policy rows audited under --trace-out carry a
            # misprediction rate; everything else renders as –
            mis = d.get("mispredict_pct")
            out.append(
                f"| {d['algorithm']} | {d['graph']} | {d['policy']} | "
                f"{d['backend']} | {d['steps']} | {d['push_steps']} | "
                f"{d['epochs']} | {c['reads']} | {c['writes']} | "
                f"{c['atomics']} | {c['locks']} | "
                f"{_fmt_weight(d['weighted_total'])} | "
                f"{row['us_per_call']:.0f} | "
                f"{'–' if mis is None else f'{mis:.1f}'} |")
        out.append("")
    if service:
        out += [
            "## Service throughput (batched multi-query serving)",
            "",
            "`qps` is queries/sec; `batched` runs one "
            "`api.solve_batch` over the whole batch, `sequential` "
            "loops single-source `api.solve` calls over the same "
            "sources. `weighted` is the batched run's "
            "`Cost.weighted_total()`.",
            "",
            "| algorithm | graph | policy | backend | batch | "
            "qps batched | qps sequential | speedup | weighted |",
            "|---|---|---|---|--:|--:|--:|--:|--:|",
        ]
        for row, d in service:
            out.append(
                f"| {d['algorithm']} | {d['graph']} | {d['policy']} | "
                f"{d['backend']} | {d['batch']} | {d['qps_batched']} | "
                f"{d['qps_sequential']} | {d['speedup']:.2f} | "
                f"{_fmt_weight(d['weighted_total'])} |")
        out.append("")
    if kernels:
        out += [
            "## Kernel wall-clock (jnp primitives vs Pallas)",
            "",
            "Per (direction × combine × graph family × batch width) "
            "cell: the jnp primitive (`pull_relax_ell` / `push_relax`) "
            "vs the Pallas kernel (`ell_spmv_pallas` / "
            "`coo_push_pallas`) at the autotuned configuration (block "
            "sizes; push also bins = destination-bin count and the "
            "phase-2 reduce strategy). `match` is the correctness "
            "cross-check; `speedup` > 1 means the kernel wins; "
            "`pct-roof` is the fraction of the analytic roofline bound "
            "achieved (distance-to-hardware — near zero in interpret "
            "mode, meaningful compiled). `pullf` rows are the "
            "frontier-restricted pull (`ell_pull_frontier_pallas`) on "
            "a BFS-shaped touched set (≤10% density); their `speedup` "
            "is vs the full-scan kernel dispatch they replace "
            "(`us_full_kernel`), timed end to end with compaction and "
            "scatter under one jit.",
            "",
            "| direction | combine | graph | batch | blocks | bins | "
            "µs jnp | µs pallas | speedup | pct-roof | match |",
            "|---|---|---|--:|---|--:|--:|--:|--:|--:|---|",
        ]
        for row, d in kernels:
            blocks = (f"e{d['block_e']}/n{d['block_n']}"
                      if "block_e" in d else f"n{d['block_n']}")
            if "strategy" in d:
                blocks += f"/{d['strategy']}"
            out.append(
                f"| {d['direction']} | {d['combine']} | {d['graph']} | "
                f"{d['batch']} | {blocks} | {d.get('bins', '–')} | "
                f"{d['us_jnp']:.0f} | "
                f"{d['us_pallas']:.0f} | {d['speedup']:.2f} | "
                f"{d.get('pct_roofline', 0):.1e} | "
                f"{d['match']} |")
        out.append("")
    if scaling:
        out += [
            "## Sharded-engine scaling (shard_map, fake multi-device)",
            "",
            "One `api.solve(backend=ShardedBackend)` run per cell; "
            "`bytes` is the total inter-device wire traffic the "
            "adaptive accounting charges (`Cost.collective_bytes`), "
            "`match` the cross-check against the single-device dense "
            "run. Push bytes < pull bytes on frontier-sparse cells is "
            "the paper's §6 asymmetry.",
            "",
            "| algorithm | policy | compression | shards | steps | "
            "bytes | µs/solve | match |",
            "|---|---|---|--:|--:|--:|--:|---|",
        ]
        for row, d in scaling:
            out.append(
                f"| {d['algorithm']} | {d['policy']} | "
                f"{d['compression']} | {d['shards']} | {d['steps']} | "
                f"{d['collective_bytes']} | {row['us_per_call']:.0f} | "
                f"{d['match']} |")
        out.append("")
    if generic:
        out += ["## Other suites", "",
                "| name | µs/call | derived |", "|---|--:|---|"]
        for row in generic:
            d = row["derived"]
            if isinstance(d, (dict, list)):
                d = json.dumps(d)
            out.append(f"| {row['name']} | {row['us_per_call']:.0f} | "
                       f"{str(d)[:120]} |")
        out.append("")
    if report["failures"]:
        out += ["## Failures", ""]
        out += [f"- `{f['suite']}`: {f['error']}"
                for f in report["failures"]]
        out.append("")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only these suites (comma-separated names)")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink suites that honor it to CI-sized graphs")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the emitted rows as a JSON report")
    ap.add_argument("--markdown", default=None, metavar="PATH",
                    help="render the report as markdown tables")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a repro.obs JSONL trace (plus a "
                         "Perfetto-loadable .chrome.json next to it); "
                         "auto-policy matrix rows gain mispredict%%")
    args = ap.parse_args()
    enable_compile_cache()
    from . import common
    common.SMOKE = args.smoke
    if args.trace_out:
        from repro.obs import Telemetry
        common.TELEMETRY = Telemetry()
    only = set(args.only.split(",")) if args.only else None
    if only is not None:
        known = {n for pair in SUITES for n in pair}
        unknown = only - known
        if unknown:
            ap.error(f"unknown suite(s) {sorted(unknown)}; "
                     f"valid: {sorted(n for n, _ in SUITES)}")
    failures = []
    for name, mod in SUITES:
        if only is not None and not only & {name, mod}:
            continue
        print(f"\n===== {name} ({mod}) =====", flush=True)
        t0 = time.time()
        try:
            module = __import__(f"benchmarks.{mod}", fromlist=["run"])
            module.run()
            print(f"----- {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            import traceback
            traceback.print_exc()
    if args.trace_out:
        from repro.obs.export import (validate_trace_file,
                                      write_chrome_trace, write_jsonl)
        n = write_jsonl(common.TELEMETRY, args.trace_out)
        validate_trace_file(args.trace_out)
        chrome = args.trace_out.rsplit(".", 1)[0] + ".chrome.json"
        write_chrome_trace(common.TELEMETRY, chrome)
        print(f"trace: {args.trace_out} ({n} events, schema ok; "
              f"chrome: {chrome})", flush=True)
    report = build_report(failures) if (args.json or args.markdown) else None
    if args.json:
        write_json(args.json, report)
        from .validate import validate_report
        validate_report(report)
        print("schema ok: benchmarks/schema.json", flush=True)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(render_markdown(report))
        print(f"markdown report: {args.markdown}", flush=True)
    if failures:
        print("\nFAILED:", failures)
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
