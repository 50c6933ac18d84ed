"""Benchmark entry point — one function per paper table/figure plus the
framework-level analyses.  Prints ``name,us_per_call,derived`` CSV rows;
``--json PATH`` additionally writes the same rows (plus the git sha) as
a JSON list — the ``BENCH_planner.json`` schema:
``[{"name", "us_per_call", "derived", "git_sha"}, ...]``.

``--scenarios`` swaps in the lifecycle-scenario suite (all registered
scenarios, every default balancer from the planner registry).  The two
output flags compose: one invocation writes *both* artifacts — the CSV
rows of every suite that ran go to ``--json PATH``, and the full
per-tick scenario results go to ``--scenarios-out`` (default
``BENCH_scenarios.json``); the two paths are guarded against clobbering
each other.

``--trace-out PATH`` additionally records the whole run through the
telemetry spine (:mod:`repro.obs`): every planner call, batch chunk and
scenario tick becomes a span, and the registry counters land in the
trace footer — ``*.jsonl`` gets the native line format, any other suffix
a Chrome/Perfetto trace JSON.  Like the other artifacts it is guarded
against clobbering ``--json`` / ``--scenarios-out``.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
        [--scenarios] [--scenarios-out PATH] [--seed N] [--trace-out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import traceback

from repro.compile_cache import enable_compile_cache


def git_sha() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True, check=True,
                              timeout=10).stdout.strip()
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small clusters only (A, C, F)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON (BENCH_planner.json "
                         "schema: name, us_per_call, derived, git_sha)")
    ap.add_argument("--scenarios", action="store_true",
                    help="run the lifecycle-scenario suite instead of the "
                         "paper suites; composes with --json (rows) and "
                         "--scenarios-out (full per-tick results)")
    ap.add_argument("--scenarios-out", metavar="PATH",
                    default="BENCH_scenarios.json",
                    help="where the scenario suite writes its full results")
    ap.add_argument("--seed", type=int, default=0,
                    help="scenario-suite seed (ignored without --scenarios)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="also write a structured trace of the whole run "
                         "(repro.obs): *.jsonl gets the native line format, "
                         "any other suffix a Chrome/Perfetto trace JSON")
    args = ap.parse_args()
    enable_compile_cache()

    if args.json and args.scenarios and \
            os.path.abspath(args.json) == os.path.abspath(args.scenarios_out):
        ap.error("--json and --scenarios-out point at the same file; the "
                 "rows artifact would clobber the scenario results")
    if args.trace_out:
        clashes = [args.json] + ([args.scenarios_out] if args.scenarios
                                 else [])
        if any(p and os.path.abspath(args.trace_out) == os.path.abspath(p)
               for p in clashes):
            ap.error("--trace-out points at another output artifact; the "
                     "trace would clobber it")

    if args.scenarios:
        from benchmarks.bench_scenarios import bench_scenarios

        def scenario_suite():
            _, rows = bench_scenarios(quick=args.quick, seed=args.seed,
                                      out=args.scenarios_out)
            return rows

        suites = [("scenarios", scenario_suite)]
    else:
        from benchmarks.paper_tables import (bench_planner_speed,
                                             bench_table1, bench_timing,
                                             bench_trajectories)
        from benchmarks.roofline import bench_roofline

        table1_clusters = ("A", "C", "F") if args.quick else ("A", "B", "C",
                                                              "D", "E", "F")
        traj_clusters = ("A",) if args.quick else ("A", "B")

        suites = [
            ("table1", lambda: bench_table1(table1_clusters)),
            ("trajectories", lambda: bench_trajectories(traj_clusters)),
            ("timing", lambda: bench_timing(traj_clusters)),
            ("planner_speed", bench_planner_speed),
            ("roofline", bench_roofline),
        ]

    tracer = None
    if args.trace_out:
        from repro import obs
        tracer = obs.start_tracing(args.trace_out, device_counters=True)

    sha = git_sha()
    json_rows = []
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}")
                json_rows.append({"name": row_name, "us_per_call": us,
                                  "derived": derived, "git_sha": sha})
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"{name},-1,FAILED:{e}")
            json_rows.append({"name": name, "us_per_call": -1,
                              "derived": f"FAILED:{e}", "git_sha": sha})
    if tracer is not None:
        from repro import obs
        obs.stop_tracing()
        print(f"# wrote trace -> {args.trace_out}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_rows, f, indent=1)
        print(f"# wrote {len(json_rows)} rows -> {args.json}", file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
