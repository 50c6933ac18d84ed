"""Lifecycle-scenario benchmark: balancers head-to-head per scenario.

Runs every registered scenario (``repro.sim.scenarios``) once per
balancer and writes ``BENCH_scenarios.json``::

    {
      "git_sha": ..., "seed": ..., "quick": ..., "balancers": [...],
      "scenarios": {
        "<scenario>": {
          "<balancer>": {"metrics": {"ticks": [...], "variance": [...],
                         "variance_target": [...], "max_util": [...],
                         "pool_max_avail": {pid: [...]},
                         "transferred_bytes": [...], ...,
                         "summary": {...}},
                         "wall_seconds": ...,
                         "counters": {"batch.rebuilds": 1, ...}},
        }, ...
      }
    }

Wall times and the per-run ``counters`` block come from the telemetry
spine (:mod:`repro.obs`): each run is a ``bench.call`` span whose
attached registry deltas (rebuilds, host syncs, absorb traffic, moved
bytes) are persisted next to the metrics, so engine-behaviour
regressions are assertable from the artifact alone.  ``--trace-out``
keeps the full trace.

The per-tick series are the scenario counterpart of the paper's Fig 4-6
trajectories; the summary comparison printed at the end is the lifecycle
counterpart of Table 1 (final variance, total moved bytes, ticks above
the fullness threshold).

    PYTHONPATH=src python -m benchmarks.bench_scenarios [--quick]
        [--scenario NAME ...] [--balancers eq,mgr,...] [--seed N] [--out P]
"""

from __future__ import annotations

import argparse
import json
import time

from benchmarks.run import git_sha
from repro import obs
from repro.core import TiB, available_planners
from repro.sim import SCENARIOS, run_scenario
from repro.compile_cache import enable_compile_cache

DEFAULT_BALANCERS = ("equilibrium_batch", "mgr")

#: registry prefixes worth persisting per scenario run (the JSON
#: ``counters`` block: engine activity, absorb traffic, sim throughput)
COUNTER_PREFIXES = ("batch.", "absorb.", "sim.", "tail.", "planner.")


def bench_scenarios(scenarios: list[str] | None = None,
                    balancers: tuple[str, ...] = DEFAULT_BALANCERS,
                    seed: int = 0, quick: bool = False,
                    out: str = "BENCH_scenarios.json"):
    """Run the scenario × balancer grid; returns (results, csv_rows)."""
    names = scenarios or sorted(SCENARIOS)
    results = {"git_sha": git_sha(), "seed": seed, "quick": quick,
               "balancers": list(balancers), "scenarios": {}}
    rows = []
    for name in names:
        per: dict[str, dict] = {}
        for bal in balancers:
            # the bench.call span times the run; its counter deltas are
            # the per-run engine activity (rebuilds, syncs, absorb
            # traffic, moved bytes), persisted next to the metrics so
            # regressions are assertable from the artifact alone
            t0 = time.perf_counter()
            with obs.span("bench.call", cat="bench", counters=True,
                          name=f"scenario.{name}.{bal}") as sp:
                r = run_scenario(name, bal, seed=seed, quick=quick)
            wall = sp.wall_s or time.perf_counter() - t0
            r["wall_seconds"] = round(wall, 3)
            r["counters"] = {
                k: v for k, v in sp.args.get("counters", {}).items()
                if k.startswith(COUNTER_PREFIXES)}
            per[bal] = r
            s = r["metrics"]["summary"]
            derived = (f"final_var={s['final_variance']:.3e};"
                       f"moved_TiB={s['total_transferred_bytes'] / TiB:.2f};"
                       f"planned={s['total_planned_moves']};"
                       f"above_thresh={s['ticks_above_threshold']};"
                       f"degraded={s['final_degraded']}")
            rows.append((f"scenario.{name}.{bal}", wall * 1e6, derived))
            print(f"  {name:22s} {bal:18s} {derived} ({wall:.1f}s)")
        results["scenarios"][name] = per
    if out:
        with open(out, "w") as f:
            json.dump(results, f, sort_keys=True)
        print(f"wrote {len(names)}x{len(balancers)} runs -> {out}")
    _print_verdicts(results)
    return results, rows


def _print_verdicts(results: dict) -> None:
    """Head-to-head summary vs the mgr baseline, when present."""
    for name, per in results["scenarios"].items():
        if "mgr" not in per:
            continue
        mgr = per["mgr"]["metrics"]["summary"]
        for bal, r in per.items():
            if bal == "mgr":
                continue
            s = r["metrics"]["summary"]
            print(f"  {name}: {bal} vs mgr — "
                  f"variance {s['final_variance']:.3e} vs "
                  f"{mgr['final_variance']:.3e} "
                  f"({'better' if s['final_variance'] < mgr['final_variance'] else 'worse'}), "
                  f"moved {s['total_transferred_bytes'] / TiB:.2f} vs "
                  f"{mgr['total_transferred_bytes'] / TiB:.2f} TiB "
                  f"({'less' if s['total_transferred_bytes'] < mgr['total_transferred_bytes'] else 'more'})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short tick counts (CI smoke)")
    ap.add_argument("--scenario", action="append", default=None,
                    metavar="NAME", choices=sorted(SCENARIOS),
                    help="run only this scenario (repeatable)")
    ap.add_argument("--balancers", default=",".join(DEFAULT_BALANCERS),
                    help="comma list of registered planners "
                         f"{available_planners()}")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_scenarios.json")
    ap.add_argument("--trace-out", default=None,
                    help="keep the bench trace (*.jsonl native, otherwise "
                         "Chrome/Perfetto JSON); default: in-memory only")
    args = ap.parse_args()
    enable_compile_cache()
    balancers = tuple(b for b in args.balancers.split(",") if b)
    for b in balancers:
        if b not in available_planners():
            ap.error(f"unknown balancer {b!r}: expected one of "
                     f"{available_planners()}")
    started = not obs.enabled()
    if started:
        obs.start_tracing(args.trace_out, device_counters=True)
    bench_scenarios(args.scenario, balancers, seed=args.seed,
                    quick=args.quick, out=args.out)
    if started:
        obs.stop_tracing()
        if args.trace_out:
            print(f"wrote trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
