"""Run one lifecycle scenario and watch the cluster move.

    PYTHONPATH=src python examples/scenario_demo.py \
        --scenario cascading-failures --balancer equilibrium_batch

Prints a per-tick table (physical utilization variance, max device
utilization, transfer backlog, cumulative moved TiB) with event
annotations, then the summary — the interactive view of what
``python -m benchmarks.run --scenarios`` measures in bulk.
"""

import argparse

from repro import obs
from repro.core import TiB, available_planners
from repro.sim import SCENARIOS, run_scenario
from repro.compile_cache import enable_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--scenario", choices=sorted(SCENARIOS),
                default="steady-growth")
ap.add_argument("--balancer", choices=available_planners(),
                default="equilibrium_batch")
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--quick", action="store_true", help="short tick count")
ap.add_argument("--stride", type=int, default=1,
                help="print every Nth tick")
ap.add_argument("--trace-out", default=None, metavar="PATH",
                help="write the run's repro.obs trace (*.jsonl native, "
                     "otherwise Chrome/Perfetto JSON for tools/tracestat.py)")
args = ap.parse_args()
enable_compile_cache()

print(f"scenario {args.scenario!r} ({SCENARIOS[args.scenario].description})")
# the run is traced (in-memory unless --trace-out): every tick and plan
# call is a span, and the timing footer below is read back from it
with obs.tracing(args.trace_out) as trace:
    result = run_scenario(args.scenario, args.balancer, seed=args.seed,
                          quick=args.quick)
m = result["metrics"]
events_at = {}
for tick, desc in m["events"]:
    events_at.setdefault(tick, []).append(desc.split("(")[0])

print(f"{'tick':>5} {'variance':>10} {'max_util':>9} {'backlog':>8} "
      f"{'moved_TiB':>10}  events")
last = len(m["ticks"]) - 1
for i, t in enumerate(m["ticks"]):
    if i % args.stride and i != last:
        continue
    note = ",".join(events_at.get(t, []))
    print(f"{t:>5} {m['variance'][i]:>10.6f} {m['max_util'][i]:>9.3f} "
          f"{m['backlog_moves'][i]:>8} "
          f"{m['transferred_bytes'][i] / TiB:>10.2f}  {note}")

s = m["summary"]
print(f"\n{args.balancer}: final variance {s['final_variance']:.3e} "
      f"(target {s['final_variance_target']:.3e}), "
      f"moved {s['total_transferred_bytes'] / TiB:.2f} TiB in "
      f"{s['total_planned_moves']} planned moves, "
      f"{s['ticks_above_threshold']} ticks above fullness threshold, "
      f"{s['final_degraded']} degraded shards")

wall: dict[str, float] = {}
for r in trace.records:
    if r.get("ev") == "span":
        wall[r["name"]] = wall.get(r["name"], 0.0) + r["dur"] / 1e6
print(f"timing (repro.obs): scenario {wall.get('sim.scenario', 0.0):.2f}s, "
      f"planner {wall.get('planner.plan', 0.0):.2f}s"
      + (f", device chunks {wall['batch.chunk']:.2f}s"
         if "batch.chunk" in wall else ""))
if args.trace_out:
    print(f"wrote trace -> {args.trace_out} "
          f"(summarize: python tools/tracestat.py {args.trace_out})")
