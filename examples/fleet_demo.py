"""Plan a small fleet of clusters with one vmapped service.

    PYTHONPATH=src python examples/fleet_demo.py [--slo-ms 5] [--ticks 4]

Three heterogeneous clusters attach to one :class:`repro.fleet.
FleetService`; each balancing interval plans *all* of them in a single
vmapped dispatch per shape bucket.  Between ticks, pool growth streams
into one lane as deltas the warm carry absorbs in place (no dense
rebuild).  The per-tick table shows each cluster's partial/complete
plan under the latency SLO; the footer summarizes the trace the run
recorded (the same spans ``tools/tracestat.py --fleet`` tabulates).
"""

import argparse

import numpy as np

from repro import obs
from repro.core import Device, PlacementRule, Pool, TiB, build_cluster
from repro.compile_cache import enable_compile_cache

GiB = TiB / 1024


def make_cluster(i: int):
    """12–14 OSDs over mixed 2/4/16 TiB devices, two replicated pools."""
    rng = np.random.default_rng(7 + i)
    devs = []
    for d in range(12 + i):
        cap = float(rng.choice([2, 4, 16])) * TiB
        devs.append(Device(id=d, capacity=cap, device_class="hdd",
                           host=f"host{d // 3}"))
    total = sum(d.capacity for d in devs)
    pools = [Pool(0, "rbd", 24 + i, PlacementRule.replicated(3, "host"),
                  stored_bytes=0.45 * total / 3),
             Pool(1, "rgw", 14 + i, PlacementRule.replicated(2, "host"),
                  stored_bytes=0.30 * total / 2)]
    return build_cluster(devs, pools, seed=i)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--budget", type=int, default=16,
                    help="moves per cluster per tick")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-tick latency SLO (unset: no deadline)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's repro.obs trace for "
                         "tools/tracestat.py --fleet")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.fleet import FleetService    # after CLI: imports touch jax

    slo = None if args.slo_ms is None else args.slo_ms / 1e3
    service = FleetService(chunk=max(1, args.budget // 2), slo_seconds=slo)
    states = {}
    for i in range(3):
        key = f"cluster-{i}"
        states[key] = make_cluster(i)
        service.attach(key, states[key])
        u = states[key].utilization()
        print(f"{key}: {states[key].n_devices} OSDs, util "
              f"{u.min():.2f}..{u.max():.2f}, "
              f"variance {states[key].utilization_variance():.5f}")

    with obs.tracing(args.trace_out) as trace:
        for t in range(args.ticks):
            if t == 2:
                # out-of-band growth streams into one lane; the warm
                # carry absorbs it without a dense rebuild
                states["cluster-1"].grow_pool(0, 256 * GiB)
                print("tick 2: +256 GiB into cluster-1/rbd "
                      "(delta absorbed in place)")
            result = service.tick(
                {k: args.budget for k in states})
            for key in sorted(states):
                plan = result.results[key]
                s = plan.stats
                print(f"  t={t} {key}: {len(plan.moves):>3} moves  "
                      f"variance {s['variance_after']:.6f}  "
                      f"converged={s['converged']}"
                      + ("  SLO-cut" if s["slo_expired"] else ""))

    ticks = [r for r in trace.records
             if r["ev"] == "span" and r["name"] == "fleet.tick"]
    chunks = sum(r.get("args", {}).get("chunks", 0) for r in ticks)
    counters = next((r for r in reversed(trace.records)
                     if r["ev"] == "counters"), {"values": {}})["values"]
    print(f"\n{len(ticks)} fleet ticks, {chunks} vmapped dispatches, "
          f"{int(counters.get('batch.host_syncs', 0))} host syncs, "
          f"{int(counters.get('batch.rebuilds', 0))} dense rebuilds, "
          f"{int(counters.get('absorb.runs', 0))} absorb runs")
    for key in sorted(states):
        print(f"{key}: final variance "
              f"{states[key].utilization_variance():.6f}")
    if args.trace_out:
        print(f"trace -> {args.trace_out} "
              f"(tools/tracestat.py {args.trace_out} --fleet)")


if __name__ == "__main__":
    main()
