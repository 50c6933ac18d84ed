"""Quickstart: the paper in ~40 lines.

Build a small heterogeneous cluster, run Ceph's count-based balancer and
Equilibrium on identical copies, and compare gained capacity, movement
volume, and utilization variance (Table-1-style row).

    PYTHONPATH=src python examples/quickstart.py
"""

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.core import (EquilibriumConfig, MgrBalancerConfig, TiB,
                        create_planner, simulate, small_test_cluster)

enable_compile_cache()
initial = small_test_cluster()
print(f"cluster: {initial.n_devices} OSDs, {len(initial.acting)} PGs, "
      f"utilization {initial.utilization().min():.2f}"
      f"–{initial.utilization().max():.2f}, "
      f"variance {initial.utilization_variance():.4f}")

# every plan() call is a span on the telemetry spine; trace in-memory
# and read the timing back from the records instead of timing by hand
with obs.tracing() as trace:
    mgr_moves = create_planner("mgr", cfg=MgrBalancerConfig()) \
        .plan(initial.copy()).moves
    eq_moves = create_planner("equilibrium", cfg=EquilibriumConfig()) \
        .plan(initial.copy()).moves

for name, moves in (("ceph mgr balancer", mgr_moves),
                    ("equilibrium      ", eq_moves)):
    res = simulate(initial, moves, record_trajectory=False)
    print(f"{name}: {len(moves):3d} moves | "
          f"gained {res.gained_free_space / TiB:6.2f} TiB | "
          f"moved {res.moved_bytes / TiB:5.2f} TiB | "
          f"variance {res.variance_before:.4f} → {res.variance_after:.5f}")

print("\nplanner timing (from the repro.obs trace):")
for r in trace.records:
    if r.get("ev") == "span" and r["name"] == "planner.plan":
        a = r["args"]
        print(f"  {a['planner']:12s} {r['dur'] / 1e3:8.1f} ms wall "
              f"({a['moves']} moves)")
