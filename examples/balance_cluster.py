"""Reproduce the paper's per-cluster experiment on any of clusters A–F:
both balancers from the same initial state, Table-1 row + trajectory CSV.

    PYTHONPATH=src python examples/balance_cluster.py --cluster A
"""

import argparse
import csv

from repro.core import (EquilibriumConfig, MgrBalancerConfig, PAPER_CLUSTERS,
                        TiB, create_planner, simulate)
from repro.compile_cache import enable_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--cluster", choices=sorted(PAPER_CLUSTERS), default="A")
ap.add_argument("--max-moves", type=int, default=10_000)
ap.add_argument("--engine", default="equilibrium",
                choices=("equilibrium", "equilibrium_batch",
                         "equilibrium_batch_sharded",
                         "equilibrium_jax_legacy"),
                help="Equilibrium planner: dense-NumPy (default), the "
                     "device-resident batched engine, its shard_map-ped "
                     "mesh variant, or the per-source legacy JAX path — "
                     "all bit-identical")
ap.add_argument("--trajectory-csv", default=None)
args = ap.parse_args()
enable_compile_cache()

initial = PAPER_CLUSTERS[args.cluster]()
print(f"cluster {args.cluster}: {initial.n_devices} OSDs, "
      f"{len(initial.acting)} PGs, {len(initial.pools)} pools")

results = {}
for name, planner_name, cfg in (
        ("default", "mgr", MgrBalancerConfig(max_moves=args.max_moves)),
        ("equilibrium", args.engine,
         EquilibriumConfig(max_moves=args.max_moves))):
    moves = create_planner(planner_name, cfg=cfg).plan(initial.copy()).moves
    res = simulate(initial, moves, trajectory_stride=max(1, len(moves) // 100))
    results[name] = res
    print(f"  {name:12s}: {len(moves):5d} moves | gained "
          f"{res.gained_free_space / TiB:8.2f} TiB | moved "
          f"{res.moved_bytes / TiB:7.2f} TiB | var "
          f"{res.variance_after:.6f} | per-class "
          f"{ {k: round(v, 6) for k, v in res.variance_by_class_after.items()} }")

if args.trajectory_csv:
    with open(args.trajectory_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["balancer", "sample", "variance", "free_TiB", "moved_TiB"])
        for name, res in results.items():
            for i, (v, fr, mv) in enumerate(zip(res.variance_trajectory,
                                                res.free_trajectory,
                                                res.moved_bytes_trajectory)):
                w.writerow([name, i, v, fr / TiB, mv / TiB])
    print(f"trajectories → {args.trajectory_csv}")
