#!/usr/bin/env python
"""Profile one sharded-planner mesh point: per-device memory of the
compiled chunk program plus a short timed plan on ``cluster_b(scale)``.

JAX fixes the host device count at process start, so on a CPU host
``benchmarks/bench_planner.py`` spawns this script once per mesh size
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and stitches
the JSON lines into its ``planner.shard.*`` rows; on a TPU host it calls
:func:`profile_point` in its own process for each mesh size instead.
The cluster build is pickle-cached and shared across mesh sizes (at
scale 8 — the ~8k-OSD, ~70k-PG profile cluster — building it dominates
everything else this script does).

The memory figures come from XLA's ``memory_analysis`` of the lowered
chunk executable (:func:`repro.core.shard.chunk_memory_stats`); for an
SPMD mesh these are per-participant, i.e. ``peak_bytes_per_device`` is
directly the quantity whose ~1/N scaling the bench reports.  The timed
plan follows the bench's cold-start convention: one warm call compiles,
then a fresh planner is timed from its own dense build.  With
``--serial-check`` (default) the serial ``equilibrium_batch`` engine
replans the same budget and the move tuples must match bit-for-bit.

Prints one JSON object on the last stdout line; non-zero exit on any
mismatch.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def load_state(scale: int, cache: str | None):
    """Build ``cluster_b(scale)`` or load the pickled build."""
    from repro.core.clustergen import cluster_b
    t0 = time.perf_counter()
    if cache and os.path.exists(cache):
        with open(cache, "rb") as f:
            return pickle.load(f), time.perf_counter() - t0, True
    state = cluster_b(scale=scale)
    if cache:
        os.makedirs(os.path.dirname(cache) or ".", exist_ok=True)
        tmp = f"{cache}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(state, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, cache)
    return state, time.perf_counter() - t0, False


def as_tuples(moves):
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in moves]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=None,
                    help="expected mesh size (asserts the forced host "
                         "platform actually exposes this many devices)")
    ap.add_argument("--scale", type=int, default=8,
                    help="cluster_b scale (8 = the ~8k-OSD profile)")
    ap.add_argument("--budget", type=int, default=64,
                    help="timed-plan move window (0 = memory profile only)")
    ap.add_argument("--cache", default=None,
                    help="pickle cache path for the built cluster")
    ap.add_argument("--no-serial-check", dest="serial_check",
                    action="store_false",
                    help="skip the serial bit-identity replan")
    ap.add_argument("--trace-out", default=None,
                    help="write the obs trace of the timed plan (feeds "
                         "tools/tracestat.py --shards)")
    args = ap.parse_args()

    import jax
    n_dev = len(jax.devices())
    if args.devices is not None and n_dev != args.devices:
        print(f"expected {args.devices} devices, found {n_dev} — set "
              f"XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{args.devices}", file=sys.stderr)
        return 2

    state, build_s, cache_hit = load_state(args.scale, args.cache)
    out = profile_point(state, n_dev, args.budget,
                        serial_check=args.serial_check,
                        trace_out=args.trace_out)
    out.update(scale=args.scale, build_s=round(build_s, 1),
               cache_hit=cache_hit)
    print(json.dumps(out))
    if out.get("identical") is False:
        print("sharded/serial move streams diverge", file=sys.stderr)
        return 1
    return 0


def profile_point(state, n_shards: int, budget: int, *,
                  serial_check: bool = True,
                  trace_out: str | None = None) -> dict:
    """One mesh point on the first ``n_shards`` visible devices: the
    compiled chunk program's per-device memory, then (``budget`` > 0) a
    timed ``budget``-move plan and, with ``serial_check``, the serial
    engine's replan of the same budget (``identical`` in the result)."""
    from repro import obs
    from repro.core import EquilibriumConfig
    from repro.core.equilibrium_batch import DONATED_CARRY
    from repro.core.planner import create_planner
    from repro.core.shard import ShardedBatchPlanner, chunk_memory_stats

    out = {"devices": n_shards, "osds": state.n_devices,
           "pgs": len(state.acting), "donated_carry": DONATED_CARRY}

    # per-participant memory of the compiled chunk program
    mem = chunk_memory_stats(ShardedBatchPlanner(state.copy(),
                                                 EquilibriumConfig(),
                                                 n_shards=n_shards))
    out.update(mem)
    out["peak_bytes_per_device"] = mem.get("peak_bytes", 0)

    if budget:
        if trace_out:
            obs.start_tracing(trace_out, device_counters=True)
        # warm call compiles the mesh program; the timed planner is then
        # cold-started (dense build included), as in bench_planner
        create_planner("equilibrium_batch_sharded", n_shards=n_shards).plan(
            state.copy(), budget=min(budget, 16))
        planner = create_planner("equilibrium_batch_sharded",
                                 n_shards=n_shards)
        timed = state.copy()
        t0 = time.perf_counter()
        res = planner.plan(timed, budget=budget)
        dt = time.perf_counter() - t0
        out.update(moves=len(res.moves), plan_s=round(dt, 3),
                   moves_per_s=round(len(res.moves) / max(dt, 1e-9), 1),
                   shards=res.stats["shards"],
                   pipeline=res.stats["pipeline"])
        if trace_out:
            obs.stop_tracing()
        if serial_check:
            serial = create_planner("equilibrium_batch",
                                    select_backend="ref")
            ref = serial.plan(state.copy(), budget=budget)
            out["identical"] = as_tuples(res.moves) == as_tuples(ref.moves)
    return out


if __name__ == "__main__":
    sys.exit(main())
