"""Fleet planning throughput: one vmapped dispatch vs N serial planners.

Three benchmark families, all over lanes the serial engine plans
bit-identically (checked per run, reported per row):

* ``fleet.stream``  — the headline: N independent clusters driven to
  convergence in fine-grained streaming mode (``chunk=1``, the SLO
  granularity a latency-bounded service plans at).  Here per-dispatch
  fixed cost (jit call + host sync + Python) dominates compute, and the
  fleet pays it once per bucket-round instead of once per cluster-move:
  the measured speedup *is* the dispatch amortization, and the move
  streams must match the serial planners move-for-move.
* ``fleet.loadgen`` — N concurrent scenario lifecycles
  (:class:`repro.fleet.loadgen.FleetLoadGen`) on one planner:
  steady-growth emits only absorbable deltas, so each cluster's whole
  lifecycle must cost exactly one dense rebuild (the initial pack) —
  the row carries ``max_rebuilds`` for CI to assert on.
* ``fleet.slo``     — a deliberately impossible deadline: the tick must
  still return a *valid partial* plan (every returned move replays
  legally on a twin) with ``slo_expired`` set.

Rows follow the repo bench schema ``{name, us_per_call, derived,
git_sha}`` (BENCH_fleet.json); every timed call runs inside a
``bench.call`` span with counter deltas attached, so
``tools/tracestat.py --bench`` / ``--fleet`` reproduce the derived
columns from the trace alone.  Host-sync accounting comes from the
``batch.host_syncs`` registry counter: the fleet's syncs-per-step must
stay at the *single-cluster* bound (one sync per bucket-round, however
many lanes), which CI asserts via the emitted fields.

    PYTHONPATH=src python -m benchmarks.bench_fleet [--quick] [--out P]
        [--trace-out P]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.run import git_sha
from repro import obs
from repro.core import (Device, PlacementRule, Pool, TiB, build_cluster,
                        create_planner)
from repro.fleet import FleetLoadGen, FleetPlanner, FleetService
from repro.obs.metrics import registry
from repro.compile_cache import enable_compile_cache

#: streaming-mode geometry: chunk=1 is the finest SLO granularity (one
#: move per dispatch per lane) — the regime the fleet exists for
CHUNK, ROW_BLOCK, ROW_CAPACITY = 1, 8, 128


def _mk_cluster(i: int):
    """Heterogeneous-but-bucketable genome: 12..15 OSDs (pads to one
    16-wide bucket), per-cluster pg counts, mixed 2/4/16 TiB devices."""
    rng = np.random.default_rng(100 + i)
    n_dev = 12 + (i % 4)
    devs, h = [], 0
    while len(devs) < n_dev:
        for _ in range(3):
            if len(devs) >= n_dev:
                break
            cap = float(rng.choice([2, 4, 16])) * TiB
            devs.append(Device(id=len(devs), capacity=cap,
                               device_class="hdd", host=f"host{h}"))
        h += 1
    total = sum(d.capacity for d in devs)
    pools = [Pool(0, "p0", 21 + i, PlacementRule.replicated(3, "host"),
                  stored_bytes=0.45 * total / 3),
             Pool(1, "p1", 13 + i, PlacementRule.replicated(2, "host"),
                  stored_bytes=0.30 * total / 2)]
    return build_cluster(devs, pools, seed=i)


def _mk_fleet(n: int) -> FleetPlanner:
    fp = FleetPlanner(chunk=CHUNK, row_block=ROW_BLOCK)
    for i in range(n):
        # pinning the carry row axis lands every cluster in one bucket:
        # one compiled program, one host sync per fleet round
        fp.add_cluster(i, _mk_cluster(i), row_capacity=ROW_CAPACITY)
    return fp


def _mk_serial(n: int) -> dict:
    out = {}
    for i in range(n):
        p = create_planner("equilibrium_batch", chunk=CHUNK,
                           row_block=ROW_BLOCK, select_backend="ref",
                           legality_cache=False, source_bounds=True)
        out[i] = (p, _mk_cluster(i))
    return out


def _drive_fleet(fp: FleetPlanner, n: int, budget: int):
    """Fleet ticks until a tick emits no moves; returns per-lane move
    keys and the tick count."""
    moves = {i: [] for i in range(n)}
    ticks = 0
    while True:
        ticks += 1
        got = 0
        for k, res in fp.plan_fleet({i: budget for i in range(n)}).items():
            moves[k].extend((m.pg, m.slot, m.src_osd, m.dst_osd)
                            for m in res.moves)
            got += len(res.moves)
        if got == 0:
            return moves, ticks


def _drive_serial(planners: dict, budget: int):
    moves, calls = {}, 0
    for k, (p, s) in planners.items():
        acc = []
        while True:
            calls += 1
            got = p.plan(s, budget=budget).moves
            acc.extend((m.pg, m.slot, m.src_osd, m.dst_osd) for m in got)
            if not got:
                break
        moves[k] = acc
    return moves, calls


def bench_stream(n: int, budget: int, repeats: int = 5) -> list[dict]:
    """Headline: N clusters to convergence, fleet vs serial loop.
    Best-of-``repeats`` on fresh twins each round — convergence consumes
    the state, so a repeat is a rebuild, not a re-run, and single-run
    jitter on a shared CPU is the dominant noise source."""
    sha = git_sha()
    reg = registry()

    # jit warmup on scratch twins (compile excluded, as in bench_planner)
    _drive_fleet(_mk_fleet(n), n, budget)
    _drive_serial(_mk_serial(n), budget)

    fleet_s = serial_s = float("inf")
    identical = True
    for _ in range(repeats):
        # fresh twins, both pre-packed by a budget=1 tick so the timed
        # window is pure steady-state streaming (no pack/rebuild inside)
        fp = _mk_fleet(n)
        fleet_moves = {k: [(m.pg, m.slot, m.src_osd, m.dst_osd)
                           for m in res.moves]
                       for k, res in fp.plan_fleet({i: 1 for i in range(n)}
                                                   ).items()}
        planners = _mk_serial(n)
        serial_moves = {k: [(m.pg, m.slot, m.src_osd, m.dst_osd)
                            for m in p.plan(s, budget=1).moves]
                        for k, (p, s) in planners.items()}

        snap = reg.snapshot()
        with obs.span("bench.call", cat="bench", counters=True,
                      name="fleet.stream.fleet") as sp:
            t0 = time.perf_counter()
            fm, ticks = _drive_fleet(fp, n, budget)
            dt_f = time.perf_counter() - t0
            sp.set(moves=sum(len(v) for v in fm.values()))
        fleet_syncs = int(reg.deltas_since(snap).get("batch.host_syncs", 0))

        snap = reg.snapshot()
        with obs.span("bench.call", cat="bench", counters=True,
                      name="fleet.stream.serial") as sp:
            t0 = time.perf_counter()
            sm, calls = _drive_serial(planners, budget)
            dt_s = time.perf_counter() - t0
            sp.set(moves=sum(len(v) for v in sm.values()))
        serial_syncs = int(reg.deltas_since(snap).get("batch.host_syncs", 0))

        for k in range(n):
            fleet_moves[k] += fm[k]
            serial_moves[k] += sm[k]
        identical = identical and fleet_moves == serial_moves
        fleet_s = min(fleet_s, dt_f)
        serial_s = min(serial_s, dt_s)
    n_moves = sum(len(v) for v in fleet_moves.values())
    speedup = serial_s / max(fleet_s, 1e-9)
    # one sync per bucket-round: per fleet step the whole fleet costs
    # what one cluster's chunk dispatch costs
    fleet_per_step = fleet_syncs / max(ticks, 1)
    serial_per_cluster = serial_syncs / max(n, 1)
    print(f"  stream: {n} clusters, {n_moves} moves | fleet {fleet_s:.3f}s "
          f"({ticks} ticks, {fleet_syncs} syncs) vs serial {serial_s:.3f}s "
          f"({calls} calls, {serial_syncs} syncs) -> {speedup:.2f}x "
          f"identical={identical}")
    shared = (f"clusters={n};moves={n_moves};speedup={speedup:.2f}x;"
              f"identical={identical};fleet_s={fleet_s:.4f};"
              f"serial_s={serial_s:.4f}")
    return [
        {"name": "fleet.stream.fleet",
         "us_per_call": 1e6 * fleet_s / max(n_moves, 1),
         "derived": (f"{shared};ticks={ticks};host_syncs={fleet_syncs};"
                     f"syncs_per_step={fleet_per_step:.1f};"
                     f"single_cluster_sync_bound={serial_per_cluster:.1f}"),
         "git_sha": sha},
        {"name": "fleet.stream.serial",
         "us_per_call": 1e6 * serial_s / max(n_moves, 1),
         "derived": (f"{shared};plan_calls={calls};"
                     f"host_syncs={serial_syncs}"),
         "git_sha": sha},
    ]


def bench_loadgen(n: int) -> list[dict]:
    """Absorb-only lifecycles: steady-growth deltas stream into lanes
    and must absorb in place — exactly one rebuild per cluster (the
    initial pack), ever."""
    sha = git_sha()
    lg = FleetLoadGen(["steady-growth"] * n, seeds=list(range(n)),
                      quick=True)
    with obs.span("bench.call", cat="bench", counters=True,
                  name="fleet.loadgen.absorb") as sp:
        t0 = time.perf_counter()
        lg.run()
        wall = time.perf_counter() - t0
        summary = lg.summary()
        sp.set(moves=summary["total_moves"])
    max_rebuilds = max(acc["rebuilds"]
                       for acc in summary["per_cluster"].values())
    print(f"  loadgen: {n}x steady-growth, {summary['fleet_ticks']} fleet "
          f"ticks, {summary['total_moves']} moves, max_rebuilds="
          f"{max_rebuilds}, slo_hit_rate={summary['slo_hit_rate']:.2f}")
    return [{
        "name": "fleet.loadgen.absorb",
        "us_per_call": 1e6 * wall / max(summary["fleet_ticks"], 1),
        "derived": (f"clusters={n};ticks={summary['ticks']};"
                    f"fleet_ticks={summary['fleet_ticks']};"
                    f"moves={summary['total_moves']};"
                    f"max_rebuilds={max_rebuilds};"
                    f"slo_hit_rate={summary['slo_hit_rate']:.2f}"),
        "git_sha": sha,
    }]


def bench_slo(n: int, budget: int) -> list[dict]:
    """An impossible deadline must yield a valid partial plan: fewer
    moves than the unconstrained twin, every one legal on replay."""
    sha = git_sha()
    fp = _mk_fleet(n)
    fp.plan_fleet({i: 1 for i in range(n)})        # warm + pack
    service = FleetService(planner=fp, slo_seconds=0.0)
    with obs.span("bench.call", cat="bench", counters=True,
                  name="fleet.slo.partial") as sp:
        t0 = time.perf_counter()
        tick = service.tick({i: budget for i in range(n)})
        wall = time.perf_counter() - t0
        sp.set(moves=tick.total_moves)
    # validity: every returned move replays legally on a fresh twin that
    # saw the same pre-tick move
    legal = True
    for k, res in tick.results.items():
        twin = _mk_cluster(k)
        pre = create_planner("equilibrium_batch", chunk=CHUNK,
                             row_block=ROW_BLOCK, select_backend="ref",
                             legality_cache=False)
        pre.plan(twin, budget=1)                   # replays the pre-tick
        for m in res.moves:
            legal &= twin.move_is_legal(m.pg, m.slot, m.dst_osd)
            twin.apply(m)
    print(f"  slo: deadline=0s -> expired={tick.slo_expired}, "
          f"{tick.total_moves} partial moves, legal={legal}")
    return [{
        "name": "fleet.slo.partial",
        "us_per_call": 1e6 * wall,
        "derived": (f"clusters={n};slo_expired={tick.slo_expired};"
                    f"moves={tick.total_moves};legal={legal};"
                    f"budget={budget}"),
        "git_sha": sha,
    }]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small fleet, short lifecycles")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--trace-out", default=None,
                    help="keep the bench trace (*.jsonl native, otherwise "
                         "Chrome/Perfetto JSON); default: in-memory only")
    args = ap.parse_args()
    enable_compile_cache()

    n = 8                       # the acceptance point: N=8 quick clusters
    n_loadgen = 2 if args.quick else 4
    budget = 64

    # tracer first: its device counters are a jit-static flag, so
    # installing it after warmup would recompile inside the timed window
    started = not obs.enabled()
    if started:
        obs.start_tracing(args.trace_out, device_counters=True)
    rows = []
    rows += bench_stream(n, budget)
    rows += bench_loadgen(n_loadgen)
    rows += bench_slo(n, budget)
    if started:
        obs.stop_tracing()
        if args.trace_out:
            print(f"wrote trace -> {args.trace_out}")
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {len(rows)} rows -> {args.out}")


if __name__ == "__main__":
    main()
