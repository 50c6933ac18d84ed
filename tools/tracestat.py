#!/usr/bin/env python
"""Summarize a :mod:`repro.obs` trace: top spans, syncs/move, prune
rate, tail share, the absorb-vs-rebuild table.

Reads either sink format (native ``.jsonl`` or the Chrome/Perfetto
export — :func:`repro.obs.read_trace` normalizes both) and prints the
aggregate views the benchmarks and CI assert on:

* ``top spans`` — cumulative wall/CPU time and call count per span name;
* ``planner`` — per-planner plan calls, moves, host syncs per move,
  prune rate (``tail.bound_hits / tail.scan_slots``), tail share
  (``tail.tail_seconds / (selection + apply)``), recompiles;
* ``absorb vs rebuild`` — warm-path absorb runs per delta type against
  cold dense rebuilds (the warm-start economics in one table);
* ``bench rows`` (``--bench``) — recomputes each ``bench.call`` span's
  derived columns from its attached counter deltas alone, proving the
  ``BENCH_*.json`` rows derive from the trace;
* ``fleet`` (``--fleet``) — tick rollup plus a per-cluster table (plan
  wall, freshness lag, SLO hits/misses) from the fleet planner's
  ``fleet.tick`` spans and the ``planner.plan`` / ``fleet.plan``
  records nested under them;
* ``shards`` (``--shards``) — per-shard tile work from the
  ``batch.shard.*{shard=N}`` counters (the kernel telemetry each mesh
  participant streams off-device, recorded under a tracer started with
  ``device_counters=True``) and the dispatch-vs-sync split of the chunk
  loop (the ``batch.dispatch`` and ``batch.fetch`` spans; how much the
  pipelined dispatch overlapped).

``--validate`` schema-checks the records (exit 1 on problems) and
``--chrome OUT`` converts a JSONL trace for Perfetto / chrome://tracing.

    PYTHONPATH=src python tools/tracestat.py TRACE [--validate]
        [--bench] [--chrome OUT] [--top N]
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

from repro.obs import read_trace, to_chrome, validate_trace


def _fmt_s(us: float) -> str:
    return f"{us / 1e6:.3f}s"


def span_table(records: list[dict], top: int) -> list[tuple]:
    """(name, calls, wall_us, cpu_us) rows, heaviest wall first."""
    agg: dict[str, list] = defaultdict(lambda: [0, 0.0, 0.0])
    for r in records:
        if r.get("ev") != "span":
            continue
        row = agg[r["name"]]
        row[0] += 1
        row[1] += r.get("dur") or 0.0
        row[2] += r.get("cpu") or 0.0
    rows = sorted(((n, c, w, p) for n, (c, w, p) in agg.items()),
                  key=lambda r: -r[2])
    return rows[:top] if top else rows


def footer_counters(records: list[dict]) -> dict[str, float]:
    for r in reversed(records):
        if r.get("ev") == "counters":
            return r.get("values", {})
    return {}


def _labelled_total(counters: dict, prefix: str) -> float:
    return sum(v for k, v in counters.items() if k.startswith(prefix))


def derived_metrics(counters: dict) -> dict:
    """The benchmark-derived quantities, from counters alone."""
    moves = counters.get("tail.moves", 0)
    syncs = counters.get("batch.host_syncs", 0)
    slots = counters.get("tail.scan_slots", 0)
    hits = counters.get("tail.bound_hits", 0)
    sel = counters.get("tail.selection_seconds", 0.0)
    app = counters.get("tail.apply_seconds", 0.0)
    tail_s = counters.get("tail.tail_seconds", 0.0)
    return {
        "moves": int(moves),
        "tail_moves": int(counters.get("tail.tail_moves", 0)),
        "syncs": int(syncs),
        "syncs_per_move": syncs / moves if moves else 0.0,
        "bound_hits": int(hits),
        "prune_rate": hits / slots if slots else 0.0,
        "tail_share": tail_s / (sel + app) if sel + app > 0 else 0.0,
        "recompiles": int(counters.get("batch.jit_recompiles", 0)),
        "rebuilds": int(counters.get("batch.rebuilds", 0)),
        "stash_moves": int(counters.get("batch.stash_moves", 0)),
    }


def print_summary(records: list[dict], top: int) -> None:
    counters = footer_counters(records)

    print("== top spans (cumulative) ==")
    print(f"{'name':24s} {'calls':>7s} {'wall':>10s} {'cpu':>10s}")
    for name, calls, wall, cpu in span_table(records, top):
        print(f"{name:24s} {calls:7d} {_fmt_s(wall):>10s} {_fmt_s(cpu):>10s}")

    d = derived_metrics(counters)
    print("\n== planner ==")
    plans = _labelled_total(counters, "planner.plans")
    print(f"plan calls            {int(plans)}")
    print(f"moves                 {d['moves']} "
          f"(tail: {d['tail_moves']})")
    print(f"host syncs            {d['syncs']} "
          f"({d['syncs_per_move']:.2f}/move)")
    print(f"prune rate            {d['prune_rate']:.2f} "
          f"({d['bound_hits']} bound hits / "
          f"{int(counters.get('tail.scan_slots', 0))} scan slots)")
    print(f"tail share            {d['tail_share']:.2f}")
    print(f"jit recompiles        {d['recompiles']}")
    print(f"stash moves           {d['stash_moves']}")

    print("\n== absorb vs rebuild ==")
    print(f"dense rebuilds        {d['rebuilds']}")
    print(f"absorb runs           {int(counters.get('absorb.runs', 0))}")
    prefix = "absorb.deltas{type="
    for k in sorted(counters):
        if k.startswith(prefix):
            dtype = k[len(prefix):-1]
            print(f"  {dtype:20s} {int(counters[k])}")
    invs = {k: v for k, v in counters.items()
            if k.startswith("tail.invalidations")}
    if invs:
        print("certificate invalidations:")
        for k in sorted(invs):
            trig = k[k.index("{trigger=") + 9:-1]
            print(f"  {trig:20s} {int(invs[k])}")


def fleet_table(records: list[dict]) -> tuple[dict, dict]:
    """Per-cluster fleet stats from the trace alone: ``fleet.tick``
    spans (tick cadence, dispatch counts, SLO cuts) plus the per-cluster
    ``planner.plan`` spans and ``fleet.plan`` points the fleet planner
    nests under them.  Returns (tick summary, per-cluster rows)."""
    ticks = {"ticks": 0, "wall_us": 0.0, "rounds": 0, "chunks": 0,
             "slo_expired": 0}
    per: dict[str, dict] = defaultdict(lambda: {
        "plans": 0, "moves": 0, "wall_us": 0.0,
        "freshness_s": 0.0, "slo_hits": 0, "slo_misses": 0,
        "converged": False})
    for r in records:
        args = r.get("args", {})
        if r.get("ev") == "span" and r.get("name") == "fleet.tick":
            ticks["ticks"] += 1
            ticks["wall_us"] += r.get("dur") or 0.0
            ticks["rounds"] += args.get("rounds", 0)
            ticks["chunks"] += args.get("chunks", 0)
            ticks["slo_expired"] += int(bool(args.get("slo_expired")))
        elif (r.get("ev") == "span" and r.get("name") == "planner.plan"
                and args.get("planner") == "fleet"):
            row = per[str(args.get("cluster", "?"))]
            row["plans"] += 1
            row["moves"] += args.get("moves", 0)
            row["wall_us"] += r.get("dur") or 0.0
        elif r.get("ev") == "point" and r.get("name") == "fleet.plan":
            row = per[str(args.get("cluster", "?"))]
            row["freshness_s"] += args.get("freshness", 0.0)
            if args.get("slo_expired"):
                row["slo_misses"] += 1
            else:
                row["slo_hits"] += 1
            row["converged"] = bool(args.get("converged"))
    return ticks, dict(per)


def print_fleet(records: list[dict]) -> None:
    ticks, per = fleet_table(records)
    print("== fleet ticks ==")
    print(f"ticks                 {ticks['ticks']}")
    print(f"tick wall             {_fmt_s(ticks['wall_us'])}")
    print(f"bucket rounds         {ticks['rounds']} "
          f"({ticks['chunks']} vmapped chunk dispatches)")
    print(f"SLO-expired ticks     {ticks['slo_expired']}")
    if not per:
        print("no per-cluster fleet.plan records")
        return
    print("\n== per cluster ==")
    print(f"{'cluster':24s} {'plans':>6s} {'moves':>7s} {'plan wall':>10s} "
          f"{'freshness':>10s} {'slo hit/miss':>12s} {'conv':>5s}")
    for key in sorted(per):
        row = per[key]
        fresh = row["freshness_s"] / max(row["plans"], 1)
        print(f"{key:24s} {row['plans']:6d} {row['moves']:7d} "
              f"{_fmt_s(row['wall_us']):>10s} {fresh:9.3f}s "
              f"{row['slo_hits']:6d}/{row['slo_misses']:<5d} "
              f"{'yes' if row['converged'] else 'no':>5s}")


def shard_tables(records: list[dict]) -> tuple[dict, dict]:
    """Sharded-planner views from the trace alone: per-shard tile work
    from the ``batch.shard.*{shard=N}`` footer counters (the kernel's
    on-device telemetry, streamed off with the chunk results) and the
    dispatch-vs-sync split of the chunk loop: the ``batch.dispatch``
    spans against the ``batch.fetch`` spans of chunk results, with the
    share of ``batch.chunk`` spans the pipelined dispatch overlapped.
    Returns (per-shard rows, chunk rollup)."""
    counters = footer_counters(records)
    per: dict[int, dict] = defaultdict(lambda: {
        "tiles_walked": 0, "cand_tiles": 0, "wins": 0})
    for k, v in counters.items():
        name, _, label = k.partition("{")
        if not name.startswith("batch.shard.") or not label:
            continue
        shard = int(label.rstrip("}").split("=", 1)[1])
        per[shard][name[len("batch.shard."):]] = int(v)
    chunks = {"chunks": 0, "overlapped": 0, "dispatch_s": 0.0,
              "sync_s": 0.0, "wall_us": 0.0}
    for r in records:
        if r.get("ev") != "span":
            continue
        args = r.get("args", {})
        if r["name"] == "batch.chunk":
            chunks["chunks"] += 1
            chunks["overlapped"] += int(bool(args.get("overlapped")))
            chunks["wall_us"] += r.get("dur") or 0.0
        elif r["name"] == "batch.dispatch":
            chunks["dispatch_s"] += (r.get("dur") or 0.0) * 1e-6
        elif r["name"] == "batch.fetch" and args.get("what") == "chunk":
            chunks["sync_s"] += (r.get("dur") or 0.0) * 1e-6
    return dict(per), chunks


def print_shards(records: list[dict]) -> None:
    per, chunks = shard_tables(records)
    print("== shards ==")
    if not per:
        print("no batch.shard.* counters (serial engine, or no plan ran)")
    else:
        total = sum(row["tiles_walked"] for row in per.values()) or 1
        print(f"{'shard':>5s} {'tiles_walked':>13s} {'cand_tiles':>11s} "
              f"{'wins':>6s} {'tile share':>11s}")
        for shard in sorted(per):
            row = per[shard]
            print(f"{shard:5d} {row['tiles_walked']:13d} "
                  f"{row['cand_tiles']:11d} {row['wins']:6d} "
                  f"{row['tiles_walked'] / total:10.2f}")
    print("\n== chunk dispatch vs sync ==")
    if not chunks["chunks"]:
        print("no batch.chunk spans")
        return
    busy = chunks["dispatch_s"] + chunks["sync_s"]
    print(f"chunks                {chunks['chunks']} "
          f"({chunks['overlapped']} dispatched ahead, "
          f"{chunks['overlapped'] / chunks['chunks']:.2f} overlap share)")
    print(f"dispatch wall         {chunks['dispatch_s']:.3f}s "
          f"({chunks['dispatch_s'] / busy if busy else 0.0:.2f} of busy)")
    print(f"sync wall             {chunks['sync_s']:.3f}s")


def print_bench_rows(records: list[dict]) -> None:
    """Recompute each bench.call row from its counter deltas alone."""
    print("== bench rows (from trace) ==")
    for r in records:
        if r.get("ev") != "span" or r["name"] != "bench.call":
            continue
        args = r.get("args", {})
        d = derived_metrics(args.get("counters", {}))
        wall = (r.get("dur") or 0.0) / 1e6
        moves = args.get("moves", d["moves"])
        per_s = moves / wall if wall > 0 else 0.0
        print(f"{args.get('name', '?')},"
              f"moves={moves},moves_per_s={per_s:.1f},"
              f"tail_time_share={d['tail_share']:.2f},"
              f"bound_hits={d['bound_hits']},"
              f"prune_rate={d['prune_rate']:.2f},"
              f"syncs={d['syncs']}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="trace file (.jsonl or Chrome JSON)")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check the trace; exit 1 on problems")
    ap.add_argument("--bench", action="store_true",
                    help="recompute bench.call derived rows from the trace")
    ap.add_argument("--fleet", action="store_true",
                    help="per-cluster fleet table (plan wall, freshness "
                         "lag, SLO hits/misses) from fleet.tick spans")
    ap.add_argument("--shards", action="store_true",
                    help="per-shard tile-work table and the chunk "
                         "dispatch-vs-sync overlap split")
    ap.add_argument("--chrome", metavar="OUT", default=None,
                    help="write the Chrome/Perfetto conversion and exit")
    ap.add_argument("--top", type=int, default=12,
                    help="span-table row cap (0 = all)")
    args = ap.parse_args()

    records = read_trace(args.trace)
    if args.validate:
        problems = validate_trace(records)
        if problems:
            for p in problems:
                print(f"INVALID: {p}", file=sys.stderr)
            return 1
        print(f"valid trace: {len(records)} records")
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(to_chrome(records), f)
        print(f"wrote {args.chrome}")
        return 0
    print_summary(records, args.top)
    if args.fleet:
        print()
        print_fleet(records)
    if args.shards:
        print()
        print_shards(records)
    if args.bench:
        print()
        print_bench_rows(records)
    return 0


if __name__ == "__main__":
    sys.exit(main())
