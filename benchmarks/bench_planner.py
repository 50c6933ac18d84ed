"""Planner throughput: moves/sec per engine at paper scale and 2× scale.

Four engines over the same §3.1 semantics (bit-identical sequences):

* ``seed-jax``  — reproduction of the seed's ``use_jax=True`` path: a
  Python peer-occupancy rebuild per source, one jit dispatch and one
  blocking ``bool(found)`` host sync per source per move.  Kept here (not
  in the library) as the fixed baseline of the perf trajectory.
* ``jax-legacy`` — the seed path after the occ_dev gather hoist
  (planner ``equilibrium_jax_legacy``): still per-source dispatch+sync.
* ``numpy``     — the dense-NumPy engine (planner ``equilibrium``).
* ``batch``     — the device-resident chunked engine (planner
  ``equilibrium_batch``).

All three registry engines run through the unified planner API
(:func:`repro.core.planner.create_planner`), one fresh planner per timed
call — cold-start throughput, the same quantity the seed measured.

Engines are jit-warmed on a scratch copy, then timed over the same
``max_moves`` window from the same initial state (steady-state planning
throughput; one-time compile excluded — it is reported separately).
Writes ``BENCH_planner.json`` rows ``{name, us_per_call, derived,
git_sha}`` so the perf trajectory starts with this PR.

Timing and the derived tail/prune columns come from the telemetry spine
(:mod:`repro.obs`): the bench installs a tracer, wraps every timed call
in a ``bench.call`` span (``counters=True``), reads the wall time back
from the span and the tail columns from the schema-normalized
``PlanResult.stats`` the registry populated.  ``--trace-out`` keeps the
trace (default: in-memory only); ``python tools/tracestat.py`` on it
reproduces every derived row from the trace alone.

    PYTHONPATH=src python -m benchmarks.bench_planner [--quick] [--out P]
        [--trace-out P]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.run import git_sha
from repro import obs
from repro.core import EquilibriumConfig, create_planner
from repro.core.clustergen import cluster_b
from repro.core.equilibrium_batch import DONATED_CARRY
from repro.core.equilibrium_jax import DenseState, _jax_select
from repro.compile_cache import enable_compile_cache

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Seed-path reproduction (pre-hoist _pick_jax + per-move Python loop)


def _seed_pick_jax(dense, rows, src_idx, cfg, pad_rows=256):
    """The seed's _pick_jax, verbatim semantics: Python per-row peer
    rebuild, padded host arrays, one jit call + one blocking sync."""
    n = dense.n_dev
    R = len(rows)
    P = pad_rows * max(1, -(-R // pad_rows))

    def padded(a, fill=0):
        out = np.full((P,) + a.shape[1:], fill, dtype=a.dtype)
        out[:R] = a
        return out

    sizes = padded(dense.sh_size[rows].astype(np.float64), -1.0)
    cls = padded(dense.sh_class[rows], 0)
    u_src = dense.util[src_idx]
    before_src = (dense.util < u_src) | ((dense.util == u_src)
                                         & (np.arange(n) < src_idx))
    member = padded(dense.member[dense.sh_pg[rows]]
                    | ~dense.dev_in[None, :] | ~before_src[None, :], True)
    peer = np.zeros((P, n), dtype=np.int16)
    for i, r in enumerate(rows):                 # the hoisted-away loop
        lvl = dense.levels[dense.sh_level[r]]
        occ_row = dense.occ[lvl][dense.sh_pg[r], dense.sh_step[r]]
        own = dense.dev_domain[lvl][src_idx]
        peer[i] = occ_row[dense.dev_domain[lvl]]
        peer[i] -= (dense.dev_domain[lvl] == own).astype(np.int16)
    own_dom_eq = np.zeros(n, dtype=bool)
    pool_rows = dense.sh_pool[rows]
    cnt = padded(dense.pool_counts[pool_rows])
    ideal = padded(dense.ideal[pool_rows])
    src_cnt = padded(dense.pool_counts[pool_rows, src_idx])
    src_ideal = padded(dense.ideal[pool_rows, src_idx])
    i, d, found = _jax_select(
        jnp.asarray(sizes), jnp.asarray(cls), jnp.asarray(member),
        jnp.asarray(peer), jnp.asarray(own_dom_eq),
        jnp.asarray(cnt), jnp.asarray(ideal),
        jnp.asarray(src_cnt), jnp.asarray(src_ideal),
        jnp.asarray(dense.used), jnp.asarray(dense.cap),
        jnp.asarray(dense.util), dense.util_sum, dense.util_sumsq,
        jnp.asarray(dense.dev_class), src_idx, cfg.count_slack,
        cfg.headroom, cfg.min_variance_delta, n)
    if not bool(found):                          # the per-source host sync
        return None
    i = int(i)
    if i >= R:
        return None
    return int(rows[i]), int(d)


def balance_seed_jax(state, cfg):
    """The seed balance_fast(use_jax=True) outer loop."""
    dense = DenseState(state)
    movements = []
    while len(movements) < cfg.max_moves:
        src_order = np.argsort(-dense.util, kind="stable")[: cfg.k]
        picked = None
        for src_idx in src_order:
            rows = dense.source_rows(int(src_idx))
            if rows.size == 0:
                continue
            picked = _seed_pick_jax(dense, rows, int(src_idx), cfg)
            if picked is not None:
                break
        if picked is None:
            break
        row, dst_idx = picked
        mv = dense.apply_row(row, dst_idx)
        state.apply(mv)
        movements.append(mv)
    return movements, []


# ---------------------------------------------------------------------------


def _registry_engine(name, **kwargs):
    """Fresh planner per call (cold start), through the unified API.
    Returns (moves, stats) — stats carries the convergence-tail
    instrumentation (sources_tried histogram, tail wall-time share)."""
    def run(state, cfg):
        result = create_planner(name, cfg=cfg, **kwargs).plan(state)
        return result.moves, result.stats
    return run


def _seed_engine(state, cfg):
    moves, _ = balance_seed_jax(state, cfg)
    return moves, {}


#: ``batch-cache`` opts into the PR-4 cross-move legality cache (now
#: off by default — at CPU tile sizes its per-move column repair costs
#: more than fresh evaluation); its delta vs ``batch`` tracks whether
#: that trade ever flips on an accelerator backend
ENGINES = (
    ("seed-jax", _seed_engine),
    ("jax-legacy", _registry_engine("equilibrium_jax_legacy")),
    ("numpy", _registry_engine("equilibrium")),
    ("batch-cache", _registry_engine("equilibrium_batch",
                                     legality_cache=True)),
    ("batch", _registry_engine("equilibrium_batch")),
)


def _timed_call(fn, state, cfg, row_name: str):
    """One timed engine call as a ``bench.call`` span: the row's wall
    time is the span's own clock and the attached counter deltas are the
    trace-side double of the derived columns (``tools/tracestat.py
    --bench`` recomputes tail share / prune rate / syncs per row from
    them alone).  Falls back to a plain timer when no tracer is
    installed (direct bench_cluster callers)."""
    t0 = time.perf_counter()
    with obs.span("bench.call", cat="bench", counters=True,
                  name=row_name) as sp:
        mv, stats = fn(state, cfg)
        sp.set(moves=len(mv))
    return mv, stats, (sp.wall_s or time.perf_counter() - t0)


def _tail_derived(stats: dict) -> str:
    """Compact convergence-tail summary for the derived field."""
    hist = stats.get("sources_tried_hist")
    if not hist:
        return ""
    total = sum(hist.values())
    tail = stats.get("tail_moves", 0)
    secs = stats.get("moves_seconds", 0.0)
    share = stats.get("tail_seconds", 0.0) / secs if secs > 0 else 0.0
    full = ",".join(f"{t}:{hist[t]}" for t in sorted(hist, key=int))
    # PR-6 source-bound counters: scans skipped by a live certificate /
    # total source-scan slots (``tried`` counts full fullest-first ranks,
    # so skipped scans are inside the denominator)
    hits = stats.get("bound_hits", 0)
    pruned = stats.get("pruned_sources", 0)
    slots = sum(int(t) * c for t, c in hist.items())
    rate = hits / slots if slots > 0 else 0.0
    syncs = stats.get("host_syncs", 0)
    # carry-donation + dispatch-pipelining provenance: rows record the
    # engine build they measured, so regressions in either are visible
    # from the bench file alone (batch engines only — the seed/legacy
    # paths have no chunk carry to donate)
    extra = ""
    if str(stats.get("engine", "")).startswith("batch"):
        extra = (f";donated_carry={DONATED_CARRY};"
                 f"pipeline={stats.get('pipeline', 0)}")
    return (f";tail_moves={tail}/{total};tail_time_share={share:.2f};"
            f"bound_hits={hits};pruned_sources={pruned};"
            f"prune_rate={rate:.2f};syncs={syncs};tried_hist={full}{extra}")


def bench_cluster(initial, tag: str, cap: int, warm: int) -> list[dict]:
    sha = git_sha()
    rows = []
    per_s = {}
    sequences = {}
    compile_s = {}
    tail = {}
    for label, fn in ENGINES:
        t0 = time.perf_counter()
        fn(initial.copy(), EquilibriumConfig(max_moves=warm))
        compile_s[label] = time.perf_counter() - t0
        mv, stats, dt = _timed_call(fn, initial.copy(),
                                    EquilibriumConfig(max_moves=cap),
                                    f"planner.{tag}.{label}")
        per_s[label] = len(mv) / max(dt, 1e-9)
        tail[label] = _tail_derived(stats)
        sequences[label] = [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in mv]
        print(f"  {tag}.{label:13s}: {len(mv)} moves, "
              f"{1e3 * dt / max(len(mv), 1):.2f} ms/move "
              f"({per_s[label]:.1f} moves/s){tail[label]}")
    identical = all(sequences[l] == sequences["batch"] for l, _ in ENGINES)
    for label, _ in ENGINES:
        speedup = per_s[label] / per_s["seed-jax"]
        rows.append({
            "name": f"planner.{tag}.{label}",
            "us_per_call": 1e6 / max(per_s[label], 1e-9),
            "derived": (f"moves_per_s={per_s[label]:.1f};"
                        f"speedup_vs_seed={speedup:.1f}x;"
                        f"identical={identical};"
                        f"warmup_s={compile_s[label]:.1f}"
                        f"{tail[label]}"),
            "git_sha": sha,
        })
    return rows


#: the batch/batch-cache pair from ENGINES — same construction, so the
#: tail rows benchmark exactly the planners the throughput rows do —
#: plus the PR-6 source-bounds opt-out: the nobounds/batch delta is the
#: direct measure of the certificate + priority-queue tail win
TAIL_ENGINES = tuple((label, fn) for label, fn in ENGINES
                     if label.startswith("batch")) + (
    ("batch-nobounds", _registry_engine("equilibrium_batch",
                                        source_bounds=False)),)


def bench_tail(initial, tag: str, warm: int) -> list[dict]:
    """Convergence-tail benchmark: run to *full* convergence, where
    ``sources_tried > 1`` moves dominate wall time, and compare the batch
    engine against its variants: ``batch-cache`` (opt-in PR-4 cross-move
    legality cache) and ``batch-nobounds`` (no PR-6 source bounds) —
    each delta is the direct measure of that layer's tail effect.  All
    variants must emit the identical move sequence."""
    sha = git_sha()
    rows = []
    per_s = {}
    tail = {}
    counts = {}
    sequences = {}
    for label, fn in TAIL_ENGINES:
        fn(initial.copy(), EquilibriumConfig(max_moves=warm))
        mv, stats, dt = _timed_call(fn, initial.copy(), EquilibriumConfig(),
                                    f"planner.tail.{tag}.{label}")
        per_s[label] = len(mv) / max(dt, 1e-9)
        tail[label] = _tail_derived(stats)
        counts[label] = len(mv)
        sequences[label] = [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in mv]
        print(f"  tail.{tag}.{label:13s}: {len(mv)} moves to convergence, "
              f"{dt:.1f}s ({per_s[label]:.1f} moves/s){tail[label]}")
    identical = all(sequences[l] == sequences["batch"]
                    for l, _ in TAIL_ENGINES)
    for label, _ in TAIL_ENGINES:
        rows.append({
            "name": f"planner.tail.{tag}.{label}",
            "us_per_call": 1e6 / max(per_s[label], 1e-9),
            "derived": (f"moves_per_s={per_s[label]:.1f};"
                        f"converged={counts[label]};"
                        f"identical={identical}"
                        f"{tail[label]}"),
            "git_sha": sha,
        })
    return rows


def _shard_profile_subprocess(repo: str, n: int, scale: int, budget: int,
                              cache: str | None,
                              trace_dir: str | None) -> dict:
    """One CPU mesh point: ``tools/shard_profile.py`` on ``n`` forced
    host devices; returns its JSON result."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n} "
                        + env.get("XLA_FLAGS", "")).strip()
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable, os.path.join(repo, "tools", "shard_profile.py"),
           "--devices", str(n), "--scale", str(scale),
           "--budget", str(budget)]
    if cache:
        cmd += ["--cache", cache]
    if trace_dir:
        cmd += ["--trace-out", os.path.join(trace_dir, f"shard_n{n}.jsonl")]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=repo)
    if proc.returncode != 0:
        raise RuntimeError(f"shard_profile --devices {n} failed:\n"
                           f"{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_shards(devices, scale: int, budget: int, cache: str | None,
                 trace_dir: str | None = None) -> list[dict]:
    """Sharded-planner profile rows, one per mesh size.

    On a TPU host every mesh point runs in this process on
    ``jax.devices()[:N]`` (a chip belongs to one process, and this one
    already holds it).  On a CPU host JAX fixes the device count at
    process start, so each mesh point spawns ``tools/shard_profile.py``
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and the
    rows are stitched from the workers' JSON lines.  The N=1 point
    anchors ``peak_ratio_vs_n1`` — the per-device peak memory of the
    compiled chunk program, whose ~1/N scaling is the scale-out claim.
    The cluster build is pickle-cached and shared across mesh sizes."""
    import jax
    sha = git_sha()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        sys.path.insert(0, os.path.join(repo, "tools"))
        from shard_profile import load_state, profile_point
        state = load_state(scale, cache)[0]
    rows, base_peak = [], None
    for n in devices:
        if on_tpu:
            res = profile_point(
                state, n, budget,
                trace_out=(os.path.join(trace_dir, f"shard_n{n}.jsonl")
                           if trace_dir else None))
        else:
            res = _shard_profile_subprocess(repo, n, scale, budget, cache,
                                            trace_dir)
        peak = int(res["peak_bytes_per_device"])
        if base_peak is None:
            base_peak = max(peak, 1)
        mps = res.get("moves_per_s", 0.0)
        print(f"  shard.B{scale}x.n{n}: {res['osds']} OSDs, peak/device "
              f"{peak / 1e6:.2f} MB ({peak / base_peak:.2f}x of n1), "
              f"{mps} moves/s, identical={res.get('identical', 'n/a')}")
        rows.append({
            "name": f"planner.shard.B{scale}x.n{n}",
            "us_per_call": 1e6 / max(mps, 1e-9),
            "derived": (f"peak_bytes_per_device={peak};"
                        f"peak_ratio_vs_n1={peak / base_peak:.2f};"
                        f"devices={n};osds={res['osds']};"
                        f"pgs={res['pgs']};moves_per_s={mps};"
                        f"identical={res.get('identical', 'n/a')};"
                        f"donated_carry={res['donated_carry']};"
                        f"pipeline={res.get('pipeline', 0)}"),
            "git_sha": sha,
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="paper scale only, short window")
    ap.add_argument("--out", default="BENCH_planner.json")
    ap.add_argument("--trace-out", default=None,
                    help="keep the bench trace (*.jsonl native, otherwise "
                         "Chrome/Perfetto JSON); default: in-memory only")
    ap.add_argument("--shards-only", action="store_true",
                    help="emit only the planner.shard.* mesh-scaling rows")
    ap.add_argument("--shard-scale", type=int, default=8,
                    help="cluster_b scale for shard rows (8 = ~8k OSDs)")
    ap.add_argument("--shard-devices", default="1,2,4",
                    help="comma-separated mesh sizes to profile")
    ap.add_argument("--shard-budget", type=int, default=64,
                    help="timed-plan move window per mesh point")
    ap.add_argument("--shard-cache", default=None,
                    help="cluster pickle cache shared across mesh points "
                         "(default .cache/cluster_b_x{scale}.pkl)")
    ap.add_argument("--shard-trace-dir", default=None,
                    help="keep per-worker shard traces here (feeds "
                         "tools/tracestat.py --shards)")
    args = ap.parse_args()
    enable_compile_cache()

    shard_devices = [int(x) for x in args.shard_devices.split(",") if x]
    shard_cache = args.shard_cache or os.path.join(
        ".cache", f"cluster_b_x{args.shard_scale}.pkl")
    if args.shards_only:
        rows = bench_shards(shard_devices, args.shard_scale,
                            args.shard_budget, shard_cache,
                            args.shard_trace_dir)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {len(rows)} rows -> {args.out}")
        return

    cap = 120 if args.quick else 400
    warm = 16 if args.quick else 32
    scales = (1,) if args.quick else (1, 2)

    # the spine is the bench clock: spans time the calls, the registry
    # carries the per-call counters the derived columns summarize
    started = not obs.enabled()
    if started:
        obs.start_tracing(args.trace_out, device_counters=True)
    rows = []
    for scale in scales:
        t0 = time.perf_counter()
        initial = cluster_b(scale=scale)
        print(f"cluster B x{scale}: {initial.n_devices} OSDs, "
              f"{len(initial.acting)} PGs (built {time.perf_counter()-t0:.0f}s)")
        rows += bench_cluster(initial, f"B{scale}x", cap=cap, warm=warm)
        if not args.quick:
            rows += bench_tail(initial, f"B{scale}x", warm=warm)
    if args.quick:
        from repro.core.clustergen import cluster_f
        rows += bench_tail(cluster_f(), "F", warm=warm)
    else:
        # mesh-scaling profile at the 10k-OSD-scale cluster: subprocesses
        # (device count is per-process), so outside the bench trace
        rows += bench_shards(shard_devices, args.shard_scale,
                             args.shard_budget, shard_cache,
                             args.shard_trace_dir)
    if started:
        obs.stop_tracing()
        if args.trace_out:
            print(f"wrote trace -> {args.trace_out}")
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {len(rows)} rows -> {args.out}")


if __name__ == "__main__":
    main()
